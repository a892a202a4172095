"""What the host takes to hand a task to the device: the worker's
``stack`` (the task's minibatches into one array a leaf) and
``dispatch`` (the call of the task program until it returns) phases,
mean per task between the window's two scrapes of the master's page."""
from benchmark.metrics._phases import phase_ms_per_task


def read(run):
    return phase_ms_per_task(run, ("stack", "dispatch"))
