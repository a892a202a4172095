"""What a task cycle waits for its input: the worker's ``fetch`` phase
(on the fused path the whole ``list(batches)``: reader, decode and
prefetch behind it), mean per task between the window's two scrapes of
the master's page."""
from benchmark.metrics._phases import phase_ms_per_task


def read(run):
    return phase_ms_per_task(run, ("fetch",))
