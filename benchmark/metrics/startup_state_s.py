"""The worker's ``state_init`` start-up phase, weights and optimizer
state made and on the device: ``edl_tpu_worker_startup_seconds`` on the
master's page at the window's end."""
from benchmark.metrics._phases import startup_seconds


def read(run):
    return startup_seconds(run, "state_init")
