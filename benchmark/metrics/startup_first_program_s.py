"""The worker's ``first_program`` start-up phase, the first call of
the task program to the end of its first wait for the device (load or
compile, and the first run): ``edl_tpu_worker_startup_seconds`` on the
master's page at the window's end."""
from benchmark.metrics._phases import startup_seconds


def read(run):
    return startup_seconds(run, "first_program")
