"""The worker's ``backend_up`` start-up phase, its first device query,
which brings the TPU runtime up: ``edl_tpu_worker_startup_seconds`` on
the master's page at the window's end."""
from benchmark.metrics._phases import startup_seconds


def read(run):
    return startup_seconds(run, "backend_up")
