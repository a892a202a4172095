"""Median gap between consecutive task programs on the device's
``XLA Modules`` lane: what the host takes between two tasks."""
from benchmark.lib.trace import program_gaps
from benchmark.metrics._common import median, task_programs


def read(run):
    gaps = program_gaps(task_programs(run))
    return None if not gaps else 1e3 * median(gaps)
