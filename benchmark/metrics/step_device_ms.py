"""Device time of the task program over the steps fused in it, median
over the traced programs."""
from benchmark.metrics._common import median, task_programs


def read(run):
    programs = task_programs(run)
    if not programs:
        return None
    return 1e3 * median([p[1] for p in programs]) / run["steps_per_task"]
