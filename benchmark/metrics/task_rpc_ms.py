"""The master's part of a task cycle as the worker sees it: its
``get_task`` (the poll, and building the task's batch stream),
``report_version`` and ``report_task`` phases, mean per task between
the window's two scrapes of the master's page."""
from benchmark.metrics._phases import phase_ms_per_task


def read(run):
    return phase_ms_per_task(
        run, ("get_task", "report_version", "report_task"))
