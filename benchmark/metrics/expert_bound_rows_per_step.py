"""The rows the expert layers' work ran over, per optimizer step (the
program's ``moe_bound_rows`` counter through the master's page): every
layer of every step runs its spread, activation, row weights and
combine over the smallest rung of its static ladder
(``models/mla_moe.py::rows_ladder``) that holds the step's held rows,
and over all ``T*k`` token-choices where they pass every rung; the
counter is that size, summed over the layers. Lower is less work around
the same grouped products: 6 layers on a rung of 32,768 read 196,608. A
program with one bound (the parent of the PR that brought the ladder)
has no such series and reads nothing."""
from benchmark.metrics._common import master_delta
from benchmark.metrics._phases import _delta

BOUND_ROWS_TOTAL = "edl_tpu_worker_moe_bound_rows_total"


def read(run):
    """Counted in the worker's ``task_log`` phase, as the routed rows
    are: the growth of that phase's count between the two scrapes is
    the number of tasks the counter's growth belongs to."""
    tasks = _delta(run, "_count", "task_log")
    if not tasks or tasks <= 0:
        return None
    if not any(k.startswith(BOUND_ROWS_TOTAL)
               for k in run.get("master_close", {})):
        return None
    rows = master_delta(run, BOUND_ROWS_TOTAL, "master_close")
    return rows / (tasks * run["steps_per_task"])
