"""Expert layers that ran over every token-choice, per optimizer step
(the program's ``moe_overflow_layers`` counter through the master's
page): a layer whose held rows pass its static row bound
(``models/mla_moe.py::rows_bound``) falls back to the path over all
``T*k`` rows, exact and as slow as the layer was before the bound. 0
where every layer of every step between the scrapes fitted; a program
without the bound has no such series and reads nothing."""
from benchmark.metrics._common import master_delta
from benchmark.metrics._phases import _delta

OVERFLOW_TOTAL = "edl_tpu_worker_moe_overflow_layers_total"


def read(run):
    """Counted in the worker's ``task_log`` phase, as the routed rows
    are: the growth of that phase's count between the two scrapes is
    the number of tasks the counter's growth belongs to."""
    tasks = _delta(run, "_count", "task_log")
    if not tasks or tasks <= 0:
        return None
    if not any(k.startswith(OVERFLOW_TOTAL)
               for k in run.get("master_close", {})):
        return None
    layers = master_delta(run, OVERFLOW_TOTAL, "master_close")
    return layers / (tasks * run["steps_per_task"])
