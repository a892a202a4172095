"""What the readers of the window-and-global sparse-expert family share.
The expert layer is the second family's, so its counters and its grouped
products are read by ``_mla_moe.py``'s functions; the attention kernels
are ``attn.N`` as everywhere (``_common.py``). New here: which of those
calls are a WINDOW layer's, and the visible pairs the model's attention
calls were handed, as they reach the master's page.

The trace names an attention call ``attn.N`` whichever layer made it.
The task program's operation table (``_scopes.py``: written beside a
``--profile_dir`` trace since PR 36) has each name's Flax ``module``,
and the model names its two kinds of attention module apart:
``.../block_*/window_attn/attn`` and ``.../block_*/global_attn/attn``.
Without a table (the parent's program, a run with no trace) the window
readers return None.

Counter (``elasticdl_tpu/worker/worker.py::_log_task_counters``):
``edl_tpu_worker_attn_visible_pairs_total`` grows with every trained
task by the (query, key) pairs a head's attention calls were handed,
summed over the rows, the layers and the task's steps, read by the model
from the mask objects it gives the kernels. It is counted in the
worker's ``task_log`` phase, as the routed rows are. A program without
the counter has no such series: the reader returns None."""

import re

from benchmark.metrics._common import ATTENTION_OPS, master_delta
from benchmark.metrics._phases import _delta
from benchmark.metrics._scopes import joined, load_table, program_ops

PAIRS_TOTAL = "edl_tpu_worker_attn_visible_pairs_total"
WINDOW_MODULE = "window_attn"


def window_attention_seconds_per_step(run):
    """Device seconds per optimizer step of the ``attn.N`` calls whose
    row in the operation table lies in a window layer's module."""
    table = load_table(run)
    if table is None:
        return None
    ops, programs = program_ops(run)
    pattern = re.compile(ATTENTION_OPS)
    total = sum(
        dur for dur, name, row in joined(ops, table)
        if pattern.match(name) and WINDOW_MODULE in row["module"].split("/"))
    if not programs or total == 0.0:
        return None
    return total / (programs * run["steps_per_task"])


def visible_pairs_per_step(run):
    """Visible pairs a head's attention calls were handed per optimizer
    step, averaged over the tasks between the scrapes."""
    tasks = _delta(run, "_count", "task_log")
    if not tasks or tasks <= 0:
        return None
    if not any(k.startswith(PAIRS_TOTAL)
               for k in run.get("master_close", {})):
        return None
    pairs = master_delta(run, PAIRS_TOTAL, "master_close")
    return pairs / (tasks * run["steps_per_task"])
