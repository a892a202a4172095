"""What the readers of the latent-attention sparse-expert family share:
the expert layer's counters as they reach the master's page, and the
grouped expert products on the device trace.

Counters (``elasticdl_tpu/worker/worker.py::_log_task_counters``):
``edl_tpu_worker_moe_rows_total`` grows with every trained task by the
token-choices that fell on held experts, summed over the task's steps
and the expert layers; ``edl_tpu_worker_moe_expert_rows_max`` is, for
the last trained task, the largest per-step sum over expert layers of
the fullest held expert's rows. Both are counted in the worker's
``task_log`` phase, so the growth of that phase's count between the two
scrapes is the number of tasks the rows' growth belongs to. A program
without the counters has no such series: every reader returns None.

Trace: the grouped products are XLA's own ragged-dot kernels (the
program calls ``jax.lax.ragged_dot``), custom calls named
``ragged-dot-<...>.N`` on the ``XLA Ops`` lane (``ragged-dot-metadata``,
the group offsets, among them).
"""

import re

from benchmark.metrics._common import master_delta, task_programs
from benchmark.metrics._phases import _delta

ROWS_TOTAL = "edl_tpu_worker_moe_rows_total"
ROWS_MAX = "edl_tpu_worker_moe_expert_rows_max"
GROUPED_PRODUCT_OPS = r"^ragged-dot"


def routed_rows_per_step(run):
    """Token-choices of held experts per optimizer step, summed over
    the expert layers, averaged over the tasks between the scrapes."""
    tasks = _delta(run, "_count", "task_log")
    if not tasks or tasks <= 0:
        return None
    if not any(k.startswith(ROWS_TOTAL) for k in run.get("master_close", {})):
        return None
    rows = master_delta(run, ROWS_TOTAL, "master_close")
    return rows / (tasks * run["steps_per_task"])


def fullest_expert_rows(run):
    values = [v for k, v in (run.get("master_close") or {}).items()
              if k.startswith(ROWS_MAX)]
    return max(values) if values else None


def grouped_product_seconds_per_step(run):
    """Device seconds of the grouped expert products per optimizer
    step, inside the traced task programs."""
    programs = task_programs(run)
    trace = run.get("trace")
    if not programs or trace is None:
        return None
    pattern = re.compile(GROUPED_PRODUCT_OPS)
    total = 0.0
    for start, dur, name in trace.lane("XLA Ops"):
        if pattern.match(name) and any(
                p[0] <= start <= p[0] + p[1] for p in programs):
            total += dur
    if total == 0.0:
        return None
    return total / (len(programs) * run["steps_per_task"])


def roofline_pct(need, seconds, kind, label):
    """The least time the chip could take for ``need`` (operations and
    bytes) over ``seconds``; says on an earlier line which bounds it."""
    from benchmark.lib import peaks

    by_flops = need["flops"] / peaks.peak(kind, "bf16_flops_per_s")
    by_bytes = need["bytes"] / peaks.peak(kind, "hbm_bytes_per_s")
    print(f"{label}: bound by "
          f"{'operations' if by_flops >= by_bytes else 'bytes'} "
          f"({by_flops * 1e3:.3f} ms against {by_bytes * 1e3:.3f} ms)",
          flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds
