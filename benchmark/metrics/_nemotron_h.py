"""What the readers of the hybrid state-space / attention / expert
family share. The expert layer is the second family's, so its counters
and its grouped products are read by ``_mla_moe.py``'s functions; the
attention kernels are ``attn.N`` as everywhere (``_common.py``). New
here: the chunked scan's kernels, custom calls named ``ssd.N`` on the
``XLA Ops`` lane (``elasticdl_tpu/ops/ssd_scan.py::SCOPE``), forward
and backward alike. A program without them has no such span: the
readers return None."""

import re

from benchmark.metrics._common import task_programs

SSD_OPS = r"^ssd(\.\d+)?$"


def ssd_seconds_per_step(run):
    """Device seconds of the scan kernels per optimizer step, inside
    the traced task programs."""
    programs = task_programs(run)
    trace = run.get("trace")
    if not programs or trace is None:
        return None
    pattern = re.compile(SSD_OPS)
    total = sum(
        dur for start, dur, name in trace.lane("XLA Ops")
        if pattern.match(name) and any(
            p[0] <= start <= p[0] + p[1] for p in programs))
    if total == 0.0:
        return None
    return total / (len(programs) * run["steps_per_task"])
