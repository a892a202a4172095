"""What the readers of a step's device phases share (PR 36).

The trace names a device operation and nothing else, so while a
``--profile_dir`` window is open the worker also writes what each name
belongs to: ``<trace_dir>/programs/jit_<program>.ops.json``
(``elasticdl_tpu/utils/profiler.py``, ``utils/hlo_ops.py``), one row per
instruction of the compiled task program with its ``phase``:
``forward``, ``recompute`` (a forward pass run again under remat),
``backward``, ``optimizer``, ``mixed`` (a fusion over several of those)
or ``other``. Here the ``XLA Ops`` spans inside the traced task programs
are summed by the phase of the row of their name. A program that writes
no table (the parent's), or a run with no trace, gives every reader
nothing to read.
"""

import bisect
import json
import os

from benchmark.metrics._common import task_programs

SCOPED = ("forward", "recompute", "backward", "optimizer")
CONTAINERS = ("while", "conditional")  # as ``lib/trace.py::top_ops``


def load_table(run):
    """{operation name: row} of the task program's table, or None."""
    trace_dir = run.get("trace_dir")
    if not trace_dir:
        return None
    program = run["traffic"].get("program", "multi_step")
    path = os.path.join(trace_dir, "programs", f"jit_{program}.ops.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return {row["name"]: row for row in json.load(f)["ops"]}


def program_ops(run):
    """[(dur_s, name)] of the ``XLA Ops`` spans that lie in a traced
    task program, containers left out, and the number of programs."""
    programs = task_programs(run)
    trace = run.get("trace")
    if not programs or trace is None:
        return [], 0
    starts = [p[0] for p in programs]
    found = []
    for start, dur, name in trace.lane("XLA Ops"):
        if name.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start <= programs[i][0] + programs[i][1]:
            found.append((dur, name))
    return found, len(programs)


def joined(ops, table):
    """[(dur_s, name, row)]: each span beside the row of its name; a
    name with no row counts as ``other``."""
    missing = {"phase": "other", "module": "", "op_name": ""}
    return [(dur, name, table.get(name, missing)) for dur, name in ops]


def step_ms_by_phase(run):
    """{phase: device ms a step} over the traced task programs, every
    phase of the table present (``mixed`` and ``other`` too), plus
    ``unnamed``: the part of ``other`` whose name has no row. None
    without a table, a trace or a task program on it."""
    if "_step_ms_by_phase" in run:
        return run["_step_ms_by_phase"]
    table = load_table(run)
    ops, programs = program_ops(run) if table is not None else ([], 0)
    result = None
    if ops:
        per_step = 1e3 / (programs * run["steps_per_task"])
        result = dict.fromkeys(SCOPED + ("mixed", "other", "unnamed"), 0.0)
        for dur, name, row in joined(ops, table):
            result[row["phase"]] += dur * per_step
            if name not in table:
                result["unnamed"] += dur * per_step
    run["_step_ms_by_phase"] = result
    return result


def phase_ms(run, phase):
    by_phase = step_ms_by_phase(run)
    return None if by_phase is None else by_phase[phase]


def scoped_pct(run):
    """Of the operations' time in the task programs, the share in
    operations of one of the four phases: what ``mixed`` and ``other``
    leave."""
    by_phase = step_ms_by_phase(run)
    if by_phase is None:
        return None
    total = sum(by_phase[p] for p in SCOPED + ("mixed", "other"))
    return 100.0 * sum(by_phase[p] for p in SCOPED) / total
