"""What several readers share. A reader is ``read(run) -> number or
None``; ``run`` is the harness's record of one run (``run.py``)."""


def window_tasks(run):
    return [t for t in run["tasks"]
            if run["open_t"] < t["t"] <= run["close_t"]]


def percentile(values, q):
    """Linear interpolation between closest ranks, as numpy's default."""
    values = sorted(values)
    if not values:
        return None
    pos = (len(values) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def median(values):
    return percentile(values, 0.5)


def tokens_per_second(run):
    """Tokens of the tasks that began and ended inside the window over
    the time from the first's beginning to the last's end. A task
    begins where the one before it ended (its 'trained' line)."""
    tasks = window_tasks(run)
    if not tasks:
        return None
    elapsed = tasks[-1]["t"] - run["open_t"]
    return len(tasks) * run["tokens_per_task"] / elapsed


def master_delta(run, prefix, end="master_end"):
    before, after = run.get("master_open", {}), run.get(end, {})
    return sum(v - before.get(k, 0.0) for k, v in after.items()
               if k.startswith(prefix))


def task_programs(run):
    """(start_s, dur_s, name) of the traced task programs: the spans of
    the ``XLA Modules`` lane whose name holds the traffic mix's
    ``program`` (the fused task step unless it says otherwise)."""
    trace = run.get("trace")
    name = run["traffic"].get("program", "multi_step")
    return trace.module_events(name) if trace else []


ATTENTION_OPS = r"^attn(\.\d+)?$"


def attention_seconds_per_step(run):
    """Device seconds of the attention kernels per optimizer step: the
    ``XLA Ops`` spans whose name is the attention scope's custom call,
    inside the traced task programs, over the steps those ran."""
    import re

    programs = task_programs(run)
    trace = run.get("trace")
    if not programs or trace is None:
        return None
    pattern = re.compile(ATTENTION_OPS)
    total = 0.0
    for start, dur, name in trace.lane("XLA Ops"):
        if pattern.match(name) and any(
                p[0] <= start <= p[0] + p[1] for p in programs):
            total += dur
    if total == 0.0:
        return None
    return total / (len(programs) * run["steps_per_task"])
