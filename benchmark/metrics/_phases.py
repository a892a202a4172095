"""What the readers of the program's own phases share (PR 24).

The worker enters every region of its task cycle and of its start-up
through one seam (``elasticdl_tpu/observability/tracing.py``,
``Phases``), which counts it in
``edl_tpu_worker_phase_seconds{phase}`` (a histogram: ``_sum``,
``_count``), keeps a start-up phase in
``edl_tpu_worker_startup_seconds{phase}`` (a gauge) and, while the
profiler's window is open, writes ``edl:<phase>`` on the host lane of
the device trace. The worker's registry reaches the master's page with
its snapshots, every ``--metrics_report_secs``, so the harness's two
scrapes (``master_open``, ``master_close``) see the worker as it was up
to that long before. A program without the seam has none of these
series and no such span: every reader here then returns None.
"""

from benchmark.lib.trace import clipped, program_gaps, union_seconds
from benchmark.metrics._common import task_programs

# The leaves of a training task's cycle, as the worker names them
# (``worker.CYCLE_LEAVES``; benchmark/tests holds the two lists equal).
LEAVES = (
    "get_task", "fetch", "stack", "dispatch", "device_wait",
    "report_version", "checkpoint", "task_log", "report_task",
)
ANNOTATION_PREFIX = "edl:"
_HISTOGRAM = "edl_tpu_worker_phase_seconds"
_STARTUP = "edl_tpu_worker_startup_seconds"


def _labelled(page, family, phase):
    """Values of ``family{...phase="<phase>"...}`` on a scraped page,
    one per worker."""
    label = f'phase="{phase}"'
    return [v for k, v in page.items()
            if k.startswith(family + "{") and label in k]


def _delta(run, suffix, phase):
    """Growth of the phase's ``_sum`` or ``_count`` between the two
    scrapes, over all workers; None unless both pages have it."""
    before = _labelled(run.get("master_open") or {}, _HISTOGRAM + suffix,
                       phase)
    after = _labelled(run.get("master_close") or {}, _HISTOGRAM + suffix,
                      phase)
    if not before or not after:
        return None
    return sum(after) - sum(before)


def phase_ms_per_task(run, phases):
    """Milliseconds a task cycle spends in ``phases``: for each, the
    mean of its entries between the two scrapes (sum over count, from
    the same two pages, so tasks before the first page, the cold ones,
    are no part of it) times its entries per cycle (1 on the fused
    path; the unfused path enters ``fetch`` once per minibatch), a
    cycle being one ``get_task``. None where a phase is on neither
    page or was not entered between them."""
    cycles = _delta(run, "_count", "get_task")
    if not cycles or cycles <= 0:
        return None
    total = 0.0
    for phase in phases:
        seconds = _delta(run, "_sum", phase)
        entries = _delta(run, "_count", phase)
        if seconds is None or not entries or entries <= 0:
            return None
        total += seconds / entries * max(1, round(entries / cycles))
    return 1e3 * total


def startup_seconds(run, phase):
    """``edl_tpu_worker_startup_seconds{phase}`` at the window's end:
    the slowest worker's, or None where the page has none."""
    values = _labelled(run.get("master_close") or {}, _STARTUP, phase)
    return max(values) if values else None


def leaf_spans(run):
    """(start_s, dur_s, phase) of the cycle's leaves on the trace's
    host lanes: the ``edl:<leaf>`` annotations. The profiler's JSON
    reads a name of the form ``a:b`` as an op ``a`` of type ``b``: it
    shows ``b`` and keeps the whole under ``args.long_name``, so that
    is where the prefix is looked for first."""
    trace = run.get("trace")
    if trace is None:
        return []
    wanted = {ANNOTATION_PREFIX + leaf: leaf for leaf in LEAVES}
    found = []
    for event in trace.spans:
        if event.get("pid") in trace.device_pids:
            continue
        name = (event.get("args") or {}).get("long_name") or event.get(
            "name") or ""
        if name in wanted:
            found.append((event["ts"] / 1e6, event.get("dur", 0) / 1e6,
                          wanted[name]))
    return sorted(found)


def gap_cover(run):
    """(seconds between consecutive task programs on ``XLA Modules``,
    seconds of them that the union of the leaves' spans covers), or
    None without programs, gaps or leaves."""
    programs = task_programs(run)
    spans = leaf_spans(run)
    if len(programs) < 2 or not spans:
        return None
    gaps = program_gaps(programs)
    covered = 0.0
    for (start, dur, _), gap in zip(programs, gaps):
        if gap > 0:
            covered += union_seconds(
                clipped(spans, start + dur, start + dur + gap))
    total = sum(g for g in gaps if g > 0)
    return (total, covered) if total > 0 else None
