"""From a profiler trace to numbers.

Reads the ``*.trace.json.gz`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/`` (Chrome trace events: ``ph`` "M" rows
name processes and threads, ``ph`` "X" rows are spans with ``ts`` and
``dur`` in microseconds). Lanes under libtpu 0.0.34, looked at by hand
in PR 21 and again here: a process ``/device:TPU:0`` with threads
``XLA Modules`` (one span per executed program), ``XLA Ops`` (one per
operation inside it) and others; host threads under ``/host:CPU``.
``module_events`` is ``benchlib.module_device_events`` copied, on this
parse. Checked on ``benchmark/lib/data/small_trace.json.gz`` by
``benchmark/selfcheck.py``.
"""

import glob
import gzip
import json
import os


def find_trace(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    return paths[-1] if paths else None


class Trace:
    def __init__(self, events):
        self.process = {}
        self.thread = {}
        spans = []
        for e in events:
            ph = e.get("ph")
            if ph == "M":
                args = e.get("args") or {}
                if e.get("name") == "process_name":
                    self.process[e.get("pid")] = args.get("name") or ""
                elif e.get("name") == "thread_name":
                    self.thread[(e.get("pid"), e.get("tid"))] = (
                        args.get("name") or "")
            elif ph == "X" and "ts" in e:
                spans.append(e)
        self.spans = spans
        self.device_pids = sorted(
            pid for pid, name in self.process.items() if "/device:" in name)

    @classmethod
    def load(cls, path: str):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return cls(json.load(f).get("traceEvents", []))

    def lane(self, lane_name: str, pid=None):
        """Spans of the thread called ``lane_name`` on device ``pid``
        (default: every device), as (start_s, dur_s, name), sorted."""
        pids = self.device_pids if pid is None else [pid]
        lanes = {key for key, name in self.thread.items()
                 if name == lane_name and key[0] in pids}
        return sorted(
            (e["ts"] / 1e6, e.get("dur", 0) / 1e6, e.get("name") or "")
            for e in self.spans if (e.get("pid"), e.get("tid")) in lanes)

    def module_events(self, name_filter: str = "", pid=None):
        """(start_s, dur_s, name) per executed program on the device."""
        mods = self.lane("XLA Modules", pid)
        named = [m for m in mods if name_filter in m[2]]
        return named if name_filter else mods

    def host_spans(self):
        return sorted(
            (e["ts"] / 1e6, e.get("dur", 0) / 1e6, e.get("name") or "")
            for e in self.spans if e.get("pid") not in self.device_pids)


def union_seconds(intervals):
    """Total length of the union of (start, dur) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, dur in sorted((s, d) for s, d in intervals):
        end = start + dur
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def clipped(spans, start=None, end=None):
    """(start, dur) of each (start, dur, name) span cut to [start, end];
    spans wholly outside are dropped."""
    out = []
    for s, d, _ in spans:
        lo = s if start is None else max(s, start)
        hi = s + d if end is None else min(s + d, end)
        if hi > lo:
            out.append((lo, hi - lo))
    return out


def busy_seconds(trace: Trace, start=None, end=None) -> float:
    """Seconds in which an operation ran on the device, averaged over
    the devices: the union of the ``XLA Ops`` spans of each, inside
    [start, end] where those are given."""
    per_device = []
    for pid in trace.device_pids:
        ops = trace.lane("XLA Ops", pid)
        per_device.append(union_seconds(clipped(ops, start, end)))
    return sum(per_device) / len(per_device) if per_device else 0.0


def top_ops(trace: Trace, limit: int = 10):
    """[(name, seconds)]: the device operations that took most time.
    Container spans (a ``while`` or ``conditional`` holds its children,
    which are listed themselves) are left out."""
    totals = {}
    for _, dur, name in trace.lane("XLA Ops"):
        if name.startswith(("while", "conditional")):
            continue
        totals[name] = totals.get(name, 0.0) + dur
    return sorted(totals.items(), key=lambda kv: -kv[1])[:limit]


def idle_gaps(trace: Trace, start=None, end=None, limit: int = 10,
              min_gap: float = 20e-6):
    """[(what the host was doing, seconds)]: device idle time inside
    the traced window ([start, end] where given) by the host span that
    covers most of each gap (the innermost such span: the latest to
    start). Gaps no host span covers by half are 'unattributed'."""
    ops = trace.lane("XLA Ops", trace.device_pids[0]) \
        if trace.device_pids else []
    gaps, cur_end = [], None
    for op_start, dur in clipped(ops, start, end):
        if cur_end is not None and op_start - cur_end >= min_gap:
            gaps.append((cur_end, op_start))
        cur_end = max(cur_end or 0.0, op_start + dur)
    host = trace.host_spans()
    starts = [h[0] for h in host]
    import bisect

    totals = {}
    for gap_start, gap_end in gaps:
        length = gap_end - gap_start
        best = None
        hi = bisect.bisect_right(starts, gap_start + length / 2)
        # Walk back from the latest span that starts before the gap's
        # middle; the first that covers half the gap is the innermost.
        for start, dur, name in reversed(host[max(0, hi - 4000):hi]):
            overlap = min(start + dur, gap_end) - max(start, gap_start)
            if overlap >= length / 2:
                best = name
                break
        key = best or "unattributed"
        totals[key] = totals.get(key, 0.0) + length
    return sorted(totals.items(), key=lambda kv: -kv[1])[:limit]


def program_gaps(modules):
    """Gaps between consecutive programs: start of one minus end of
    the one before, seconds."""
    return [b[0] - (a[0] + a[1]) for a, b in zip(modules, modules[1:])]
