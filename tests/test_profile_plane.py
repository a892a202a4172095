"""Continuous-profiling plane (observability/profiler.py) + exemplar-
linked histograms: overhead pin, bounded flame tables, window
semantics with an injectable clock, the master ProfileStore + /profile
endpoint, the OpenMetrics exemplar format, and the SLO-fire →
profile-and-exemplar-carrying incident bundle loop
(docs/observability.md "Continuous profiling & exemplars").
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from elasticdl_tpu.observability import profiler as profiler_mod
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.exposition import render_prometheus
from elasticdl_tpu.observability.profiler import (
    OVERFLOW_KEY,
    ProfileStore,
    SamplingProfiler,
    component_role,
    diff_profiles,
    fold_spans,
    folded_text,
    merge_windows,
    pprof_json,
    thread_class,
    top_frames,
)
from elasticdl_tpu.observability.registry import MetricsRegistry
from tools.check_profile import (
    check_bundle_profile,
    check_profile_payload,
)


@pytest.fixture(autouse=True)
def _clean_seams():
    yield
    profiler_mod.uninstall_profiler()
    tracing.uninstall_recorder()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, secs):
        self.t += secs
        return self.t


# ---- sampler semantics ---------------------------------------------------


# The pin's measurement, run in an interpreter of its own: a pass walks
# every thread of its process, so inside a test worker it would also
# walk the harness (pytest's and xdist's own stacks and threads) and
# read up to twice the cost it is defined over.
_PASS_COST_SCRIPT = """
import threading, time
from elasticdl_tpu.observability.profiler import SamplingProfiler

stop = threading.Event()

def parked(depth=12):
    if depth:
        return parked(depth - 1)
    stop.wait()

threads = [threading.Thread(target=parked, daemon=True) for _ in range(6)]
for t in threads:
    t.start()
prof = SamplingProfiler(hz=67.0, window_secs=3600.0)
for _ in range(20):
    prof.sample()  # warm the frame-name cache
best = float("inf")
for _round in range(10):
    t0 = time.thread_time()
    for _ in range(200):
        prof.sample()
    best = min(best, (time.thread_time() - t0) / 200)
stop.set()
for t in threads:
    t.join(timeout=2.0)
print(best)
"""


@pytest.mark.perf
def test_overhead_pin_under_one_percent():
    """The always-on pin: one sampling pass must be cheap enough that
    the default rate costs <= 1% of one core (the PR 4 <5µs span
    guard's sibling — ISSUE 13 acceptance). The pass cost is measured
    against RESIDENT threads parked in waits (deep stacks to walk, no
    GIL contention): a pass's true cost is its walk time — time spent
    waiting for a busy thread to release the GIL is time the worker is
    doing its own work, not profiler overhead. It is timed on the
    sampling thread's own CPU clock (``time.thread_time``): six xdist
    workers share the cores a wall clock would time, and a pass that
    was descheduled has cost the worker nothing. Best of 10 rounds
    damps what a busy sibling hyperthread still adds to CPU time; a
    regression that makes the walk 2-3x slower still fails every
    round."""
    import os
    import subprocess
    import sys

    import elasticdl_tpu

    out = subprocess.run(
        [sys.executable, "-c", _PASS_COST_SCRIPT], check=True,
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(elasticdl_tpu.__file__)),
    ).stdout
    best = float(out.strip().splitlines()[-1])
    assert best * 67.0 <= 0.01, (
        f"profiler costs {best * 67.0:.2%} of a core at 67 Hz "
        f"({best * 1e6:.0f}µs/pass) — over the 1% pin"
    )


def test_flame_table_bounded_under_stack_churn():
    """Pathological stack churn (every sample a distinct call path)
    must collapse into the overflow bucket, never grow the table past
    max_stacks."""
    prof = SamplingProfiler(
        hz=67.0, window_secs=3600.0, max_stacks=16
    )
    namespace = {"time": time}
    # 64 distinct named functions -> 64 distinct leaf frames.
    for i in range(64):
        exec(
            f"def churn_fn_{i}(evt):\n"
            f"    evt.set()\n"
            f"    time.sleep(0.5)\n",
            namespace,
        )
    for i in range(64):
        evt = threading.Event()
        t = threading.Thread(
            target=namespace[f"churn_fn_{i}"], args=(evt,),
            daemon=True,
        )
        t.start()
        evt.wait(2.0)
        prof.sample()
        # Let the sleeper die before the next round so thread count
        # stays bounded (its 0.5s sleep outlives the sample).
    windows = prof.snapshot_windows(include_open=True)
    assert windows
    table = windows[-1]["samples"]
    assert len(table) <= 16 + 1  # max_stacks + the overflow bucket
    assert OVERFLOW_KEY in table
    assert windows[-1]["dropped"] > 0


def test_window_rotation_with_injectable_clock():
    clock = FakeClock()
    prof = SamplingProfiler(
        hz=10.0, window_secs=10.0, clock=clock, role="test",
        instance="7",
    )
    for _ in range(5):
        prof.sample()
        clock.advance(1.0)
    windows, cursor = prof.windows_since(0)
    assert windows == [] and cursor == 0  # window still open
    clock.advance(6.0)  # past the 10s boundary
    prof.sample()       # rolls: closes [1000, 1011), opens a new one
    windows, cursor = prof.windows_since(0)
    assert len(windows) == 1 and cursor == 1
    w = windows[0]
    assert w["seq"] == 1
    assert w["t0"] == 1000.0 and w["t1"] == 1011.0
    assert w["sample_count"] == 5
    assert w["role"] == "test" and w["instance"] == "7"
    assert w["hz"] == 10.0
    # The post-roll sample opened a fresh accumulation.
    open_w = prof.snapshot_windows(include_open=True)[-1]
    assert open_w.get("open") and open_w["sample_count"] == 1
    # Cursor semantics: nothing new until the next close.
    again, cursor2 = prof.windows_since(cursor)
    assert again == [] and cursor2 == 1
    prof.close_window()
    newer, cursor3 = prof.windows_since(cursor)
    assert len(newer) == 1 and newer[0]["seq"] == 2 and cursor3 == 2


def test_thread_class_folding():
    assert thread_class("MainThread") == "main"
    assert thread_class("ThreadPoolExecutor-0_3") == "pool"
    assert thread_class("Thread-4 (busy)") == "thread"
    assert thread_class("rowservice-metrics-report") == (
        "rowservice-metrics-report"
    )
    assert thread_class("incident-writer") == "incident-writer"
    assert thread_class("Dummy-2") == "pool"


# ---- folded / pprof / checker -------------------------------------------


def _window(samples, passes=50, t0=0.0, t1=5.0, hz=10.0,
            threads=None):
    return {
        "seq": 1, "t0": t0, "t1": t1, "hz": hz, "role": "w",
        "instance": "0", "sample_count": passes,
        "threads": dict(threads or {"main": 1}), "samples": samples,
        "dropped": 0,
    }


def test_folded_pprof_and_checker_green():
    samples = {"main;a.f;a.g": 30, "main;a.f": 20}
    w = _window(samples)
    payload = {
        "component": "w-0",
        "window": w,
        "folded": folded_text(samples),
        "pprof": pprof_json(w),
    }
    assert folded_text(samples).splitlines()[0] == "main;a.f;a.g 30"
    assert check_profile_payload(payload) == []


def test_checker_flags_count_inconsistency_and_bad_pprof():
    # 5s at 10 Hz can't produce 500 passes.
    w = _window({"main;a.f": 500}, passes=500)
    errors = check_profile_payload({"window": w})
    assert any("window×hz" in e or "windowxhz" in e.lower()
               or "ceiling" in e for e in errors)
    # A class holding more samples than passes × its peak threads.
    w2 = _window({"main;a.f": 49, "main;a.g": 49}, passes=50)
    errors2 = check_profile_payload({"window": w2})
    assert any("class 'main'" in e for e in errors2)
    # Span-derived phases stacks are exempt from the class check.
    w3 = _window(
        {"main;a.f": 40, "phases;w/0;task;device_step": 400},
        passes=50,
    )
    assert check_profile_payload({"window": w3}) == []
    # pprof with out-of-table indices.
    w4 = _window({"main;a.f": 10})
    pp = pprof_json(w4)
    pp["samples"][0]["location_id"] = [99]
    errors4 = check_profile_payload({"window": w4, "pprof": pp})
    assert any("string table" in e for e in errors4)


def test_merge_and_diff():
    w1 = _window({"main;a.f": 10, "main;a.g": 10}, t0=0, t1=5)
    w2 = _window({"main;a.f": 30}, t0=5, t1=10)
    merged = merge_windows([w1, w2])
    assert merged["samples"] == {"main;a.f": 40, "main;a.g": 10}
    assert merged["sample_count"] == 100
    assert merged["t0"] == 0 and merged["t1"] == 10
    diff = diff_profiles(merged, w1)
    by_stack = {d["stack"]: d for d in diff}
    # a.f grew from 50% to 80% share, a.g shrank 50% -> 20%.
    assert by_stack["main;a.f"]["delta_frac"] == pytest.approx(0.3)
    assert by_stack["main;a.g"]["delta_frac"] == pytest.approx(-0.3)


def test_top_frames_self_vs_total():
    rows = top_frames({"main;a.f;a.g": 60, "main;a.f": 40}, top=10)
    by_frame = {r["frame"]: r for r in rows}
    assert by_frame["a.g"]["self"] == 60
    assert by_frame["a.f"]["self"] == 40
    assert by_frame["a.f"]["total"] == 100
    assert rows[0]["frame"] == "a.g"  # self-ordered


def test_fold_spans_self_time_weighting():
    spans = [
        {"span_id": "p", "parent_id": None, "name": "task",
         "role": "worker", "instance": "3", "dur": 1.0, "t0": 0.0},
        {"span_id": "c", "parent_id": "p", "name": "device_step",
         "role": "worker", "instance": "3", "dur": 0.6, "t0": 0.1},
    ]
    folded = fold_spans(spans, hz=10.0, role="worker", instance="3")
    # parent self = 0.4s -> 4 pseudo-samples; child = 0.6s -> 6.
    assert folded == {
        "phases;worker/3;task": 4,
        "phases;worker/3;task;device_step": 6,
    }
    # Role filter: nothing for another component.
    assert fold_spans(spans, hz=10.0, role="master") == {}


def test_component_role_mapping():
    assert component_role("") == ("master", "0")
    assert component_role("3") == ("worker", "3")
    assert component_role("rowservice-1") == ("rowservice", "1")
    assert component_role("serving-2") == ("serving", "2")
    assert component_role("router-0") == ("router", "0")


# ---- ProfileStore --------------------------------------------------------


def test_store_ingest_dedup_and_merged_window():
    store = ProfileStore()
    w1 = _window({"main;a.f": 10}, t0=100.0, t1=110.0)
    w2 = dict(_window({"main;a.g": 5}, t0=110.0, t1=120.0), seq=2)
    assert store.ingest("w1", [w1, w2]) == 2
    # Re-offering the same windows (failed-RPC re-send) is a no-op.
    assert store.ingest("w1", [w1, w2]) == 0
    merged = store.merged("w1", window_secs=50.0, now=130.0)
    assert merged["samples"] == {"main;a.f": 10, "main;a.g": 5}
    # A narrow recent window excludes the old one.
    recent = store.merged("w1", window_secs=15.0, now=130.0)
    assert recent["samples"] == {"main;a.g": 5}
    # Unknown component renders the available list.
    body = store.render("nope", window_secs=10.0)
    assert "error" in body and body["components"]


def test_store_render_with_spans_and_base():
    store = ProfileStore()
    store.ingest("3", [_window({"main;a.f": 10}, t0=0.0, t1=10.0)])
    store.ingest("3", [
        dict(_window({"main;a.f": 10, "main;a.g": 30},
                     t0=10.0, t1=20.0), seq=2),
    ])
    spans = [{
        "span_id": "s", "parent_id": None, "name": "device_step",
        "role": "worker", "instance": "3", "dur": 2.0, "t0": 12.0,
    }]
    body = store.render(
        "3", window_secs=10.0, base_secs=10.0, spans=spans, now=20.0,
    )
    assert check_profile_payload(body) == []
    # Span-derived phase stack merged into the same flame view.
    assert "phases;worker/3;device_step" in body["window"]["samples"]
    assert body["base"]["samples"] == {"main;a.f": 10}
    assert body["diff"]
    # bundle_capture: every component with data, folded text included.
    bundle = store.bundle_capture(window_secs=100.0, now=20.0)
    assert check_bundle_profile(bundle) == []
    assert "3" in bundle["components"]


def test_profile_http_route_over_metrics_plane():
    from elasticdl_tpu.observability import MetricsPlane

    plane = MetricsPlane(registry=MetricsRegistry())
    plane.ingest("2", {
        "instance": "tok", "families": [],
        "profiles": [_window({"main;a.f": 10},
                             t0=time.time() - 5, t1=time.time())],
    })
    http = plane.serve(port=0)
    try:
        base = f"http://localhost:{http.port}"
        with urllib.request.urlopen(f"{base}/profile") as resp:
            listing = json.loads(resp.read())
        assert [c["component"] for c in listing["components"]] == ["2"]
        with urllib.request.urlopen(
            f"{base}/profile?component=2&window=60"
        ) as resp:
            body = json.loads(resp.read())
        assert check_profile_payload(body) == []
        assert body["window"]["samples"] == {"main;a.f": 10}
    finally:
        plane.stop()


def test_remove_worker_drops_profiles():
    from elasticdl_tpu.observability import MetricsPlane

    plane = MetricsPlane(registry=MetricsRegistry())
    plane.ingest("2", {
        "instance": "tok", "families": [],
        "profiles": [_window({"main;a.f": 10})],
    })
    assert plane.profiles.merged("2", 1e9, now=10.0)
    plane.remove_worker("2")
    assert plane.profiles.merged("2", 1e9, now=10.0) is None


def test_reporter_piggybacks_spans_and_profiles():
    """ComponentMetricsReporter must carry the process's flight
    recorder and profiler windows to report_metrics, committing its
    cursors only on success — the row-service/router/serving path into
    the master's trace + profile stores."""
    from elasticdl_tpu.comm.rpc import RpcServer
    from elasticdl_tpu.observability import MetricsPlane
    from elasticdl_tpu.observability.reporter import (
        ComponentMetricsReporter,
    )

    plane = MetricsPlane(registry=MetricsRegistry())

    def report_metrics(request):
        plane.ingest(
            f"{request['component']}-{request['component_id']}",
            request.get("metrics"),
        )
        return {"accepted": True}

    server = RpcServer(
        "localhost:0",
        {"elasticdl_tpu.Master": {"report_metrics": report_metrics}},
    ).start()
    try:
        tracing.install_recorder(tracing.FlightRecorder(64))
        tracing.set_process_role("rowservice", "0")
        with tracing.span("row_push"):
            pass
        clock = FakeClock()
        prof = profiler_mod.install_profiler(SamplingProfiler(
            hz=10.0, window_secs=10.0, clock=clock,
            role="rowservice", instance="0",
        ))
        prof.sample()
        clock.advance(11.0)
        prof.sample()  # closes window 1
        reporter = ComponentMetricsReporter(
            f"localhost:{server.port}", "rowservice", 0,
            registry=MetricsRegistry(),
        )
        reporter.send_once()
        assert reporter.reports_sent == 1
        assert len(plane.traces) >= 1
        # now= aligned with the fake clock the windows were cut on.
        merged_kw = dict(window_secs=1e9, now=2000.0)
        assert plane.profiles.merged(
            "rowservice-0", **merged_kw
        ) is not None
        # Cursors committed: a second send re-offers nothing new.
        before = plane.profiles.merged("rowservice-0", **merged_kw)
        reporter.send_once()
        after = plane.profiles.merged("rowservice-0", **merged_kw)
        assert after["sample_count"] == before["sample_count"]
    finally:
        server.stop(0)


# ---- exemplars -----------------------------------------------------------


def test_exemplar_capture_per_bucket_and_snapshot_roundtrip():
    reg = MetricsRegistry()
    h = reg.histogram("demo_seconds", "d", exemplars=True)
    h.observe(0.02, trace_id="t-fast")
    h.observe(0.03, trace_id="t-faster")   # same bucket: latest wins
    h.observe(200.0, trace_id="t-overflow")  # past the top bucket
    h.observe(0.3)  # no ambient span, no explicit id -> no exemplar
    series = reg.snapshot()["families"][0]["series"][0]
    ex = series["exemplars"]
    buckets = reg.snapshot()["families"][0]["buckets"]
    fast_idx = str(next(
        i for i, ub in enumerate(buckets) if 0.03 <= ub
    ))
    assert ex[fast_idx][1] == "t-faster"
    assert ex[str(len(buckets))][1] == "t-overflow"  # +Inf bucket
    # msgpack-safe (the piggyback wire format).
    from elasticdl_tpu.common import tensor_utils

    tensor_utils.loads(tensor_utils.dumps(reg.snapshot()))


def test_exemplar_ambient_from_open_span():
    reg = MetricsRegistry()
    h = reg.histogram("demo_seconds", "d", exemplars=True)
    tracing.install_recorder(tracing.FlightRecorder(16))
    with tracing.span("op") as sp:
        h.observe(0.5)
        trace_id = sp.trace_id
    series = reg.snapshot()["families"][0]["series"][0]
    assert [e[1] for e in series["exemplars"].values()] == [trace_id]


def test_exemplar_flag_idempotent_redeclare():
    reg = MetricsRegistry()
    h1 = reg.histogram("demo_seconds", "d")
    h2 = reg.histogram("demo_seconds", "d", exemplars=True)
    assert h1 is h2 and h1.exemplars
    # Non-exemplar observe paths stay exemplar-free without a trace.
    h1.observe(0.1)
    assert "exemplars" not in (
        reg.snapshot()["families"][0]["series"][0]
    )


def test_exposition_exemplar_golden_file():
    """OpenMetrics exemplar format on bucket lines, pinned against a
    checked-in golden so any renderer change shows as a diff."""
    import pathlib

    reg = MetricsRegistry()
    h = reg.histogram("exemplar_seconds", "latency", ["op"],
                      buckets=(0.1, 1.0), exemplars=True)
    series = h.labels("pull")
    series.observe(0.05, trace_id="trace-fast")
    series.observe(0.5, trace_id="trace-slow")
    series.observe(7.0, trace_id="trace-overflow")
    # Pin the wall-clock stamps so the rendering is deterministic.
    with reg._lock:
        series.exemplars = {
            i: (v, tid, 1700000000.0 + i)
            for i, (v, tid, _ts) in series.exemplars.items()
        }
    text = render_prometheus(reg.snapshot(), exemplars=True)
    golden_path = (
        pathlib.Path(__file__).parent / "golden"
        / "exposition_exemplars.txt"
    )
    assert text == golden_path.read_text()
    # The CLASSIC 0.0.4 rendering must stay exemplar-free — standard
    # Prometheus parsers reject the mid-line '#' (exemplars are only
    # legal on the negotiated OpenMetrics content type).
    assert "# {" not in render_prometheus(reg.snapshot())
    # The exemplar suffix must not break the scrape parser.
    from tools.dump_metrics import parse_samples

    order, families, _helps, types = parse_samples(text)
    assert order == ["edl_tpu_exemplar_seconds"]
    names = [n for n, _l, _v in families["edl_tpu_exemplar_seconds"]]
    assert "edl_tpu_exemplar_seconds_bucket" in names


def test_metrics_endpoint_negotiates_openmetrics_exemplars():
    """/metrics stays classic 0.0.4 (no exemplar suffixes) for plain
    scrapers; an Accept naming openmetrics gets the exemplar-carrying
    OpenMetrics body with its mandatory ``# EOF`` terminator."""
    from elasticdl_tpu.observability import MetricsPlane

    reg = MetricsRegistry()
    reg.histogram("demo_seconds", "d", exemplars=True).observe(
        0.1, trace_id="t-1"
    )
    plane = MetricsPlane(registry=reg)
    http = plane.serve(port=0)
    try:
        url = f"http://localhost:{http.port}/metrics"
        with urllib.request.urlopen(url) as resp:
            classic = resp.read().decode()
            classic_type = resp.headers.get("Content-Type", "")
        assert "# {" not in classic and "0.0.4" in classic_type
        req = urllib.request.Request(url, headers={
            "Accept": "application/openmetrics-text; version=1.0.0",
        })
        with urllib.request.urlopen(req) as resp:
            om = resp.read().decode()
            om_type = resp.headers.get("Content-Type", "")
        assert '# {trace_id="t-1"}' in om
        assert om.endswith("# EOF\n")
        assert "openmetrics-text" in om_type
    finally:
        plane.stop()


def test_hot_histograms_declare_exemplars():
    """The ISSUE-named hot families must be exemplar-enabled where
    they are declared (a refactor silently dropping the flag would
    blind every incident bundle)."""
    from elasticdl_tpu.embedding.optimizer import (
        SGD,
        HostOptimizerWrapper,
    )
    from elasticdl_tpu.embedding.row_service import HostRowService
    from elasticdl_tpu.embedding.table import EmbeddingTable

    reg = MetricsRegistry()
    HostRowService(
        {"t": EmbeddingTable("t", 4)},
        HostOptimizerWrapper(SGD(0.1)),
        metrics_registry=reg,
    )
    fams = {
        f.name: f for f in reg._families.values()
    }
    assert fams["edl_tpu_row_service_pull_seconds"].exemplars
    assert fams["edl_tpu_row_service_push_seconds"].exemplars
    assert fams["edl_tpu_checkpoint_stall_seconds"].exemplars


# ---- SLO fire -> bundle with exemplars + profile (fast lane) -------------


def _hot_spin_for_profile(budget_ms=8.0):
    deadline = time.perf_counter() + budget_ms / 1e3
    acc = 0
    while time.perf_counter() < deadline:
        acc += 1
    return acc


class _HotOptimizer:
    """Optimizer stand-in burning a named hot function per apply."""

    def apply_gradients(self, table, ids, grads):
        _hot_spin_for_profile()
        table.set(ids, np.asarray(table.get(ids)) - 0.1 * grads)
        return table


def test_profile_drill_fast_lane(tmp_path):
    """Condensed in-process twin of ``make profile-smoke``: a REAL
    localhost row service whose pushes burn a named hot function,
    profiled at 67 Hz with tracing on; an SLO threshold rule over the
    push histogram fires and the incident bundle must carry a valid
    profile snapshot (hot function included) and >=1 exemplar trace id
    resolving in trace.json."""
    from elasticdl_tpu.comm.rpc import RpcStub, wait_for_channel_ready
    from elasticdl_tpu.embedding.row_service import HostRowService
    from elasticdl_tpu.embedding.table import EmbeddingTable
    from elasticdl_tpu.observability import MetricsPlane
    from elasticdl_tpu.observability.slo import IncidentRecorder, SLORule
    from tools.check_incident import check_incident

    reg = MetricsRegistry()
    service = HostRowService(
        {"drill": EmbeddingTable("drill", 8)}, _HotOptimizer(),
        metrics_registry=reg,
    )
    service.start("localhost:0")
    tracing.install_recorder(tracing.FlightRecorder(4096))
    tracing.set_process_role("rowservice", "0")
    prof = profiler_mod.install_profiler(SamplingProfiler(
        hz=67.0, window_secs=0.5, role="rowservice", instance="0",
    ))
    prof.start()
    plane = MetricsPlane(registry=MetricsRegistry())
    plane.enable_timeseries(cadence_secs=0.2)
    recorder = IncidentRecorder(
        str(tmp_path / "incidents"), metrics_plane=plane,
        store=plane.timeseries, background=False,
    )
    plane.enable_slo(
        rules=[SLORule(
            name="push-slow", kind="threshold",
            series="edl_tpu_row_service_push_seconds",
            source="rowservice-0", aggregation="p99", op=">",
            value=0.002, window_secs=60.0, min_count=5,
        )],
        incident_recorder=recorder,
    )
    stub = None
    try:
        channel = wait_for_channel_ready(
            f"localhost:{service.port}", timeout=30.0
        )
        stub = RpcStub(channel, "RowService")
        ids = np.arange(8, dtype=np.int64)
        grads = np.full((8, 8), 0.01, np.float32)
        deadline = time.monotonic() + 30.0
        seq = 0
        while time.monotonic() < deadline:
            stub.call("push_row_grads", table="drill", ids=ids,
                      grads=grads, client="fastlane", seq=seq)
            seq += 1
            # The piggyback path, driven by hand: snapshot + spans +
            # profile windows into the plane, exactly what the
            # reporter/worker piggyback ships.
            snapshot = reg.snapshot()
            spans, _ = tracing.spans_since(0)
            snapshot["spans"] = spans
            windows, _ = profiler_mod.windows_since(0)
            snapshot["profiles"] = windows
            plane.ingest("rowservice-0", snapshot)
            plane.slo_tick()
            merged = plane.profiles.merged("rowservice-0", 300.0)
            hot_visible = merged and any(
                "_hot_spin_for_profile" in s
                for s in merged["samples"]
            )
            if plane.slo.firing() and hot_visible:
                break
        assert plane.slo.firing() == ["push-slow"]
        assert recorder.bundles
        # Re-capture now that hot windows are certainly in the store
        # (the fast lane compresses the drill's warm-up; cooldown=0
        # would flap in production, so capture a second bundle by
        # hand instead).
        recorder._last_capture.clear()
        bundle = recorder.capture(
            plane.slo.alert_state("push-slow")
        )
        errors = check_incident(
            bundle, require_profile=True, require_exemplars=True
        )
        assert errors == [], errors
        with open(f"{bundle}/profile.json") as fh:
            profile = json.load(fh)
        folded = profile["components"]["rowservice-0"]["folded"]
        assert "_hot_spin_for_profile" in folded
        # The exemplar trace ids resolve to spans in the bundle.
        with open(f"{bundle}/exemplars.json") as fh:
            exemplars = json.load(fh)["exemplars"]
        assert exemplars
        with open(f"{bundle}/trace.json") as fh:
            events = json.load(fh)["traceEvents"]
        trace_ids = {
            (e.get("args") or {}).get("trace_id")
            for e in events if e.get("ph") == "X"
        }
        assert any(e["trace_id"] in trace_ids for e in exemplars)
    finally:
        if stub is not None:
            stub.close()
        prof.stop()
        service.stop(0)
        plane.stop()


# ---- push validation (the malformed-grads satellite) ---------------------


def test_push_rejects_malformed_grads_cleanly():
    """Wrong-dim / wrong-count / ragged / non-numeric grad blocks must
    bounce as INVALID_ARGUMENT before reaching the apply kernels (the
    PR 11 segfault), and the service must keep serving afterwards."""
    from elasticdl_tpu.comm.rpc import (
        RpcError,
        RpcStub,
        wait_for_channel_ready,
    )
    from elasticdl_tpu.embedding.optimizer import (
        SGD,
        HostOptimizerWrapper,
    )
    from elasticdl_tpu.embedding.row_service import HostRowService
    from elasticdl_tpu.embedding.table import EmbeddingTable

    service = HostRowService(
        {"t": EmbeddingTable("t", 4)},
        HostOptimizerWrapper(SGD(0.1)),
        metrics_registry=MetricsRegistry(),
    )
    service.start("localhost:0")
    stub = None
    try:
        channel = wait_for_channel_ready(
            f"localhost:{service.port}", timeout=30.0
        )
        stub = RpcStub(channel, "RowService", max_retries=0)
        bad_payloads = [
            # wrong dim (5 != 4)
            dict(table="t", ids=np.arange(3),
                 grads=np.zeros((3, 5), np.float32)),
            # wrong count (2 != 3)
            dict(table="t", ids=np.arange(3),
                 grads=np.zeros((2, 4), np.float32)),
            # 1-D block
            dict(table="t", ids=np.arange(1),
                 grads=np.zeros(4, np.float32)),
            # ragged nest
            dict(table="t", ids=[1, 2],
                 grads=[[1.0, 2.0, 3.0, 4.0], [1.0]]),
            # non-numeric
            dict(table="t", ids=[1],
                 grads=[["a", "b", "c", "d"]]),
            # unknown table
            dict(table="zzz", ids=[1],
                 grads=np.zeros((1, 4), np.float32)),
            # 2-D ids
            dict(table="t", ids=np.zeros((2, 2), np.int64),
                 grads=np.zeros((4, 4), np.float32)),
            # missing grads
            dict(table="t", ids=[1]),
            # duplicate ids (the apply contract is one update per id;
            # previously surfaced as INTERNAL via the wrapper's bare
            # ValueError)
            dict(table="t", ids=[5, 5],
                 grads=np.zeros((2, 4), np.float32)),
        ]
        for payload in bad_payloads:
            with pytest.raises(RpcError) as err:
                stub.call("push_row_grads", **payload)
            assert err.value.code == "INVALID_ARGUMENT", payload
        # The service survived every rejection: a valid push applies
        # and reads back moved rows.
        before = np.asarray(stub.call(
            "pull_rows", table="t", ids=np.arange(3)
        )["rows"])
        stub.call("push_row_grads", table="t", ids=np.arange(3),
                  grads=np.ones((3, 4), np.float32))
        after = np.asarray(stub.call(
            "pull_rows", table="t", ids=np.arange(3)
        )["rows"])
        assert not np.allclose(before, after)
        # Malformed pulls bounce cleanly too.
        with pytest.raises(RpcError) as err:
            stub.call("pull_rows", table="t", ids="garbage")
        assert err.value.code == "INVALID_ARGUMENT"
    finally:
        if stub is not None:
            stub.close()
        service.stop(0)


def test_push_validation_in_process():
    """The validators themselves (no RPC): InvalidRequest with a
    message naming the mismatch."""
    from elasticdl_tpu.comm.rpc import InvalidRequest
    from elasticdl_tpu.embedding.optimizer import (
        SGD,
        HostOptimizerWrapper,
    )
    from elasticdl_tpu.embedding.row_service import HostRowService
    from elasticdl_tpu.embedding.table import EmbeddingTable

    service = HostRowService(
        {"t": EmbeddingTable("t", 4)},
        HostOptimizerWrapper(SGD(0.1)),
        metrics_registry=MetricsRegistry(),
    )
    with pytest.raises(InvalidRequest, match="dim"):
        service._push_row_grads({
            "table": "t", "ids": [1, 2],
            "grads": np.zeros((2, 3), np.float32),
        })
    with pytest.raises(InvalidRequest, match="unknown table"):
        service._push_row_grads({
            "table": "nope", "ids": [1],
            "grads": np.zeros((1, 4), np.float32),
        })
    # A valid in-process push still works after rejections.
    out = service._push_row_grads({
        "table": "t", "ids": np.arange(2, dtype=np.int64),
        "grads": np.zeros((2, 4), np.float32),
    })
    assert out == {"map_version": 0}
