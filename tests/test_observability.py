"""Unified telemetry plane: registry → aggregation → /metrics.

Covers the observability subsystem end to end: the process-local
metrics registry (counters/gauges/histograms, labeled families), the
Prometheus text-format renderer against a golden exposition, the
stdlib HTTP endpoint (/metrics + /healthz on an ephemeral port), the
master-side cluster view (snapshot merge, TTL aging, immediate removal
on elastic resize), the phases→registry bridge, the SummaryWriter
context-manager contract, and the acceptance path: an in-process
MiniCluster run whose master /metrics aggregates ≥2 workers' step
histograms, dispatcher gauges, and embedding/row-service counters —
and drops a departed worker's series.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from elasticdl_tpu.common.timing import Timing
from elasticdl_tpu.embedding.optimizer import SGD, HostOptimizerWrapper
from elasticdl_tpu.embedding.row_service import HostRowService
from elasticdl_tpu.embedding.table import EmbeddingTable
from elasticdl_tpu.master.tensorboard_service import SummaryWriter
from elasticdl_tpu.observability import (
    ClusterMetrics,
    MetricsHTTPServer,
    MetricsPlane,
    MetricsRegistry,
    render_prometheus,
    tracing,
)
from elasticdl_tpu.testing.cluster import MiniCluster
from elasticdl_tpu.testing.data import (
    create_frappe_record_file,
    model_zoo_dir,
)
from tools.dump_metrics import fetch_metrics, main as dump_metrics_main


# ---- registry -----------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    g.inc(1)

    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)

    snap = {f["name"]: f for f in reg.snapshot()["families"]}
    assert snap["edl_tpu_reqs_total"]["series"][0]["value"] == 3.5
    assert snap["edl_tpu_depth"]["series"][0]["value"] == 6.0
    hist = snap["edl_tpu_lat_seconds"]["series"][0]
    assert hist["buckets"] == [1, 1]  # per-bucket (non-cumulative)
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(5.55)
    # Snapshots must be wire-safe (piggybacked on msgpack RPCs).
    json.dumps(reg.snapshot())


def test_labeled_families_and_redeclare():
    reg = MetricsRegistry()
    c = reg.counter("tasks_total", "tasks", ["type"])
    c.labels("train").inc()
    c.labels("train").inc()
    c.labels(type="eval").inc()
    with pytest.raises(ValueError):
        c.labels("train", "extra")
    # Idempotent re-declare returns the same family...
    assert reg.counter("tasks_total", "tasks", ["type"]) is c
    # ...but a kind or labelnames mismatch is a bug, not a merge.
    with pytest.raises(ValueError):
        reg.gauge("tasks_total", "tasks", ["type"])
    with pytest.raises(ValueError):
        reg.counter("tasks_total", "tasks", ["kind"])
    # Histograms additionally pin their buckets at first declaration.
    h = reg.histogram("lat", "l", buckets=(0.1, 1.0))
    assert reg.histogram("lat", "l", buckets=(1.0, 0.1)) is h  # order-free
    with pytest.raises(ValueError):
        reg.histogram("lat", "l", buckets=(0.5, 5.0))

    series = {
        tuple(s["labels"]): s["value"]
        for f in reg.snapshot()["families"]
        if f["name"] == "edl_tpu_tasks_total"
        for s in f["series"]
    }
    assert series == {("train",): 2.0, ("eval",): 1.0}


def test_gauge_pull_time_callback():
    reg = MetricsRegistry()
    depth = [3]
    reg.gauge("todo", "pull-time").set_function(lambda: len(depth) * 10)
    (fam,) = reg.snapshot()["families"]
    assert fam["series"][0]["value"] == 10.0
    # A dying callback must not poison the snapshot.
    reg.gauge("todo", "pull-time").set_function(
        lambda: (_ for _ in ()).throw(RuntimeError)
    )
    (fam,) = reg.snapshot()["families"]
    assert fam["series"][0]["value"] == 0.0


# ---- exposition ---------------------------------------------------------

GOLDEN = """\
# HELP edl_tpu_demo_latency_seconds Latency demo
# TYPE edl_tpu_demo_latency_seconds histogram
edl_tpu_demo_latency_seconds_bucket{le="0.1"} 1
edl_tpu_demo_latency_seconds_bucket{le="1"} 2
edl_tpu_demo_latency_seconds_bucket{le="+Inf"} 3
edl_tpu_demo_latency_seconds_sum 5.55
edl_tpu_demo_latency_seconds_count 3
# HELP edl_tpu_demo_requests_total Requests demo
# TYPE edl_tpu_demo_requests_total counter
edl_tpu_demo_requests_total{path="/ok"} 3
edl_tpu_demo_requests_total{path="a\\"b\\\\c\\nd"} 1
# HELP edl_tpu_demo_temp Temp demo
# TYPE edl_tpu_demo_temp gauge
edl_tpu_demo_temp 1.5
"""


def test_render_prometheus_golden():
    reg = MetricsRegistry()
    h = reg.histogram("demo_latency_seconds", "Latency demo",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    c = reg.counter("demo_requests_total", "Requests demo", ["path"])
    c.labels("/ok").inc(3)
    c.labels('a"b\\c\nd').inc()  # label-value escaping
    reg.gauge("demo_temp", "Temp demo").set(1.5)
    assert render_prometheus(reg.snapshot()) == GOLDEN


def test_exposition_escaping_golden_file(tmp_path):
    """Label values with ``\\``, ``"``, and newlines (and HELP text
    with both) must render escaped per exposition format 0.0.4 — an
    unescaped task name would corrupt the whole scrape. Pinned against
    a checked-in golden file so any renderer change shows as a diff."""
    import pathlib

    reg = MetricsRegistry()
    c = reg.counter("escape_total",
                    'help with \\ backslash and\nnewline', ["task"])
    c.labels('quoted "name"').inc(1)
    c.labels('back\\slash').inc(2)
    c.labels('multi\nline').inc(3)
    c.labels('all three: \\ " \n!').inc(4)
    h = reg.histogram("escape_seconds", "latency", ["op"],
                      buckets=(0.5,))
    h.labels('pull "fast"\n').observe(0.25)
    text = render_prometheus(reg.snapshot())
    golden = (
        pathlib.Path(__file__).parent / "golden"
        / "exposition_escaping.txt"
    ).read_text()
    assert text == golden
    # Every sample line survives as ONE line (raw newlines would split
    # them) and the values parse back out of the escaped text.
    lines = [l for l in text.splitlines() if not l.startswith("#")]
    assert len(lines) == 8
    for line in lines:
        assert line.rstrip().rsplit(" ", 1)[1].replace(".", "").isdigit()


def test_render_prometheus_worker_labels():
    master = MetricsRegistry()
    master.gauge("master_up", "m").set(1)
    w = MetricsRegistry()
    w.counter("worker_steps_total", "s").inc(4)
    text = render_prometheus(
        master.snapshot(), {0: w.snapshot(), 1: w.snapshot()}
    )
    # Master-local series carry no worker label; worker series do, and
    # the shared family emits ONE HELP/TYPE header.
    assert "edl_tpu_master_up 1\n" in text
    assert 'edl_tpu_worker_steps_total{worker="0"} 4' in text
    assert 'edl_tpu_worker_steps_total{worker="1"} 4' in text
    assert text.count("# TYPE edl_tpu_worker_steps_total counter") == 1


def test_http_endpoint_metrics_healthz_404():
    server = MetricsHTTPServer(lambda: "edl_tpu_up 1\n", port=0).start()
    try:
        base = f"http://localhost:{server.port}"
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4"
            )
            assert resp.read() == b"edl_tpu_up 1\n"
        with urllib.request.urlopen(f"{base}/healthz") as resp:
            assert resp.status == 200
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/nope")
        assert err.value.code == 404
    finally:
        server.stop()


# ---- aggregation --------------------------------------------------------


def _snap(**counters):
    reg = MetricsRegistry()
    for name, value in counters.items():
        reg.counter(name, "").inc(value)
    return reg.snapshot()


def test_cluster_metrics_ttl_aging_and_removal():
    cluster = ClusterMetrics(ttl_secs=10.0)
    cluster.ingest(0, _snap(steps_total=5), now=100.0)
    cluster.ingest(1, _snap(steps_total=7), now=104.0)
    assert sorted(cluster.snapshots(now=105.0)) == [0, 1]
    # Worker 0's last report ages past the TTL; worker 1 stays.
    assert sorted(cluster.snapshots(now=112.0)) == [1]
    # Elastic resize: the master removes a recovered worker immediately.
    cluster.remove_worker(1)
    assert cluster.snapshots(now=112.0) == {}
    # Invalid ids / empty snapshots are dropped at the door.
    cluster.ingest(-1, _snap(x=1))
    cluster.ingest(3, {})
    assert cluster.snapshots() == {}


def test_cluster_aggregate_sums_and_histogram_means():
    cluster = ClusterMetrics()
    reg = MetricsRegistry()
    reg.counter("steps_total", "").inc(4)
    reg.histogram("lat", "", buckets=(1.0,)).observe(0.5)
    cluster.ingest(0, reg.snapshot())
    reg2 = MetricsRegistry()
    reg2.counter("steps_total", "").inc(6)
    reg2.histogram("lat", "", buckets=(1.0,)).observe(1.5)
    cluster.ingest(1, reg2.snapshot())
    agg = cluster.aggregate()
    assert agg["edl_tpu_steps_total"] == 10.0
    assert agg["edl_tpu_lat_count"] == 2.0
    assert agg["edl_tpu_lat_mean"] == pytest.approx(1.0)


def test_aggregate_monotonic_across_departures():
    """A departed worker's counters/histograms keep counting in the
    scalar aggregate (TensorBoard totals must not regress on elastic
    resize); its gauges — point-in-time values — do not linger."""
    cluster = ClusterMetrics()
    reg = MetricsRegistry()
    reg.counter("examples_total", "").inc(100)
    reg.gauge("inflight", "").set(3)
    reg.histogram("lat", "", buckets=(1.0,)).observe(0.5)
    cluster.ingest(0, reg.snapshot())
    cluster.ingest(1, _snap(examples_total=40))

    cluster.remove_worker(0)
    agg = cluster.aggregate()
    assert agg["edl_tpu_examples_total"] == 140.0
    assert agg["edl_tpu_lat_count"] == 1.0
    assert "edl_tpu_inflight" not in agg


def test_relaunch_under_same_name_does_not_resurrect_stale_snapshot():
    """Elastic resize relaunch semantics: a worker that dies and comes
    back under the SAME worker id (new registry instance token) must
    not resurrect its dead predecessor's snapshot — not via the TTL
    path, and not when the replacement reports before the master even
    noticed the death."""
    # Path 1: death noticed via TTL aging.
    cluster = ClusterMetrics(ttl_secs=10.0)
    reg = MetricsRegistry()
    reg.counter("examples_total", "").inc(100)
    reg.gauge("inflight", "").set(7)
    cluster.ingest(0, reg.snapshot(), now=100.0)
    assert cluster.snapshots(now=120.0) == {}  # aged out
    fresh = MetricsRegistry()
    fresh.counter("examples_total", "").inc(2)
    cluster.ingest(0, fresh.snapshot(), now=121.0)
    live = cluster.snapshots(now=121.0)
    # The live view is the replacement's snapshot, not the stale one.
    (series,) = [
        s for f in live[0]["families"]
        if f["name"] == "edl_tpu_examples_total" for s in f["series"]
    ]
    assert series["value"] == 2.0
    agg = cluster.aggregate()
    # ...but the dead process's counters fold into the monotonic base.
    assert agg["edl_tpu_examples_total"] == 102.0
    # Its point-in-time gauges do NOT linger.
    assert "edl_tpu_inflight" not in agg

    # Path 2: the replacement reports while the stale snapshot is
    # still live (died and relaunched inside the TTL) — the aggregate
    # must stay monotonic instead of silently dropping to 2.
    cluster2 = ClusterMetrics(ttl_secs=1e9)
    reg2 = MetricsRegistry()
    reg2.counter("examples_total", "").inc(100)
    cluster2.ingest(0, reg2.snapshot(), now=100.0)
    fresh2 = MetricsRegistry()
    fresh2.counter("examples_total", "").inc(2)
    cluster2.ingest(0, fresh2.snapshot(), now=101.0)
    assert cluster2.aggregate()["edl_tpu_examples_total"] == 102.0
    # And the rendered per-worker series show only the live snapshot.
    text = render_prometheus(None, cluster2.snapshots(now=101.0))
    assert 'edl_tpu_examples_total{worker="0"} 2' in text
    assert "100" not in text


def test_alternating_generations_stay_bounded():
    """A stalled-but-alive old process alternating reports with its
    replacement under one worker id (the chaos stall regime) must not
    inflate the aggregate: each generation's fold is REPLACED, not
    re-added, and a generation that reports again drops its fold (its
    cumulative values ride the live snapshot)."""
    cluster = ClusterMetrics(ttl_secs=1e9)
    reg_a = MetricsRegistry()
    reg_a.counter("examples_total", "").inc(100)
    reg_b = MetricsRegistry()
    reg_b.counter("examples_total", "").inc(5)
    for round_no in range(4):
        cluster.ingest(0, reg_a.snapshot(), now=100.0 + 2 * round_no)
        cluster.ingest(0, reg_b.snapshot(), now=101.0 + 2 * round_no)
        # Live B + folded A, each at its LATEST value (A gained one
        # example per round) — never A+B+A+... compounding.
        assert cluster.aggregate()["edl_tpu_examples_total"] == (
            105.0 + round_no
        )
        reg_a.counter("examples_total", "").inc(1)  # A still training
    cluster.ingest(0, reg_a.snapshot(), now=200.0)
    assert cluster.aggregate()["edl_tpu_examples_total"] == pytest.approx(
        104.0 + 5.0  # live A (104 now) + folded B
    )


def test_fold_ledger_compacts_under_elastic_churn():
    """Long elastic jobs relaunch the same worker id many times; only
    the newest few generations stay individually keyed (bounded
    memory), older ones compact into the permanent base — totals stay
    exact either way."""
    cluster = ClusterMetrics(ttl_secs=1e9)
    for gen in range(6):
        reg = MetricsRegistry()
        reg.counter("examples_total", "").inc(10)
        cluster.ingest(0, reg.snapshot(), now=float(gen))
    # 5 replaced generations + 1 live, each worth 10.
    assert cluster.aggregate()["edl_tpu_examples_total"] == 60.0
    assert len(cluster._folds) <= ClusterMetrics._MAX_FOLDS_PER_WORKER
    assert cluster._compacted_totals["edl_tpu_examples_total"] == 10.0


def test_compacted_generation_resurrection_cancels():
    """A generation compacted into the permanent base that turns out
    to be stalled-but-alive (reports again) must cancel its compacted
    contribution — the residual error is bounded by its stall-window
    growth, never a permanent full double count."""
    cluster = ClusterMetrics(ttl_secs=1e9)
    cluster._MAX_FOLDS_PER_WORKER = 1  # force compaction quickly
    reg_a = MetricsRegistry()
    reg_a.counter("examples_total", "").inc(10)
    snap_a = reg_a.snapshot()
    cluster.ingest(0, snap_a, now=1.0)
    cluster.ingest(0, _snap(examples_total=10), now=2.0)  # B folds A
    cluster.ingest(0, _snap(examples_total=10), now=3.0)  # C: A compacts
    assert cluster._compacted_totals["edl_tpu_examples_total"] == 10.0
    # A wakes and reports again, having grown by 2 during the stall.
    reg_a.counter("examples_total", "").inc(2)
    cluster.ingest(0, reg_a.snapshot(), now=4.0)
    # Exact would be A12 + B10 + C10 = 32; the cancel leaves only the
    # 2-example stall growth as undercount — not 42 (double-counted A).
    assert cluster.aggregate()["edl_tpu_examples_total"] == 30.0


def test_print_spans_groups_interleaved_traces():
    """Two traces whose roots interleave in time still render as one
    block per trace."""
    import io

    from tools.dump_metrics import print_spans

    spans = [
        {"span_id": f"{t}{i}", "trace_id": f"tr{t}", "parent_id": None,
         "name": f"root{t}{i}", "role": "worker", "instance": "0",
         "t0": float(i * 2 + t), "dur": 0.1, "attrs": {}}
        for i in range(2) for t in range(2)  # interleaved starts
    ]
    buf = io.StringIO()
    print_spans(spans, out=buf)
    text = buf.getvalue()
    assert text.count("trace tr0") == 1
    assert text.count("trace tr1") == 1


def test_metrics_plane_collects_piggybacked_spans():
    """Worker snapshots may carry a ``spans`` key next to
    ``families``; the plane pops it into its TraceCollector (the
    cluster metrics view never sees it) and /traces-style rendering
    merges the local flight recorder in, deduped."""
    from elasticdl_tpu.observability import tracing

    plane = MetricsPlane(registry=MetricsRegistry())
    snapshot = _snap(steps_total=1)
    snapshot["spans"] = [
        {"span_id": "a", "name": "task", "trace_id": "t"},
        {"span_id": "b", "name": "device_step", "trace_id": "t",
         "parent_id": "a"},
    ]
    plane.ingest(0, snapshot)
    assert "spans" not in snapshot  # popped before the cluster view
    assert {s["span_id"] for s in plane.traces.spans()} == {"a", "b"}
    # Re-delivery (two in-process workers sharing one recorder) dedups.
    plane.ingest(1, {"instance": "x", "families": [], "spans": [
        {"span_id": "a", "name": "task", "trace_id": "t"},
    ]})
    assert len(plane.traces.spans()) == 2
    # trace_spans merges the process flight recorder (master-local
    # spans that never ride a report RPC).
    rec = tracing.install_recorder(tracing.FlightRecorder(8))
    try:
        with tracing.Tracer("master").span("dispatch"):
            pass
    finally:
        tracing.uninstall_recorder()
    assert rec.snapshot()  # sanity
    tracing.install_recorder(rec)
    try:
        names = {s["name"] for s in plane.trace_spans()}
    finally:
        tracing.uninstall_recorder()
    assert names == {"task", "device_step", "dispatch"}


def test_traces_endpoint_and_dump_metrics(capsys):
    """/traces next to /metrics + ``tools/dump_metrics.py --traces``
    pretty-printing the span tree of a live process."""
    from tools.dump_metrics import main as dump_main

    plane = MetricsPlane(registry=MetricsRegistry())
    plane.ingest(0, {
        "instance": "i", "families": [],
        "spans": [
            {"span_id": "root", "name": "task", "trace_id": "t",
             "parent_id": None, "role": "worker", "instance": "0",
             "t0": 1.0, "dur": 0.5, "attrs": {"task_id": 4}},
            {"span_id": "kid", "name": "device_step", "trace_id": "t",
             "parent_id": "root", "role": "worker", "instance": "0",
             "t0": 1.1, "dur": 0.3, "attrs": {}},
        ],
    })
    server = plane.serve(port=0)
    try:
        with urllib.request.urlopen(
            f"http://localhost:{server.port}/traces"
        ) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
        assert {s["span_id"] for s in body["spans"]} == {"root", "kid"}
        assert dump_main(
            [f"localhost:{server.port}", "--traces"]
        ) == 0
        out = capsys.readouterr().out
        assert "trace t" in out
        assert "task  [worker/0]  500.000ms  task_id=4" in out
        # The child renders indented under its parent.
        assert "    device_step" in out
    finally:
        plane.stop()


def test_aggregate_reconciles_reappearing_worker_id():
    cluster = ClusterMetrics(ttl_secs=10.0)
    reg = MetricsRegistry()
    reg.counter("examples_total", "").inc(100)
    cluster.ingest(0, reg.snapshot(), now=100.0)

    # TTL flap: the same process (same registry instance token) goes
    # silent past the TTL, then reports again with cumulative values —
    # un-retire, no double count.
    assert cluster.snapshots(now=120.0) == {}
    reg.counter("examples_total", "").inc(20)
    cluster.ingest(0, reg.snapshot(), now=121.0)
    assert cluster.aggregate()["edl_tpu_examples_total"] == 120.0

    # Replacement: a restarted process reuses worker id 0 but carries a
    # new instance token and restarted counters — the old process's
    # total folds into the base and the new counts add on top.
    cluster.remove_worker(0)
    cluster.ingest(0, _snap(examples_total=5), now=122.0)
    assert cluster.aggregate()["edl_tpu_examples_total"] == 125.0


class _FakeWriter:
    def __init__(self):
        self.calls = []

    def add_scalars(self, scalars, step):
        self.calls.append((scalars, step))


def test_metrics_plane_tensorboard_bridge():
    plane = MetricsPlane(registry=MetricsRegistry())
    writer = _FakeWriter()
    plane.set_summary_writer(writer)
    plane.publish_tensorboard(3)  # no worker data yet → no write
    assert writer.calls == []
    plane.ingest(0, _snap(steps_total=2))
    plane.publish_tensorboard(5)
    (scalars, step), = writer.calls
    assert step == 5
    assert scalars["metrics/edl_tpu_steps_total"] == 2.0
    # Called every master poll tick: identical (step, aggregates) must
    # not re-write the same tfevents frame.
    plane.publish_tensorboard(5)
    assert len(writer.calls) == 1
    plane.ingest(1, _snap(steps_total=3))
    plane.publish_tensorboard(5)
    assert len(writer.calls) == 2


# ---- phases → registry (the seam owns worker_phase_seconds) -------------


def test_phase_seam_lands_in_registry():
    """What ``Timing.publish`` did before the seam: every entry of a
    phase lands in ``edl_tpu_worker_phase_seconds{phase}``; ``Timing``
    keeps its own min/max accumulators for the local executor."""
    reg = MetricsRegistry()
    phases = tracing.Phases(reg, tracing.Tracer("worker"))
    timing = Timing(enabled=True)
    for _ in range(3):
        with phases.phase("device_step"), timing.record("batch_process"):
            pass
    stats = timing.summary()["batch_process"]
    assert stats["count"] == 3
    assert 0 <= stats["min_secs"] <= stats["max_secs"] <= stats["total_secs"]
    families = {f["name"]: f for f in reg.snapshot()["families"]}
    (series,) = families["edl_tpu_worker_phase_seconds"]["series"]
    assert series["labels"] == ["device_step"] and series["count"] == 3
    assert phases.durations["device_step"] == pytest.approx(series["sum"])


# ---- SummaryWriter contract --------------------------------------------


def test_summary_writer_context_manager_creates_parents(tmp_path):
    logdir = tmp_path / "runs" / "exp1" / "tb"  # parents don't exist
    with SummaryWriter(str(logdir)) as writer:
        writer.add_scalars({"loss": 0.5}, 1)
        writer.flush()
        events = list(logdir.glob("events.out.tfevents.*"))
        assert events and events[0].stat().st_size > 0
    with pytest.raises(ValueError):
        writer.add_scalars({"loss": 0.1}, 2)
    writer.flush()  # flush after close is a no-op, not a crash


# ---- acceptance: in-process cluster → /metrics -------------------------


def test_cluster_job_exposes_aggregated_metrics(tmp_path, capsys):
    train = create_frappe_record_file(str(tmp_path / "t.rec"), 96, seed=7)
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="deepfm.deepfm_host.custom_model",
        training_data=train,
        minibatch_size=16,
        num_minibatches_per_task=2,
        num_workers=2,
        metrics_port=0,  # ephemeral
    )
    port = cluster.metrics_http.port
    # The row plane registers its counters in the same process registry
    # the workers snapshot (the serving process IS a worker host in the
    # in-process harness); drive a pull+push so they are non-zero.
    service = HostRowService(
        {"items": EmbeddingTable("items", 4)},
        HostOptimizerWrapper(SGD(lr=0.1)),
    )
    service.handlers()["pull_rows"](
        {"table": "items", "ids": np.arange(3, dtype=np.int64)}
    )
    service.handlers()["push_row_grads"]({
        "table": "items",
        "ids": np.arange(3, dtype=np.int64),
        "grads": np.ones((3, 4), np.float32),
    })

    cluster.run()
    assert cluster.finished

    with urllib.request.urlopen(
        f"http://localhost:{port}/healthz"
    ) as resp:
        assert resp.status == 200
    text = fetch_metrics(f"localhost:{port}")

    # Worker step-latency histograms from BOTH workers.
    assert "# TYPE edl_tpu_worker_step_seconds histogram" in text
    for wid in (0, 1):
        assert (
            f'edl_tpu_worker_step_seconds_count{{kind="train",'
            f'worker="{wid}"}}'
        ) in text
    # Task-dispatcher queue gauges (drained job → zeros, but present).
    assert "edl_tpu_master_task_queue_depth 0" in text
    assert "edl_tpu_master_tasks_doing 0" in text
    assert "edl_tpu_master_tasks_dispatched_total" in text
    # Embedding-tier + row-service counters rode the worker snapshots.
    assert "edl_tpu_embedding_lookup_ids_total" in text
    assert "edl_tpu_row_service_pulled_rows_total" in text
    assert "edl_tpu_row_service_pushed_rows_total" in text
    # The worker's phases landed as histograms (the phase seam).
    for name in ("task", "get_task", "fetch", "device_step", "dispatch",
                 "device_wait", "report_version", "report_task"):
        assert (
            f'edl_tpu_worker_phase_seconds_count{{phase="{name}"' in text
        ), name
    # Start-up phases ride the snapshot as gauges.
    assert 'edl_tpu_worker_startup_seconds{phase="state_init"' in text
    assert 'edl_tpu_worker_startup_seconds{phase="first_program"' in text

    # `make metrics` / tools/dump_metrics.py works against the cluster.
    assert dump_metrics_main([f"localhost:{port}"]) == 0
    pretty = capsys.readouterr().out
    assert "edl_tpu_worker_step_seconds  [histogram]" in pretty

    # Elastic departure: a recovered/scaled-away worker's series vanish
    # immediately (the TTL path is covered in the ClusterMetrics test).
    cluster.servicer.remove_worker_metrics(1)
    text = fetch_metrics(f"localhost:{port}")
    assert 'worker="1"' not in text
    assert 'worker="0"' in text
    cluster.stop()
