"""Distributed tracing plane: spans → flight recorder → RPC context →
Perfetto export → critical path.

Covers the tracer core (nesting, discard, ring bounds, incremental
cursors, collector dedup), the zero-cost discipline when no recorder is
installed (microbenchmark guard), trace-context propagation through a
real gRPC round trip (client span → server span child, ``_trace_ctx``
stripped before the handler), serving request spans (queue-wait /
batch-assembly / predict against the submitting request's tree), the
Chrome/Perfetto exporter + ``tools/check_trace.py`` schema checker, the
critical-path straggler attribution, and the acceptance smoke: a traced
2-worker MiniCluster job whose exported JSON holds a task tree crossing
master → worker → row-service (the ``make trace-smoke`` lane).

The phase seam (``tracing.Phases``): each of its three sinks alone and
together, what it costs with no recorder and no trace window, that the
worker's phases tile a fused task's cycle, and the slow-task line.
"""

import json
import logging
import time

import numpy as np
import pytest

from elasticdl_tpu.comm.rpc import RpcServer, RpcStub
from elasticdl_tpu.observability import critical_path, tracing
from elasticdl_tpu.observability.tracing import (
    FlightRecorder,
    TraceCollector,
    Tracer,
)
from elasticdl_tpu.observability.trace_export import (
    chrome_trace,
    export_chrome_trace,
)
from tools.check_trace import check_trace


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test starts and ends with tracing off (the module global
    must never leak between tests — or into other test files)."""
    tracing.uninstall_recorder()
    yield
    tracing.uninstall_recorder()


# ---- tracer core --------------------------------------------------------


def test_spans_nest_and_record():
    rec = tracing.install_recorder(FlightRecorder(16))
    tracer = Tracer("worker", "3")
    with tracer.span("task", task_id=7) as task:
        with tracer.span("device_step") as step:
            pass
    spans = {s["name"]: s for s in rec.snapshot()}
    assert spans["device_step"]["parent_id"] == task.span_id
    assert spans["device_step"]["trace_id"] == task.trace_id
    assert spans["task"]["parent_id"] is None
    assert spans["task"]["attrs"] == {"task_id": 7}
    assert spans["task"]["role"] == "worker"
    assert spans["task"]["instance"] == "3"
    # Inner spans record before outer (they close first).
    assert rec.snapshot()[0]["name"] == "device_step"
    assert step.dur <= task.dur


def test_span_discard_and_error_attr():
    rec = tracing.install_recorder(FlightRecorder(16))
    tracer = Tracer("worker")
    with tracer.span("wait_poll") as sp:
        sp.discard()
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("nope")
    (span,) = rec.snapshot()
    assert span["name"] == "boom"
    assert span["attrs"]["error"] == "ValueError"


def test_ambient_span_inherits_role_and_process_default():
    rec = tracing.install_recorder(FlightRecorder(16))
    tracing.set_process_role("rowservice", "2")
    with tracing.span("root"):
        with Tracer("master").span("dispatch"):
            with tracing.span("inner"):
                pass
    by_name = {s["name"]: s for s in rec.snapshot()}
    assert by_name["root"]["role"] == "rowservice"
    assert by_name["root"]["instance"] == "2"
    # Ambient spans inherit the ENCLOSING span's role, not the
    # process default — the dispatch subtree stays on the master track.
    assert by_name["inner"]["role"] == "master"
    tracing.set_process_role("process")


def test_span_exit_on_other_thread_repairs_entering_stack():
    """A span held open across a generator yield can be finalized on a
    different thread (GeneratorExit during GC): exit must remove the
    span's own entry from the stack it was pushed onto — never blind-
    pop the finalizing thread's stack — so the entering thread's later
    spans don't parent under a dead trace."""
    import threading

    tracing.install_recorder(FlightRecorder(16))
    tracer = Tracer("worker")
    span = tracer.span("task")
    span.__enter__()
    other = threading.Thread(
        target=lambda: span.__exit__(None, None, None)
    )
    other.start()
    other.join()
    # The entering thread's stack was repaired: a fresh span is a ROOT.
    with tracer.span("next") as nxt:
        pass
    assert nxt.parent_id is None
    assert nxt.trace_id != span.trace_id


def test_metrics_fn_delivery_commit_only_on_success():
    """task_stream wiring for the span-cursor commit: the delivered
    callback fires only after a get_task that CARRIED a snapshot
    succeeded — never on RPC failure (failed offers must be re-offered
    by the worker) and never for snapshot-less polls."""
    from elasticdl_tpu.comm.rpc import RpcError
    from elasticdl_tpu.common.task import Task
    from elasticdl_tpu.common.constants import TaskType
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    calls = {"n": 0, "delivered": 0}

    class FlakyMaster:
        def get_task(self, metrics=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RpcError("blip", code="UNAVAILABLE")
            if calls["n"] == 2:
                # Snapshot-less poll (rate-limited): no commit.
                assert metrics is None
                return Task(task_id=-1, type=TaskType.WAIT), False
            return None, True  # finished

        def report_task_result(self, *a, **k):
            return True

    snapshots = iter([{"families": [], "spans": [{"span_id": "s"}]},
                      None, {"families": []}])
    service = TaskDataService(
        FlakyMaster(), data_reader=None, dataset_fn=None,
        minibatch_size=1, wait_sleep_secs=0.01,
        metrics_fn=lambda: next(snapshots),
        on_metrics_delivered=lambda: calls.__setitem__(
            "delivered", calls["delivered"] + 1
        ),
    )
    assert list(service.task_stream()) == []
    # Failed offer (call 1) and empty poll (call 2) commit nothing;
    # only the final successful snapshot-carrying call commits.
    assert calls["delivered"] == 1


def test_ring_bounds_and_incremental_cursor():
    rec = tracing.install_recorder(FlightRecorder(4))
    tracer = Tracer("w")
    for i in range(6):
        with tracer.span(f"s{i}"):
            pass
    assert len(rec) == 4  # oldest two evicted
    assert [s["name"] for s in rec.snapshot()] == [
        "s2", "s3", "s4", "s5"
    ]
    spans, cursor = tracing.spans_since(0)
    assert [s["name"] for s in spans] == ["s2", "s3", "s4", "s5"]
    with tracer.span("s6"):
        pass
    fresh, cursor2 = tracing.spans_since(cursor)
    assert [s["name"] for s in fresh] == ["s6"]
    assert cursor2 > cursor
    assert tracing.spans_since(cursor2) == ([], cursor2)


def test_collector_dedups_and_bounds():
    collector = TraceCollector(capacity=3)
    spans = [
        {"span_id": f"id{i}", "name": f"s{i}"} for i in range(4)
    ]
    assert collector.ingest(spans[:2]) == 2
    assert collector.ingest(spans[:2]) == 0  # dup delivery
    assert collector.ingest(spans[2:]) == 2
    assert len(collector) == 3  # FIFO-bounded: id0 evicted
    assert [s["span_id"] for s in collector.spans()] == [
        "id1", "id2", "id3"
    ]
    assert collector.ingest(None) == 0
    assert collector.ingest([{"no_id": True}, "junk"]) == 0


@pytest.mark.perf
def test_null_span_overhead_unmeasurable():
    """No recorder installed → the instrumented step loop must pay
    nothing measurable: one module-global read + a shared no-op span.
    Generous 5µs/call bound (measured ~0.3µs) keeps this robust on a
    loaded CI box while still catching an accidental allocation or
    lock on the disabled path."""
    assert not tracing.enabled()
    tracer = Tracer("worker")
    n = 20000

    def once() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("step"):
                pass
        return (time.perf_counter() - t0) / n

    per_call = min(once() for _ in range(5))
    assert per_call < 5e-6, f"null span cost {per_call * 1e6:.2f}µs"


# ---- the phase seam -----------------------------------------------------


def _phase_series(reg):
    families = {f["name"]: f for f in reg.snapshot()["families"]}
    return {
        s["labels"][0]: s
        for s in families["edl_tpu_worker_phase_seconds"]["series"]
    }


@pytest.fixture
def trace_window():
    """A ``utils.profiler.Profiler`` over tests/test_profiler.py's fake
    backend: the annotation sink is open between its ``start_trace``
    and its ``stop``, as under ``--profile_dir``."""
    from test_profiler import _FakeBackend

    from elasticdl_tpu.utils.profiler import Profiler

    fake = _FakeBackend()
    prof = Profiler("/tmp/trace", start_step=1, num_steps=100,
                    backend=fake)
    yield prof, fake
    prof.stop()
    assert tracing._ANNOTATE is None


@pytest.mark.parametrize("sinks", [
    "histogram", "recorder", "annotation", "all", "error", "discard",
    "startup",
])
def test_phase_feeds_each_sink_under_one_name(sinks, trace_window):
    """One entry, one name, three sinks: the histogram always, a span
    when a recorder is installed, ``edl:<name>`` on the profiler's
    trace only between ``start_trace`` and ``stop``."""
    from elasticdl_tpu.observability import MetricsRegistry

    prof, fake = trace_window
    reg = MetricsRegistry()
    phases = tracing.Phases(reg, Tracer("worker", "7"))
    rec = None
    if sinks in ("recorder", "all", "error", "discard"):
        rec = tracing.install_recorder(FlightRecorder(16))
    with phases.phase("fetch"):
        pass  # before the window: never annotated
    assert fake.annotations == []
    if sinks in ("annotation", "all"):
        prof.observe_step(1)
        assert fake.calls == [("start", "/tmp/trace")]

    if sinks == "error":
        with pytest.raises(KeyError):
            with phases.phase("device_step", kind="train") as ph:
                raise KeyError("boom")
    elif sinks == "discard":
        with phases.phase("device_step") as ph:
            ph.discard()
    elif sinks == "startup":
        with phases.startup("device_step") as ph:
            pass
    else:
        with phases.phase("device_step", kind="train") as ph:
            with phases.phase("dispatch"):
                pass
            ph.set(batches=2)
    assert ph.dur >= 0.0

    series = _phase_series(reg)
    if sinks == "discard":
        assert "device_step" not in series
        assert "device_step" not in phases.durations
    else:
        # The histogram observes whatever else is on, and an exception
        # inside the phase too.
        assert series["device_step"]["count"] == 1
        assert series["device_step"]["sum"] == pytest.approx(ph.dur)
        assert phases.durations["device_step"] == pytest.approx(ph.dur)
    gauges = {
        f["name"]: f for f in reg.snapshot()["families"]
    }["edl_tpu_worker_startup_seconds"]["series"]
    if sinks == "startup":
        (gauge,) = gauges
        assert gauge["labels"] == ["device_step"]
        assert gauge["value"] == pytest.approx(ph.dur)
    else:
        assert gauges == []

    spans = {s["name"]: s for s in rec.snapshot()} if rec else {}
    if sinks in ("recorder", "all"):
        step = spans["device_step"]
        assert step["instance"] == "7" and step["role"] == "worker"
        assert step["attrs"] == {"kind": "train", "batches": 2}
        assert spans["dispatch"]["parent_id"] == step["span_id"]
        assert step["dur"] == pytest.approx(ph.dur, abs=1e-3)
    elif sinks == "error":
        assert spans["device_step"]["attrs"]["error"] == "KeyError"
    elif sinks == "discard":
        assert "device_step" not in spans
    else:
        assert not tracing.enabled()

    if sinks in ("annotation", "all"):
        assert fake.annotations == ["edl:device_step", "edl:dispatch"]
        prof.stop()
        with phases.phase("fetch"):
            pass  # after the window: never annotated
        assert fake.annotations == ["edl:device_step", "edl:dispatch"]
    else:
        assert fake.annotations == []


@pytest.mark.perf
def test_phase_off_path_overhead():
    """No recorder, no trace window: a phase costs what
    ``Timing.record`` cost before it — two monotonic reads and one
    histogram observe. Timed on the thread's own CPU clock (xdist
    workers share the cores a wall clock would time), best of several
    rounds; 20µs/entry (measured ~2µs) catches a span allocated, an
    annotation entered or a registry lookup left on the always-on
    path, and is 0.02% of a 1.5 s task's ten phases."""
    from elasticdl_tpu.observability import MetricsRegistry

    assert not tracing.enabled() and tracing._ANNOTATE is None
    phases = tracing.Phases(MetricsRegistry(), Tracer("worker"))
    n = 5000

    def once() -> float:
        t0 = time.thread_time()
        for _ in range(n):
            with phases.phase("dispatch"):
                pass
        return (time.thread_time() - t0) / n

    per_call = min(once() for _ in range(7))
    assert per_call < 20e-6, f"phase cost {per_call * 1e6:.2f}µs"


class _Lines(logging.Handler):
    """Collects a program logger's messages (its loggers do not
    propagate, so caplog does not see them)."""

    def __init__(self, *loggers):
        super().__init__()
        self.lines = []
        self._loggers = loggers

    def emit(self, record):
        self.lines.append((record.levelname, record.getMessage()))

    def __enter__(self):
        for lg in self._loggers:
            lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        for lg in self._loggers:
            lg.removeHandler(self)


def _fused_mnist_cluster(tmp_path, records=256, **kw):
    from elasticdl_tpu.testing.cluster import MiniCluster
    from elasticdl_tpu.testing.data import (
        create_mnist_record_file,
        model_zoo_dir,
    )

    train = create_mnist_record_file(
        str(tmp_path / "t.rec"), records, seed=3
    )
    return MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train, minibatch_size=16,
        num_minibatches_per_task=4, fuse_task_steps=True, **kw,
    )


def test_phases_tile_the_fused_task_cycle(tmp_path):
    """A ``--fuse_task_steps`` job with the recorder on: the leaves of
    every ``task`` span cover at least 90% of it, ``device_step`` is
    ``dispatch`` + ``device_wait``, ``fetch`` is entered (with the
    task's batches and bytes), the start-up phases are kept as gauges,
    and the two lines a check outside the process reads are printed."""
    from elasticdl_tpu.master import task_dispatcher
    from elasticdl_tpu.worker import worker as worker_mod

    rec = tracing.install_recorder(FlightRecorder(4096))
    cluster = _fused_mnist_cluster(tmp_path)
    with _Lines(worker_mod.logger, task_dispatcher.logger) as log:
        cluster.run()
    assert cluster.finished
    # The worker's side: the master's own spans (its ``dispatch``
    # under get_task) are no part of the tiling.
    spans = [s for s in rec.snapshot() if s["role"] == "worker"]
    _, children = critical_path.build_index(spans)
    tasks = [s for s in spans if s["name"] == "task"
             and s["attrs"].get("type") == "training"]
    assert len(tasks) == 4
    # The cycle's leaves, and in the first task the weights.
    leaves = set(worker_mod.CYCLE_LEAVES) | {"state_init"}
    for task in tasks:
        tree = critical_path.subtree(task, children)
        covered = sum(s["dur"] for s in tree if s["name"] in leaves)
        assert covered >= 0.9 * task["dur"], (
            covered, task["dur"],
            {s["name"]: s["dur"] for s in tree},
        )
        names = [s["name"] for s in tree]
        for leaf in ("get_task", "fetch", "stack", "dispatch",
                     "device_wait", "report_version", "checkpoint",
                     "task_log", "report_task"):
            assert names.count(leaf) == 1, (leaf, names)
        (fetch,) = [s for s in tree if s["name"] == "fetch"]
        assert fetch["attrs"]["batches"] == 4
        assert fetch["attrs"]["bytes"] > 0
    steps = [s for s in spans if s["name"] == "device_step"]
    assert len(steps) == 4
    for step in steps:
        kids = children[step["span_id"]]
        assert [k["name"] for k in kids] == ["dispatch", "device_wait"]
        assert sum(k["dur"] for k in kids) >= 0.9 * step["dur"]
        assert step["attrs"]["kind"] == "train_fused"
    # Start-up, once each: weights, then the first program around the
    # first device_step.
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name["state_init"]) == 1
    (first,) = by_name["first_program"]
    assert first["parent_id"] == tasks[0]["span_id"]
    assert children[first["span_id"]][0]["name"] == "device_step"
    registry = cluster.workers[0]._metrics
    startup = {
        s["labels"][0]: s["value"]
        for f in registry.snapshot()["families"]
        if f["name"] == "edl_tpu_worker_startup_seconds"
        for s in f["series"]
    }
    assert startup["first_program"] == pytest.approx(first["dur"], abs=1e-3)
    assert startup["state_init"] > 0
    # step seconds are the device_step phase: dispatch and the wait.
    step_series = {
        s["labels"][0]: s
        for f in registry.snapshot()["families"]
        if f["name"] == "edl_tpu_worker_step_seconds"
        for s in f["series"]
    }
    assert step_series["train_fused"]["sum"] >= sum(
        s["dur"] for s in steps
    ) - 1e-3
    # The lines for a check that reads from outside.
    messages = [m for _, m in log.lines]
    trained = [m for m in messages if " trained: " in m]
    losses = [m for m in messages if " losses: [" in m]
    assert len(trained) == len(losses) == 4
    for line_t, line_l in zip(trained, losses):
        task_id = line_t.split()[1]
        assert line_l.startswith(f"Task {task_id} losses: [")
        values = [float(x) for x in
                  line_l.split("[", 1)[1].rstrip("]").split(", ")]
        assert len(values) == 4
        mean = float(line_t.rsplit("mean_loss=", 1)[1])
        assert sum(values) / 4 == pytest.approx(mean, abs=2e-6)
    dispatched = [m for m in messages if " dispatched: " in m]
    assert any(
        "type=training shard=" in m and "start=0 end=64 worker=0" in m
        for m in dispatched
    ), dispatched


def test_worker_without_a_profiler_keeps_no_shapes(tmp_path, monkeypatch):
    """No ``--profile_dir``: no ``Profiler`` is built, so the loop tells
    nobody its program, keeps no shapes and imports no parser of
    compiled text."""
    import sys

    from elasticdl_tpu.utils import profiler as step_profiler

    class Args:
        profile_dir = ""

    assert step_profiler.from_args(Args()) is None

    def never(*args, **kwargs):
        raise AssertionError("the profiler's seam was entered")

    monkeypatch.setattr(step_profiler.Profiler, "note_program", never)
    monkeypatch.setattr(step_profiler.Profiler, "observe_step", never)
    monkeypatch.delitem(
        sys.modules, "elasticdl_tpu.utils.hlo_ops", raising=False)
    cluster = _fused_mnist_cluster(tmp_path, records=128)
    assert cluster.workers[0]._profiler is None
    cluster.run()
    assert cluster.finished
    assert "elasticdl_tpu.utils.hlo_ops" not in sys.modules


def test_slow_task_line_names_the_phase(tmp_path):
    """A task made slow by a sleeping reader gets one WARNING line that
    says so: its cycle, the running median, and ``fetch`` holding the
    difference."""
    import ast

    from elasticdl_tpu.worker import worker as worker_mod

    cluster = _fused_mnist_cluster(tmp_path, records=640)
    worker = cluster.workers[0]
    reader = worker._reader
    read_records = reader.read_records
    seen = {"tasks": 0, "slept": None}

    def sleepy(task):
        seen["tasks"] += 1
        if seen["tasks"] == 8:
            seen["slept"] = task.task_id
            # On the prefetch thread: the loop waits. Longer than a
            # task's device time on a box loaded by six test workers
            # (2.4 s seen), or ``device_wait`` is the longest leaf.
            time.sleep(4.0)
        yield from read_records(task)

    reader.read_records = sleepy
    with _Lines(worker_mod.logger) as log:
        cluster.run()
    assert cluster.finished and seen["tasks"] == 10
    # One line for the task that slept (a loaded box may make another
    # task slow too: that one gets its own line).
    slow = [m for level, m in log.lines if level == "WARNING"
            and m.startswith(f"Task {seen['slept']} slow: ")]
    assert len(slow) == 1, log.lines
    line = slow[0]
    cycle = float(line.split("cycle=")[1].split("s")[0])
    median = float(line.split("median=")[1].split("s")[0])
    phases = ast.literal_eval(line.split("phases=")[1])
    assert set(phases) == set(worker_mod.CYCLE_LEAVES) | {"other"}
    assert cycle > 1.5 * median and cycle >= 4.0
    assert phases["fetch"] >= 4.0
    assert max(phases, key=phases.get) == "fetch"
    assert sum(phases.values()) == pytest.approx(cycle, abs=0.01)


# ---- RPC propagation ----------------------------------------------------


def test_trace_ctx_propagates_over_grpc():
    rec = tracing.install_recorder(FlightRecorder(64))
    seen = []
    server = RpcServer(
        "localhost:0",
        {"RowService": {"echo": lambda req: {"fields": sorted(req)}}},
        tag="rowservice/1",
    ).start()
    try:
        stub = RpcStub(f"localhost:{server.port}", "RowService")
        with Tracer("worker", "0").span("task") as task:
            resp = stub.call("echo", x=1)
        seen = resp["fields"]
    finally:
        server.stop(0)
    # The handler never sees the trace context as a payload field.
    assert seen == ["x"]
    by_name = {s["name"]: s for s in rec.snapshot()}
    client = by_name["rpc/echo"]
    srv = by_name["serve/echo"]
    assert client["parent_id"] == task.span_id
    assert srv["parent_id"] == client["span_id"]
    assert srv["trace_id"] == task.trace_id
    assert srv["role"] == "rowservice" and srv["instance"] == "1"


def test_rpc_without_recorder_sends_no_ctx():
    requests = []

    def echo(req):
        requests.append(dict(req))
        return {}

    server = RpcServer(
        "localhost:0", {"Svc": {"echo": echo}}
    ).start()
    try:
        RpcStub(f"localhost:{server.port}", "Svc").call("echo", a=1)
    finally:
        server.stop(0)
    assert requests == [{"a": 1}]  # no _trace_ctx on the wire


# ---- serving spans ------------------------------------------------------


class _SumModel:
    version = 1
    meta = {"batch_polymorphic": True}
    static_batch_size = None

    def predict(self, features):
        return np.asarray(features).sum(axis=1, keepdims=True)


class _OneModelStore:
    def current(self):
        return _SumModel()

    def stop(self):
        pass


def test_serving_request_spans():
    from elasticdl_tpu.serving.server import BatchingPredictor

    rec = tracing.install_recorder(FlightRecorder(64))
    predictor = BatchingPredictor(
        _OneModelStore(), max_batch_size=8, batch_deadline_ms=1.0,
    ).start()
    try:
        outputs, _version = predictor.submit(
            np.ones((3, 4), np.float32), timeout=10.0
        )
        assert outputs.shape == (3, 1)
    finally:
        predictor.stop()
    by_name = {s["name"]: s for s in rec.snapshot()}
    request = by_name["request"]
    assert request["role"] == "serving"
    assert request["attrs"] == {"n": 3}
    for phase in ("queue_wait", "batch_assembly", "predict"):
        span = by_name[phase]
        assert span["parent_id"] == request["span_id"]
        assert span["trace_id"] == request["trace_id"]
    assert by_name["predict"]["attrs"]["examples"] == 3


# ---- export + checker ---------------------------------------------------


def _demo_spans():
    rec = tracing.install_recorder(FlightRecorder(64))
    with Tracer("worker", "0").span("task", task_id=1):
        with Tracer("master").span("dispatch"):
            pass
        with tracing.span("device_step"):
            with Tracer("rowservice", "0").span("row_pull", rows=8):
                pass
    tracing.uninstall_recorder()
    return rec.snapshot()


def test_chrome_trace_structure_and_checker(tmp_path):
    spans = _demo_spans()
    trace = export_chrome_trace(spans, str(tmp_path / "t.json"))
    events = trace["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == len(spans)
    # One pid per (role, instance), each named via metadata.
    names = {
        e["args"]["name"] for e in meta if e["name"] == "process_name"
    }
    assert names == {"worker", "master", "rowservice"}
    # ts normalized to the earliest span; µs units; ids in args.
    assert min(e["ts"] for e in complete) == 0.0
    assert all(e["args"].get("span_id") for e in complete)
    assert check_trace(str(tmp_path / "t.json")) == []
    # The checker actually checks: break the tree and it objects.
    broken = dict(trace)
    broken["traceEvents"] = [
        e for e in events
        if e.get("cat") != "rowservice" or e["ph"] == "M"
    ]
    (tmp_path / "broken.json").write_text(json.dumps(broken))
    errors = check_trace(str(tmp_path / "broken.json"))
    assert errors and "rowservice" in errors[0]


def test_chrome_trace_empty():
    assert chrome_trace([]) == {
        "traceEvents": [], "displayTimeUnit": "ms"
    }


# ---- critical path ------------------------------------------------------


def _span(name, span_id, parent, t0, dur, **attrs):
    return {
        "name": name, "span_id": span_id, "parent_id": parent,
        "trace_id": "t", "role": "worker", "instance": "0",
        "tid": 1, "t0": t0, "dur": dur, "attrs": attrs,
    }


def test_critical_path_names_dominant_phase():
    spans = []
    # 9 fast tasks dominated by device_step, 1 straggler dominated by
    # a row pull under its step.
    for i in range(9):
        tid = f"task{i}"
        spans.append(_span("task", tid, None, i * 10.0, 1.0, task_id=i))
        spans.append(_span("device_step", f"st{i}", tid,
                           i * 10.0 + 0.1, 0.8))
    spans.append(_span("task", "task9", None, 90.0, 5.0, task_id=9))
    spans.append(_span("device_step", "st9", "task9", 90.1, 4.8))
    spans.append(_span("rpc/pull_rows", "pull9", "st9", 90.2, 4.5))
    report = critical_path.analyze(spans)
    tasks = report["tasks"]
    assert tasks["count"] == 10
    assert tasks["p50_secs"] == pytest.approx(1.0)
    assert tasks["p99_secs"] == pytest.approx(5.0)
    assert tasks["p99"]["dominant_phase"] == "device_step"
    assert tasks["p99"]["attrs"]["task_id"] == 9
    steps = report["steps"]
    # The p99 step's time sits under its row pull, and the p50/p99
    # phase means split cleanly (fast steps are all self time).
    assert steps["p99"]["dominant_phase"] == "rpc/pull_rows"
    assert steps["p50_phase_means"]["self"] == pytest.approx(0.8)
    assert steps["p99_phase_means"]["rpc/pull_rows"] == pytest.approx(4.5)
    text = critical_path.render_report(report)
    assert "dominated by [rpc/pull_rows]" in text


def test_p99_exemplar_is_rank_p99_not_max():
    """In a large group, one extreme outlier must not become the
    headline 'p99 task' (it still shows in stragglers) — the
    attributed exemplar is the span at the nearest-rank p99."""
    spans = [
        _span("task", f"t{i}", None, float(i), 1.0) for i in range(100)
    ]
    spans.append(_span("task", "outlier", None, 100.0, 100.0))
    report = critical_path.analyze(spans)
    tasks = report["tasks"]
    assert tasks["p99_secs"] == pytest.approx(1.0)
    assert tasks["p99"]["dur_secs"] == pytest.approx(1.0)
    assert tasks["stragglers"][0]["dur_secs"] == pytest.approx(100.0)


def test_critical_path_empty():
    report = critical_path.analyze([])
    assert report["tasks"] is None and report["steps"] is None
    assert "none recorded" in critical_path.render_report(report)


# ---- acceptance: traced 2-worker job → Perfetto JSON --------------------


def test_trace_smoke_end_to_end(tmp_path):
    """The ``make trace-smoke`` path inside the fast pytest lane: a
    2-worker in-process job with the recorder on, exported to Perfetto
    JSON, schema-checked (≥1 task tree crossing master → worker →
    row-service), with a critical-path report that names a dominant
    phase for the p99 step."""
    from elasticdl_tpu.observability.trace_export import run_traced_job

    spans = run_traced_job(
        str(tmp_path / "job"), model="sparse", num_workers=2,
        records=32, minibatch_size=8, num_minibatches_per_task=2,
    )
    assert not tracing.enabled()  # recorder uninstalled on the way out
    out = str(tmp_path / "TRACE.json")
    export_chrome_trace(spans, out)
    assert check_trace(out) == []
    report = critical_path.analyze(spans)
    assert report["tasks"]["count"] >= 2
    assert report["steps"]["p99"]["dominant_phase"]
    # Worker spans piggybacked to the master over real gRPC: the task
    # spans carry worker roles and task ids the dispatcher handed out.
    task_ids = {
        s["attrs"].get("task_id") for s in spans if s["name"] == "task"
    }
    assert len(task_ids) >= 2
