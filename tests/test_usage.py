"""Workload attribution plane (observability/principal.py +
observability/usage.py): principal propagation over RPC, ambient
tagging, bounded-label metering, the master /usage rollup, SLO
per-workload burn rules, and the drill/checker pair
(docs/observability.md "Workload attribution").
"""

import contextlib
import json
import pathlib
import threading
import urllib.request

import pytest

from elasticdl_tpu.comm.rpc import RpcServer, RpcStub, wait_for_channel_ready
from elasticdl_tpu.observability import principal, usage
from elasticdl_tpu.observability import registry as registry_mod
from elasticdl_tpu.observability.aggregator import MetricsPlane
from elasticdl_tpu.observability.exposition import render_prometheus
from elasticdl_tpu.observability.registry import (
    MetricsRegistry,
    default_registry,
)
from tools.check_trace import check_trace
from tools.check_usage import check_usage

REPO_ROOT = pathlib.Path(__file__).parent.parent


@pytest.fixture(autouse=True)
def _principal_hygiene():
    """Leave no ambient principal or disabled kill-switch behind."""
    yield
    principal.set_process_principal()
    principal.set_enabled(True)


@contextlib.contextmanager
def _fresh_default_registry():
    """Swap the process default registry for a clean one (and re-arm
    the job-fold ledger to it) so per-test metering is deterministic."""
    fresh = MetricsRegistry()
    old = registry_mod._DEFAULT
    registry_mod._DEFAULT = fresh
    old_gen, old_jobs = usage._fold_generation, usage._fold_jobs
    usage._fold_generation, usage._fold_jobs = fresh.generation, set()
    try:
        yield fresh
    finally:
        registry_mod._DEFAULT = old
        usage._fold_generation, usage._fold_jobs = old_gen, old_jobs


# ---- principal semantics -------------------------------------------------


def test_principal_wire_roundtrip_and_unknown_coercion():
    p = principal.Principal("tenant-a", "worker", "training")
    assert principal.from_wire(p.wire()) == p
    # Purposes are a CLOSED enum: junk coerces to unknown, never a
    # new label value.
    q = principal.Principal("tenant-a", "worker", "mining-bitcoin")
    assert q.purpose == principal.UNKNOWN
    assert principal.from_wire("not a dict") is None
    assert principal.NOBODY.purpose == principal.UNKNOWN


def test_pushed_inherits_unset_fields_from_ambient():
    with principal.pushed(job="tenant-a", component="worker",
                          purpose="training"):
        assert principal.current().job == "tenant-a"
        # Internal fan-outs override ONLY the purpose; job/component
        # ride along so migration bytes still bill the owning job.
        with principal.pushed(purpose="migration"):
            who = principal.current()
            assert (who.job, who.component, who.purpose) == (
                "tenant-a", "worker", "migration"
            )
        assert principal.current().purpose == "training"
    assert principal.current() is None


def test_process_default_reaches_other_threads():
    principal.set_process_principal(job="tenant-b",
                                    component="worker",
                                    purpose="training")
    seen = {}

    def probe():
        seen["who"] = principal.current()

    t = threading.Thread(target=probe)
    t.start()
    t.join()
    assert seen["who"].job == "tenant-b"
    # Thread-local pushes still outrank the process default.
    with principal.pushed(purpose="replay"):
        assert principal.current().purpose == "replay"


def test_kill_switch_suppresses_wire_and_metering():
    with _fresh_default_registry():
        principal.set_enabled(False)
        with principal.pushed(job="j", component="c",
                              purpose="training"):
            assert principal.current_wire() is None
            usage.meter_request(principal.current(), "Svc.m", 0.001)
            usage.meter_rows(principal.current(), "m", rows=1,
                             nbytes=8)
        names = {
            f["name"] for f in default_registry().snapshot()["families"]
        }
        assert not any("usage_" in n for n in names)
        principal.set_enabled(True)
        usage.meter_request(
            principal.Principal("j", "c", "training"), "Svc.m", 0.001
        )
        names = {
            f["name"] for f in default_registry().snapshot()["families"]
        }
        assert "edl_tpu_usage_requests_total" in names


# ---- label-cardinality bounds --------------------------------------------


def test_job_churn_folds_to_other_without_registry_growth():
    with _fresh_default_registry() as reg:
        for i in range(usage.MAX_JOBS + 40):
            usage.meter_rows(
                principal.Principal(f"job-{i}", "worker", "training"),
                "push_row_grads", rows=1, nbytes=8,
            )
        fam = next(
            f for f in reg.snapshot()["families"]
            if f["name"] == "edl_tpu_usage_rows_total"
        )
        jobs = {
            dict(zip(fam["labelnames"], s["labels"]))["job"]
            for s in fam["series"]
        }
        # MAX_JOBS distinct values + the fold bucket — churn past the
        # cap lands in __other__ instead of growing the registry.
        assert len(jobs) == usage.MAX_JOBS + 1
        assert usage.OTHER_JOB in jobs
        other = sum(
            s["value"] for s in fam["series"]
            if dict(zip(fam["labelnames"], s["labels"]))["job"]
            == usage.OTHER_JOB
        )
        assert other == 40
        # unknown rides free: it must never consume fold budget.
        assert usage.fold_job(principal.UNKNOWN) == principal.UNKNOWN
        # reset() re-arms the ledger with the bumped generation.
        reg.reset()
        assert usage.fold_job("job-late") == "job-late"


def test_redeclare_with_different_labelnames_raises():
    with _fresh_default_registry():
        usage.meter_request(
            principal.Principal("j", "c", "training"), "Svc.m", 0.001
        )
        with pytest.raises(ValueError):
            default_registry().counter(
                "usage_requests_total", "clash", ["job", "tenant"]
            )


# ---- RPC propagation -----------------------------------------------------


def test_rpc_carries_principal_and_meters_server_side():
    def echo(request):
        return {"who": principal.current().wire(),
                "echo": request.get("value")}

    server = RpcServer(
        "localhost:0", {"Echo": {"echo": echo}}
    ).start()
    try:
        with _fresh_default_registry() as reg:
            channel = wait_for_channel_ready(
                f"localhost:{server.port}", timeout=10, retries=3
            )
            stub = RpcStub(channel, "Echo")
            with principal.pushed(job="tenant-a", component="worker",
                                  purpose="training"):
                reply = stub.call("echo", value=1)
            # The handler thread saw the caller's principal ambiently.
            assert reply["who"]["job"] == "tenant-a"
            assert reply["who"]["purpose"] == "training"
            # Untagged calls meter as unknown, not as a crash.
            untagged = stub.call("echo", value=2)
            assert untagged["who"]["purpose"] == principal.UNKNOWN
            channel.close()
            fam = next(
                f for f in reg.snapshot()["families"]
                if f["name"] == "edl_tpu_usage_requests_total"
            )
            by_labels = {
                tuple(s["labels"]): s["value"] for s in fam["series"]
            }
            assert by_labels[
                ("tenant-a", "worker", "training", "Echo.echo")
            ] == 1
            assert by_labels[
                (principal.UNKNOWN, principal.UNKNOWN,
                 principal.UNKNOWN, "Echo.echo")
            ] == 1
    finally:
        server.stop(0)


# ---- /usage rollup -------------------------------------------------------


def _usage_snapshot(meter):
    """A reporter snapshot carrying usage families, built on a fresh
    registry so tests stay independent of process-global state."""
    with _fresh_default_registry() as reg:
        meter()
        return reg.snapshot()


def test_usage_endpoint_totals_shares_and_top_k():
    worker_snap = _usage_snapshot(lambda: (
        usage.meter_request(
            principal.Principal("tenant-a", "worker", "training"),
            "RowService.push_row_grads", 0.010,
        ),
        usage.meter_rows(
            principal.Principal("tenant-a", "worker", "training"),
            "push_row_grads", rows=100, nbytes=3200,
        ),
        usage.meter_rows(
            principal.Principal("tenant-b", "serving", "serving_read"),
            "pull_rows", rows=10, nbytes=320,
        ),
    ))
    row_snap = _usage_snapshot(lambda: usage.meter_request(
        principal.Principal("tenant-a", "worker", "migration"),
        "RowService.ingest_rows", 0.002,
    ))
    plane = MetricsPlane(registry=MetricsRegistry())
    plane.ingest(0, worker_snap)
    plane.ingest("rowservice-0", row_snap)
    body = plane.usage(top_k=1)
    assert body["totals"]["requests"] == 2
    assert body["totals"]["rows"] == 110
    assert body["totals"]["bytes"] == 3520
    # Principals are ranked by bytes; shares are fractions of totals.
    top = body["principals"][0]
    assert top["principal"]["job"] == "tenant-a"
    assert top["share"]["bytes"] == pytest.approx(3200 / 3520)
    # Per-shard top-K respects K per reporter, keyed by reporter name.
    assert set(body["shards"]) == {"0", "rowservice-0"}
    assert len(body["shards"]["0"]["top"]) == 1
    assert body["shards"]["rowservice-0"]["top"][0]["principal"][
        "purpose"] == "migration"
    # Everything above was tagged: the coverage ratio is 1.0.
    assert body["attributed_handler_share"] == pytest.approx(1.0)

    server = plane.serve(port=0)
    try:
        with urllib.request.urlopen(
            f"http://localhost:{server.port}/usage?top=1"
        ) as resp:
            assert resp.status == 200
            http_body = json.loads(resp.read())
        assert http_body["totals"] == body["totals"]
        assert len(http_body["shards"]["0"]["top"]) == 1
    finally:
        plane.stop()


def test_attributed_share_counts_unknown_handler_time():
    snap = _usage_snapshot(lambda: (
        usage.meter_request(
            principal.Principal("j", "c", "training"), "Svc.m", 0.03,
        ),
        usage.meter_request(principal.NOBODY, "Svc.m", 0.01),
    ))
    body = usage.summarize_usage({"w": snap})
    assert body["attributed_handler_share"] == pytest.approx(
        0.75, abs=1e-6
    )
    assert body["purposes"][principal.UNKNOWN]["share"] == (
        pytest.approx(0.25, abs=1e-6)
    )


def test_usage_exposition_golden_file():
    """The attribution families render through the standard
    exposition path — pinned against a checked-in golden so label
    order, bucket layout, and naming changes show as a diff."""
    with _fresh_default_registry() as reg:
        who = principal.Principal("tenant-a", "worker", "training")
        usage.meter_request(who, "RowService.push_row_grads", 0.003)
        usage.meter_rows(who, "push_row_grads", rows=64, nbytes=2048)
        usage.meter_lock_hold(who, 0.002)
        usage.meter_fsync_wait(who, 0.004)
        usage.meter_cold_fault(who, 8, 0.001)
        # The streaming ingestion purpose (closed-enum member since
        # the stream plane landed) renders like any other.
        streamer = principal.Principal(
            "tenant-a", "master", "streaming_ingest"
        )
        usage.meter_request(streamer, "Master.report_task_result", 0.002)
        text = render_prometheus(reg.snapshot())
    golden = (
        pathlib.Path(__file__).parent / "golden"
        / "exposition_usage.txt"
    ).read_text()
    assert text == golden


# ---- SLO per-workload burn -----------------------------------------------


def test_default_rules_cover_per_workload_burn():
    from elasticdl_tpu.observability.slo import default_rules

    rules = {r.name: r for r in default_rules()}
    for name, purpose in (("usage-burn-serving-read", "serving_read"),
                          ("usage-burn-training", "training")):
        rule = rules[name]
        assert rule.series == "edl_tpu_usage_handler_seconds"
        assert rule.labels == {"purpose": purpose}
        assert rule.latency_threshold is not None


# ---- drill + checker -----------------------------------------------------


def test_check_usage_validates_committed_report(tmp_path):
    report_path = REPO_ROOT / "USAGE_DRILL.json"
    errors, report = check_usage(str(report_path))
    assert errors == []
    assert report["passed"]
    # A tampered report (training billed for migration bytes) fails.
    bad = json.loads(report_path.read_text())
    bad["purity"]["purposes_by_method"]["ingest_rows"] = [
        "migration", "training"
    ]
    bad_path = tmp_path / "USAGE_DRILL.json"
    bad_path.write_text(json.dumps(bad))
    errors, _ = check_usage(str(bad_path))
    assert any("ingest_rows" in e for e in errors)
    # Directory form resolves the conventional file name.
    assert check_usage(str(tmp_path))[0] == errors


def test_check_trace_flags_partial_principal(tmp_path):
    def event(name, cat, pid, span, parent=None, extra=None):
        args = {"span_id": span, "parent_id": parent, "trace_id": "t"}
        args.update(extra or {})
        return {"ph": "X", "name": name, "cat": cat, "ts": 1,
                "dur": 1, "pid": pid, "tid": 1, "args": args}

    meta = [{"ph": "M", "name": "process_name", "pid": p,
             "args": {"name": f"p{p}"}} for p in (1, 2, 3)]
    full = {"principal_job": "j", "principal_component": "c",
            "principal_purpose": "training"}
    good = {"traceEvents": meta + [
        event("task", "master", 1, "a", extra=full),
        event("device_step", "worker", 2, "b", parent="a"),
        event("row_pull", "rowservice", 3, "c", parent="b"),
    ]}
    path = tmp_path / "good.json"
    path.write_text(json.dumps(good))
    assert check_trace(str(path)) == []

    bad = {"traceEvents": meta + [
        event("task", "master", 1, "a",
              extra={"principal_job": "j"}),
        event("device_step", "worker", 2, "b", parent="a",
              extra={**full, "principal_purpose": "mining"}),
        event("row_pull", "rowservice", 3, "c", parent="b"),
    ]}
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    errors = check_trace(str(path))
    assert any("partial principal" in e for e in errors)
    assert any("outside the closed enum" in e for e in errors)


def test_usage_drill_passes(tmp_path, monkeypatch):
    """Fast-lane twin of ``make usage-smoke`` (shrunk schedule): the
    purity and coverage gates through a live 2->3 split, which are
    exact. The overhead gate (``P99_GATE``, a ratio of two wall-clock
    p99s) is measured and reported here but held only by ``make
    usage-smoke``, on a quiet machine: beside five other test workers
    it read over 1.05 with nothing wrong. The drill reads the process
    registry, which is its own in ``make usage-smoke``; here it gets a
    fresh one, or the unattributed handler time of whatever test files
    ran before in this worker process counts against its coverage."""
    from elasticdl_tpu.chaos import usage_drill

    monkeypatch.setattr(usage_drill, "PUSHES", 80)
    monkeypatch.setattr(usage_drill, "SPLIT_AT", 40)
    monkeypatch.setattr(usage_drill, "WARMUP", 10)
    with _fresh_default_registry():
        report = usage_drill.run_drill(str(tmp_path), seed=7)
    assert report["purity"]["ok"], report["purity"]["problems"]
    assert all(
        report["purity"]["bytes_by_method"][method] > 0
        for method in ("ingest_rows", "replica_refresh")
    )
    assert report["attribution"]["ok"], report["attribution"]
    assert report["attribution"]["attributed_handler_share"] >= 0.95
    ratios = [a["ratio"] for a in report["latency"]["attempts"]]
    assert ratios and all(0 < r < float("inf") for r in ratios)
    assert [
        p for p in report["problems"] if "attributed p99" not in p
    ] == []
