"""Streaming ingestion plane (docs/online_learning.md): the
append-only stream source, the dispatcher's streaming mode with
journaled exactly-once watermarks, the ingestor's backpressure and
watermark-triggered eval, and the committed STREAM_DRILL.json
contract."""

import json
import os
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.common.constants import ReaderType, TaskType
from elasticdl_tpu.common.task import Task
from elasticdl_tpu.data.stream import (
    FileTailStream,
    StreamDataReader,
    StreamTruncatedError,
    StreamWriter,
)
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.journal import (
    JOURNAL_FILE,
    REPORT,
    SNAPSHOT,
    STREAM,
    MasterJournal,
    apply_stream_record,
    apply_stream_report_record,
    new_stream_state,
    normalize_stream_state,
    read_records,
    recover_master_state,
)
from elasticdl_tpu.master.stream_ingest import StreamIngestor
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.observability.registry import MetricsRegistry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

METRICS = {"mean_out": lambda labels, outputs: np.mean(outputs)}


def write_records(tmp_path, partition="clicks", n=10, start=0):
    writer = StreamWriter(str(tmp_path))
    for i in range(start, start + n):
        writer.append(partition, f"rec-{i}".encode())
    writer.close()


def stream_dispatcher(records_per_task=4, **kw):
    return TaskDispatcher(
        {}, records_per_task=records_per_task, shuffle=False,
        streaming=True, **kw
    )


def drain_one(dispatcher, success=True, err_reason=""):
    task = dispatcher.get(0)
    assert task is not None
    dispatcher.report(task.task_id, success, err_reason=err_reason)
    return task


# ---- source ---------------------------------------------------------------


class TestFileTailStream:
    def test_append_read_roundtrip(self, tmp_path):
        write_records(tmp_path, n=5)
        source = FileTailStream(str(tmp_path))
        assert source.partitions() == ["clicks"]
        assert source.end_offset("clicks") == 5
        assert source.read("clicks", 1, 4) == [
            b"rec-1", b"rec-2", b"rec-3"
        ]

    def test_tail_sees_later_appends(self, tmp_path):
        write_records(tmp_path, n=3)
        source = FileTailStream(str(tmp_path))
        assert source.end_offset("clicks") == 3
        write_records(tmp_path, n=2, start=3)
        # The SAME handle polls the growing file on every read call.
        assert source.end_offset("clicks") == 5
        assert source.read("clicks", 3, 5) == [b"rec-3", b"rec-4"]

    def test_read_beyond_end_raises(self, tmp_path):
        write_records(tmp_path, n=3)
        source = FileTailStream(str(tmp_path))
        with pytest.raises(StreamTruncatedError):
            source.read("clicks", 2, 7)

    def test_torn_tail_frame_is_invisible(self, tmp_path):
        write_records(tmp_path, n=4)
        stream_file = next(
            str(p) for p in tmp_path.iterdir()
            if p.name.endswith(".edlstream")
        )
        # A crash mid-append leaves a torn frame: half a length
        # header. Readers must surface only the complete prefix.
        with open(stream_file, "ab") as fh:
            fh.write(b"\x50\x00")
        source = FileTailStream(str(tmp_path))
        assert source.end_offset("clicks") == 4
        assert source.read("clicks", 0, 4)[-1] == b"rec-3"

    def test_append_time_monotone_and_known(self, tmp_path):
        write_records(tmp_path, n=3)
        source = FileTailStream(str(tmp_path))
        times = [source.append_time("clicks", i) for i in range(3)]
        assert all(t > 0 for t in times)
        assert times == sorted(times)

    def test_multiple_partitions_independent(self, tmp_path):
        write_records(tmp_path, "clicks", n=3)
        write_records(tmp_path, "views", n=5)
        source = FileTailStream(str(tmp_path))
        assert sorted(source.partitions()) == ["clicks", "views"]
        assert source.end_offset("clicks") == 3
        assert source.end_offset("views") == 5


class TestStreamDataReader:
    def test_stream_task_reads_offset_range(self, tmp_path):
        write_records(tmp_path, n=6)
        reader = StreamDataReader(stream_dir=str(tmp_path))
        task = Task(shard_name="clicks", start=2, end=5,
                    type=TaskType.TRAINING,
                    extended_config={"stream": True})
        assert list(reader.read_records(task)) == [
            b"rec-2", b"rec-3", b"rec-4"
        ]
        assert reader.create_shards() == {}
        assert reader.metadata.extra.get("stream") is True

    def test_non_stream_task_requires_fallback(self, tmp_path):
        write_records(tmp_path, n=2)
        reader = StreamDataReader(stream_dir=str(tmp_path))
        task = Task(shard_name="e1", start=0, end=2,
                    type=TaskType.EVALUATION)
        with pytest.raises(ValueError, match="fallback"):
            list(reader.read_records(task))

        class Fallback:
            def read_records(self, task):
                yield b"from-fallback"

        routed = StreamDataReader(
            stream_dir=str(tmp_path), fallback=Fallback()
        )
        assert list(routed.read_records(task)) == [b"from-fallback"]

    def test_factory_routes_stream_scheme(self, tmp_path):
        from elasticdl_tpu.data.factory import create_data_reader

        write_records(tmp_path, n=1)
        reader = create_data_reader(
            data_origin=f"stream://{tmp_path}"
        )
        assert isinstance(reader, StreamDataReader)
        reader = create_data_reader(
            data_origin=str(tmp_path), reader_type=ReaderType.STREAM
        )
        assert isinstance(reader, StreamDataReader)


# ---- dispatcher streaming mode --------------------------------------------


class TestStreamingDispatcher:
    def test_create_stream_tasks_splits_and_clips(self):
        d = stream_dispatcher(records_per_task=4)
        assert d.create_stream_tasks("clicks", 0, 10) == 3
        ranges = [
            (t.shard_name, t.start, t.end)
            for t in (d.get(0), d.get(0), d.get(0))
        ]
        assert ranges == [("clicks", 0, 4), ("clicks", 4, 8),
                          ("clicks", 8, 10)]
        # Re-offering an already-generated range is a no-op (ingestor
        # retry after a lost ack), a partial overlap clips.
        assert d.create_stream_tasks("clicks", 0, 10) == 0
        assert d.create_stream_tasks("clicks", 6, 12) == 1
        task = d.get(0)
        assert (task.start, task.end) == (10, 12)
        assert task.extended_config["stream"] is True

    def test_watermark_advances_only_contiguously(self):
        d = stream_dispatcher(records_per_task=4)
        d.create_stream_tasks("clicks", 0, 12)
        t0, t1, t2 = d.get(0), d.get(1), d.get(0)
        # Completing [8,12) and [4,8) out of order parks them as
        # pending; the watermark stays at the missing prefix.
        d.report(t2.task_id, True)
        progress = d.stream_progress()["clicks"]
        assert progress["committed"] == 0
        assert progress["pending"] == {8: 12}
        d.report(t1.task_id, True)
        assert d.stream_progress()["clicks"]["committed"] == 0
        # The prefix lands: the watermark jumps over the whole run.
        d.report(t0.task_id, True)
        progress = d.stream_progress()["clicks"]
        assert progress["committed"] == 12
        assert progress["pending"] == {}

    def test_failed_task_does_not_advance_watermark(self):
        d = stream_dispatcher(records_per_task=4)
        d.create_stream_tasks("clicks", 0, 4)
        task = d.get(0)
        d.report(task.task_id, False, err_reason="worker_dead")
        assert d.stream_progress()["clicks"]["committed"] == 0
        # The requeued retry commits it.
        retry = d.get(1)
        assert (retry.start, retry.end) == (0, 4)
        d.report(retry.task_id, True)
        assert d.stream_progress()["clicks"]["committed"] == 4

    def test_finished_requires_close_stream(self):
        d = stream_dispatcher(records_per_task=4)
        d.create_stream_tasks("clicks", 0, 4)
        drain_one(d)
        # Drained queues with a live tail: the job must stay alive.
        assert not d.finished()
        d.close_stream()
        assert d.finished()

    def test_export_restore_carries_stream_state(self):
        d = stream_dispatcher(records_per_task=4)
        d.create_stream_tasks("clicks", 0, 8)
        drain_one(d)
        state = d.export_state()
        d2 = TaskDispatcher({}, records_per_task=4, shuffle=False)
        d2.restore_state(state)
        assert d2.is_streaming
        progress = d2.stream_progress()["clicks"]
        assert progress["committed"] == 4
        assert progress["next"] == 8

    def test_preempt_leases_requeues_stream_tasks(self):
        d = stream_dispatcher(records_per_task=4)
        d.create_stream_tasks("clicks", 0, 8)
        d.get(0), d.get(1)
        assert d.preempt_leases() == 2
        assert d.stream_progress()["clicks"]["committed"] == 0
        todo, doing = d.queue_depths()
        assert (todo, doing) == (2, 0)
        for _ in range(2):
            drain_one(d)
        assert d.stream_progress()["clicks"]["committed"] == 8


class TestPreemptRecoverRefillRace:
    @pytest.fixture(autouse=True)
    def _quiet_dispatcher(self):
        """The storm leases each range many times with tasks of zero
        length, so a lease lives for as long as the dispatcher's own
        log lines take to write (one per dispatch since PR 24): with
        them on, ``recover_tasks(1)`` finds the same range leased four
        times running, its ``worker_dead`` budget burns out, the range
        fails for good and the watermark can never pass it. That is
        the retry budget working, not an offset lost: keep the lines
        out of the race."""
        import logging

        from elasticdl_tpu.master import task_dispatcher

        level = task_dispatcher.logger.level
        task_dispatcher.logger.setLevel(logging.WARNING)
        yield
        task_dispatcher.logger.setLevel(level)

    def test_concurrent_refill_never_loses_or_doubles_offsets(self):
        """``preempt_leases`` + ``recover_tasks`` racing a live pump's
        ``create_stream_tasks`` refill: every offset must resolve
        exactly once, the watermark must stay monotone, and nothing
        may wedge."""
        d = stream_dispatcher(records_per_task=2)
        total = 400
        stop = threading.Event()
        watermarks = []
        errors = []

        def producer():
            cursor = 0
            while cursor < total and not stop.is_set():
                nxt = min(total, cursor + 6)
                d.create_stream_tasks("clicks", cursor, nxt)
                cursor = nxt

        def chaos():
            while not stop.is_set():
                d.preempt_leases()
                d.recover_tasks(1)
                last = -1
                committed = d.stream_progress()["clicks"]["committed"]
                if committed < last:
                    errors.append(
                        f"watermark regressed {last}->{committed}"
                    )
                last = committed
                watermarks.append(committed)

        def worker(worker_id):
            while not stop.is_set():
                task = d.get(worker_id)
                if task is None:
                    if (d.stream_progress()["clicks"]["committed"]
                            == total):
                        return
                    continue
                # Report may race a preempt that already resolved the
                # lease — a duplicate outcome must be answered from
                # the ledger, not crash or double-advance.
                d.report(task.task_id, True)

        threads = [
            threading.Thread(target=producer),
            threading.Thread(target=chaos),
            threading.Thread(target=worker, args=(1,)),
            threading.Thread(target=worker, args=(2,)),
        ]
        for t in threads:
            t.start()
        try:
            deadline_worker_threads = threads[2:]
            for t in deadline_worker_threads:
                t.join(timeout=60)
        finally:
            stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors
        progress = d.stream_progress()["clicks"]
        assert progress["committed"] == total
        assert progress["pending"] == {}
        assert monotone(watermarks)


def monotone(samples):
    return all(b >= a for a, b in zip(samples, samples[1:]))


# ---- journal: exactly-once across failover --------------------------------


def journal_stream_fold(journal_dir):
    state = new_stream_state()
    for _off, _end, record in read_records(
        os.path.join(journal_dir, JOURNAL_FILE)
    ):
        if record["t"] == SNAPSHOT and record.get("stream") is not None:
            state = normalize_stream_state(record["stream"])
        elif record["t"] == STREAM:
            apply_stream_record(state, record)
        elif record["t"] == REPORT:
            apply_stream_report_record(state, record)
    return state


class TestJournaledStream:
    def test_recovery_resumes_from_committed_watermark(self, tmp_path):
        journal_dir = str(tmp_path / "journal")
        journal = MasterJournal(journal_dir)
        journal.open_generation()
        d = stream_dispatcher(records_per_task=4)
        d.attach_journal(journal)
        d.create_stream_tasks("clicks", 0, 12)
        done = drain_one(d)
        leased = d.get(1)  # dies leased — must survive as doing
        journal.close()

        j2 = MasterJournal(journal_dir)
        d2 = stream_dispatcher(records_per_task=4)
        stats = recover_master_state(j2, d2)
        assert stats["generation"] >= 1
        progress = d2.stream_progress()["clicks"]
        assert progress["committed"] == done.end
        assert progress["next"] == 12
        # The pre-crash lease is still doing (lease-preserving
        # recovery); the dead worker's requeue path resolves it.
        assert leased.task_id in d2.doing_tasks_of(1)
        d2.recover_tasks(1)
        while not d2.stream_progress()["clicks"]["committed"] == 12:
            drain_one(d2)
        # An ingestor resuming from the journaled cursor re-offers
        # the whole tail; the clip makes it a no-op (never re-acked).
        assert d2.create_stream_tasks("clicks", 0, 12) == 0
        fold = journal_stream_fold(journal_dir)["partitions"]["clicks"]
        assert fold["committed"] == d2.stream_progress()[
            "clicks"
        ]["committed"]
        j2.close()

    def test_cold_fold_matches_live_after_snapshot(self, tmp_path):
        journal_dir = str(tmp_path / "journal")
        # Tight cadence: compaction rewrites the file as [fence,
        # snapshot] mid-run, so the fold must pick the stream state up
        # from the SNAPSHOT record, not just raw STREAM/REPORT ones.
        journal = MasterJournal(journal_dir, snapshot_every=5)
        journal.open_generation()
        d = stream_dispatcher(records_per_task=2)
        d.attach_journal(journal)
        d.create_stream_tasks("clicks", 0, 10)
        for _ in range(5):
            drain_one(d)
        d.create_stream_tasks("clicks", 10, 14)
        for _ in range(2):
            drain_one(d)
        journal.close()
        fold = journal_stream_fold(journal_dir)["partitions"]["clicks"]
        live = d.stream_progress()["clicks"]
        assert fold["committed"] == live["committed"] == 14
        assert fold["next"] == live["next"] == 14


# ---- ingestor -------------------------------------------------------------


class TestStreamIngestor:
    def test_pump_generates_and_backpressures(self, tmp_path):
        write_records(tmp_path, n=40)
        d = stream_dispatcher(records_per_task=2)
        ingestor = StreamIngestor(
            FileTailStream(str(tmp_path)), d, max_todo=4,
            metrics_registry=MetricsRegistry(),
        )
        ingestor.pump()
        todo, _doing = d.queue_depths()
        assert todo == 4  # clamped at max_todo, not the 20 available
        summary = ingestor.pump()
        assert summary["backpressured"]
        # Draining the queue un-blocks the next pass, and the pass
        # after a blocked one accrues backpressure seconds.
        for _ in range(4):
            drain_one(d)
        ingestor.pump()
        assert ingestor.backpressure_seconds > 0.0
        assert d.stream_progress()["clicks"]["next"] > 8

    def test_render_reports_watermarks_and_lag(self, tmp_path):
        write_records(tmp_path, n=6)
        d = stream_dispatcher(records_per_task=3)
        ingestor = StreamIngestor(
            FileTailStream(str(tmp_path)), d, max_todo=8,
            metrics_registry=MetricsRegistry(),
        )
        ingestor.pump()
        drain_one(d)
        body = ingestor.render()
        part = body["partitions"]["clicks"]
        assert part["end"] == 6
        assert part["committed"] == 3
        assert part["lag_records"] == 3
        assert part["watermark_lag_seconds"] >= 0.0
        assert body["max_todo"] == 8

    def test_watermark_eval_trigger(self, tmp_path):
        write_records(tmp_path, n=8)
        d = TaskDispatcher(
            {}, evaluation_shards={"e1": (0, 4)}, records_per_task=2,
            shuffle=False, streaming=True,
        )
        ev = EvaluationService(d, METRICS)
        ingestor = StreamIngestor(
            FileTailStream(str(tmp_path)), d, max_todo=16,
            eval_service=ev, eval_every_records=4,
            metrics_registry=MetricsRegistry(),
        )
        ingestor.pump()
        # Two stream tasks commit -> 4 records past the marker: the
        # next pump opens an eval round over the validation shards.
        for _ in range(2):
            task = d.get(0)
            assert task.type == TaskType.TRAINING
            d.report(task.task_id, True)
        ingestor.pump()
        evals = d.count_tasks(TaskType.EVALUATION)
        assert evals == 2
        assert ev.add_watermark_eval_if_needed(4) is False  # armed once

    def test_eval_marker_seeds_from_recovered_watermark(self, tmp_path):
        write_records(tmp_path, n=8)
        d = TaskDispatcher(
            {}, evaluation_shards={"e1": (0, 4)}, records_per_task=2,
            shuffle=False, streaming=True,
        )
        d.create_stream_tasks("clicks", 0, 8)
        for _ in range(4):
            drain_one(d)  # recovered state: 8 records committed
        ev = EvaluationService(d, METRICS)
        StreamIngestor(
            FileTailStream(str(tmp_path)), d, max_todo=16,
            eval_service=ev, eval_every_records=2,
            metrics_registry=MetricsRegistry(),
        )
        # Without seeding, 8 committed records would fire immediately.
        assert ev.add_watermark_eval_if_needed(8) is False


# ---- SLO + attribution surface -------------------------------------------


class TestObservabilitySurface:
    def test_default_rules_include_watermark_stall(self):
        from elasticdl_tpu.observability.slo import default_rules

        rules = {r.name: r for r in default_rules()}
        rule = rules["stream-watermark-stall"]
        assert rule.series == (
            "edl_tpu_stream_ingest_watermark_lag_seconds"
        )
        assert rule.aggregation == "max"

    def test_purpose_enum_mirrors_agree(self):
        import sys as _sys

        from elasticdl_tpu.observability.principal import PURPOSES

        _sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
        from check_trace import PRINCIPAL_PURPOSES
        from check_usage import PURPOSES as USAGE_PURPOSES

        assert "streaming_ingest" in PURPOSES
        assert set(USAGE_PURPOSES) == set(PURPOSES)
        assert PRINCIPAL_PURPOSES == set(PURPOSES) | {"unknown"}


# ---- committed drill artifact ---------------------------------------------


class TestCheckStream:
    @pytest.fixture()
    def report(self):
        path = os.path.join(REPO_ROOT, "STREAM_DRILL.json")
        if not os.path.exists(path):
            pytest.skip("no committed STREAM_DRILL.json")
        with open(path) as fh:
            return json.load(fh)

    def _run(self, tmp_path, report):
        import sys as _sys

        _sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
        from check_stream import check_stream

        path = str(tmp_path / "STREAM_DRILL.json")
        with open(path, "w") as fh:
            json.dump(report, fh)
        errors, _ = check_stream(path)
        return errors

    def test_committed_report_passes(self, tmp_path, report):
        assert self._run(tmp_path, report) == []

    def test_tampered_verdict_fails(self, tmp_path, report):
        report["passed"] = False
        assert any(
            "did not pass" in e for e in self._run(tmp_path, report)
        )

    def test_offset_gap_detected(self, tmp_path, report):
        part = report["kill"]["twin"]["final_progress"]
        partition = sorted(part)[0]
        part[partition]["committed"] -= 1
        errors = self._run(tmp_path, report)
        assert any("gap" in e or "committed" in e for e in errors)

    def test_reacked_watermark_detected(self, tmp_path, report):
        resumed = report["kill"]["killed"]["resumed_progress"]
        partition = sorted(resumed)[0]
        resumed[partition]["committed"] = 0
        report["kill"]["killed"]["committed_at_kill"][partition][
            "committed"
        ] = 5
        errors = self._run(tmp_path, report)
        assert any("re-acked" in e for e in errors)

    def test_missing_dead_wal_audit_detected(self, tmp_path, report):
        report["kill"]["killed"].pop("dead_wal_fsck", None)
        errors = self._run(tmp_path, report)
        assert any("never audited" in e for e in errors)

    def test_fsck_classifies_stream_report(self, tmp_path, report):
        import sys as _sys

        _sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
        from fsck import run_fsck

        with open(tmp_path / "STREAM_DRILL.json", "w") as fh:
            json.dump(report, fh)
        errors, summary = run_fsck(str(tmp_path))
        assert errors == []
        assert summary["checked"]["stream"] == 1


class TestStreamingMasterAssembly:
    """The production ``Master``/worker assembly in streaming mode:
    ``--stream_dir`` with no ``--training_data`` must behave as a
    TRAINING job (regression: the eval-only heuristic used to open a
    phantom round at construction whose tasks the streaming dispatcher
    deliberately never queues, wedging every watermark trigger behind
    an eval job that could not finish)."""

    @staticmethod
    def _seed_mnist_stream(stream_dir, n, partition="clicks"):
        from elasticdl_tpu.common import tensor_utils

        writer = StreamWriter(str(stream_dir))
        rng = np.random.RandomState(11)
        for _ in range(n):
            label = int(rng.randint(10))
            image = rng.rand(784) * 32.0
            block = 784 // 10
            image[label * block:(label + 1) * block] += 192.0
            writer.append(partition, tensor_utils.dumps({
                "image": image.reshape(28, 28).astype(np.float32),
                "label": label,
            }))
        writer.close()

    def test_stream_master_trains_and_fires_watermark_eval(
        self, tmp_path
    ):
        from elasticdl_tpu.common.args import (
            build_parser,
            parse_worker_args,
        )
        from elasticdl_tpu.master.main import Master
        from elasticdl_tpu.testing.data import (
            create_mnist_record_file,
            model_zoo_dir,
        )
        from elasticdl_tpu.worker.main import build_worker

        model_def = "mnist.mnist_functional.custom_model"
        stream_dir = tmp_path / "stream"
        self._seed_mnist_stream(stream_dir, 32)
        eval_rec = create_mnist_record_file(
            str(tmp_path / "e.rec"), 32, seed=2
        )
        master_args = build_parser("master").parse_args([
            "--model_zoo", model_zoo_dir(),
            "--model_def", model_def,
            "--stream_dir", str(stream_dir),
            "--stream_poll_secs", "0.05",
            "--stream_eval_every_records", "16",
            "--validation_data", eval_rec,
            "--minibatch_size", "16",
            "--master_addr", "localhost:0",
            "--job_name", "stream-assembly",
        ])
        master = Master(master_args)
        # The regression lock: no phantom eval-only round may exist —
        # the watermark trigger must find the service idle.
        assert master.evaluation_service._eval_job is None
        assert master.task_dispatcher.is_streaming
        master.prepare()
        try:
            worker_args = parse_worker_args([
                "--worker_id", "0",
                "--model_zoo", model_zoo_dir(),
                "--model_def", model_def,
                "--stream_dir", str(stream_dir),
                "--validation_data", eval_rec,
                "--minibatch_size", "16",
                "--master_addr", f"localhost:{master.port}",
                "--job_name", "stream-assembly",
            ])
            worker = build_worker(worker_args)
            run_thread = threading.Thread(
                target=worker.run, daemon=True
            )
            run_thread.start()
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                progress = master.task_dispatcher.stream_progress()
                committed = progress.get("clicks", {}).get(
                    "committed", 0
                )
                if (committed == 32
                        and master.evaluation_service
                        .completed_results):
                    break
                time.sleep(0.25)
            progress = master.task_dispatcher.stream_progress()
            assert progress["clicks"]["committed"] == 32
            # The watermark trigger (every 16 of 32 records) opened a
            # round and the worker's fallback reader completed it with
            # real metrics.
            results = master.evaluation_service.completed_results
            assert results
            for metrics in results.values():
                assert "accuracy" in metrics
            # Streaming jobs end by closing the stream, not draining.
            assert not master.task_dispatcher.finished()
            master.task_dispatcher.close_stream()
            run_thread.join(timeout=60)
            assert not run_thread.is_alive()
            assert master.task_dispatcher.finished()
        finally:
            master.stop()
