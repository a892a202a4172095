"""The readers of the program's own phases (PR 24) on a recorded run:
``data/phase_run.json`` holds the worker's phase series from the two
scrapes of the master's page and, from the trace, the task programs on
``XLA Modules`` and the ``edl:`` spans of the host lanes, all of one
traced run of ``gpt2m_steady`` on a TPU v5e (PR 24's first: the task's
bookkeeping then came after the wait for the device, which is the
2.5 ms nothing covers behind each ``device_wait``). The expected values
were worked out from the file's rows by hand (written out beside
each). A
run of a program without the seam (the parent's page, a trace with no
``edl:`` span) gives every reader nothing to read.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_phase_readers.py -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import paths
from benchmark.lib import trace as trace_lib
from benchmark.metrics import _phases

DATA = os.path.join(paths.BENCH, "tests", "data", "phase_run.json")

# name -> (expected, how it was worked out from the file)
EXPECTED = {
    "input_wait_ms": (
        3.125073799992606,
        "fetch: (0.13856177999981156 - 0.044809566000033385) s over "
        "35 - 5 entries, one a cycle (get_task: 35 - 5)"),
    "task_rpc_ms": (
        4.696727499987219,
        "get_task 0.05389577899995857 s / 30 + report_version "
        "0.04855444599991188 s / 30 + report_task "
        "0.03845159999974612 s / 30 (those two: entries 34 - 4)"),
    "host_dispatch_ms": (
        8.342641800000667,
        "stack 0.004708162 s / 30 + dispatch (207.05821769300005 - "
        "206.812646601) s / 30"),
    "gap_attributed_pct": (
        86.42403531897621,
        "gaps between the three programs 19,871.631 and 18,442.243 us; "
        "the leaves cover 16,957.302 of the first (the tail of "
        "device_wait 3,139.429, report_version 2,087.45, checkpoint "
        "9.56, task_log 1,363.74, report_task 1,448.17, get_task "
        "2,257.63, fetch 5,038.86, stack 175.27, the head of dispatch "
        "1,437.193) and 16,155.094 of the second; the parents "
        "edl:task and edl:device_step are no leaves"),
    "startup_backend_s": (6.611204056000002, "the gauge at the close"),
    "startup_state_s": (5.336400027000003, "the gauge at the close"),
    "startup_first_program_s": (
        208.683480161, "the gauge at the close (this run compiled)"),
}


def _recorded():
    raw = paths.load_json(DATA)
    return {
        "master_open": raw["master_open"],
        "master_close": raw["master_close"],
        "traffic": raw["traffic"],
        "trace": trace_lib.Trace(raw["traceEvents"]),
    }


def _without_the_seam():
    """What the parent gives: the old phase labels on the page, the
    same device lanes, and Python frames on the host's."""
    raw = paths.load_json(DATA)
    page = {
        'edl_tpu_worker_phase_seconds_sum{phase="batch_process",worker="0"}': 1.0,
        'edl_tpu_worker_phase_seconds_count{phase="batch_process",worker="0"}': 9.0,
    }
    events = [e for e in raw["traceEvents"]
              if not (e.get("args") or {}).get("long_name")]
    events.append({"ph": "X", "pid": 701, "tid": 1, "ts": 1.0, "dur": 5.0,
                   "name": "$worker.py:670 _process_train_task"})
    return {
        "master_open": dict(page), "master_close": dict(page),
        "traffic": raw["traffic"], "trace": trace_lib.Trace(events),
    }


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_the_recorded_run(name):
    value = harness.read_metric(name, _recorded())
    assert value == pytest.approx(EXPECTED[name][0], rel=1e-9), (
        EXPECTED[name][1])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_the_seam(name):
    assert harness.read_metric(name, _without_the_seam()) is None


def test_no_window_between_the_scrapes_reads_nothing():
    """Both scrapes saw the same snapshot of the worker (a window
    shorter than --metrics_report_secs): no entries between them."""
    run = _recorded()
    run["master_open"] = run["master_close"]
    for name in ("input_wait_ms", "task_rpc_ms", "host_dispatch_ms"):
        assert harness.read_metric(name, run) is None


def test_unfused_path_counts_fetch_once_per_minibatch():
    """``fetch`` entered 3 times a cycle (2 minibatches and the end of
    the stream): the task's input wait is the three together."""
    def page(cycles):
        return {
            f'edl_tpu_worker_phase_seconds_count{{phase="get_task",worker="0"}}': cycles,
            f'edl_tpu_worker_phase_seconds_sum{{phase="get_task",worker="0"}}': 0.001 * cycles,
            f'edl_tpu_worker_phase_seconds_count{{phase="fetch",worker="0"}}': 3 * cycles,
            f'edl_tpu_worker_phase_seconds_sum{{phase="fetch",worker="0"}}': 0.002 * 3 * cycles,
        }
    run = {"master_open": page(2), "master_close": page(12)}
    assert harness.read_metric("input_wait_ms", run) == pytest.approx(6.0)


def test_leaves_are_the_workers():
    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.worker import worker

    assert _phases.LEAVES == worker.CYCLE_LEAVES
    assert _phases.ANNOTATION_PREFIX == tracing.ANNOTATION_PREFIX


def test_every_reader_has_its_entry():
    manifest = paths.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in EXPECTED:
        assert os.path.exists(paths.metric_path(name))
        assert entries[name]["workloads"] == ["gpt2m_steady"]
