"""The reader of the expert layer's bound-rows counter on two pages of
the master's: the rows the layers' work ran over a step between the
scrapes, nothing where the program has no such series (the parent of
the PR that brought the ladder of bounds) or no task was logged."""

import pytest

from benchmark import run as harness

BOUND_ROWS = 'edl_tpu_worker_moe_bound_rows_total{worker="0"}'
TASK_LOGS = 'edl_tpu_worker_phase_seconds_count{phase="task_log",worker="0"}'


def _run(before, after, logs=(6.0, 9.0)):
    pages = [{TASK_LOGS: n} for n in logs]
    for page, rows in zip(pages, (before, after)):
        if rows is not None:
            page[BOUND_ROWS] = rows
    return {"master_open": pages[0], "master_close": pages[1],
            "steps_per_task": 8}


@pytest.mark.parametrize("layers,rung", [(6, 32768), (8, 24576), (4, 12288)])
def test_bound_rows_are_read_per_step(layers, rung):
    """Every layer on one rung in each of 3 tasks of 8 steps."""
    grown = 3 * 8 * layers * float(rung)
    assert harness.read_metric(
        "expert_bound_rows_per_step", _run(1e6, 1e6 + grown)) == layers * rung


def test_a_program_without_the_counter_reads_nothing():
    for run in (_run(None, None), _run(0.0, 0.0, logs=(9.0, 9.0)),
                {"master_open": {}, "master_close": {}, "steps_per_task": 8}):
        assert harness.read_metric(
            "expert_bound_rows_per_step", run) is None


def test_the_manifest_lists_the_reader_beside_the_overflow_counters():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = per_layer["expert_bound_rows_per_step"]
    overflow = per_layer["expert_overflow_layers_per_step"]
    assert entry["workloads"] == overflow["workloads"]
    assert entry["layer"] == overflow["layer"]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("rows", "lower", "program_counter",
                                "train_tokens_per_s")
