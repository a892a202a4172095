"""The reader of the expert layer's overflow counter on two pages of the
master's: layers a step between the scrapes, 0 where the counter stood
still, nothing where the program has no such series (the parent of the
PR that brought the row bound)."""

from benchmark import run as harness

OVERFLOW = 'edl_tpu_worker_moe_overflow_layers_total{worker="0"}'
TASK_LOGS = 'edl_tpu_worker_phase_seconds_count{phase="task_log",worker="0"}'


def _run(before, after, logs=(6.0, 9.0)):
    pages = [{TASK_LOGS: n} for n in logs]
    for page, layers in zip(pages, (before, after)):
        if layers is not None:
            page[OVERFLOW] = layers
    return {"master_open": pages[0], "master_close": pages[1],
            "steps_per_task": 8}


def test_overflowing_layers_are_read_per_step():
    assert harness.read_metric(
        "expert_overflow_layers_per_step", _run(5.0, 5.0 + 12.0)) == 0.5


def test_a_counter_that_stood_still_reads_zero():
    assert harness.read_metric(
        "expert_overflow_layers_per_step", _run(0.0, 0.0)) == 0.0


def test_a_program_without_the_counter_reads_nothing():
    for run in (_run(None, None), _run(0.0, 0.0, logs=(9.0, 9.0)),
                {"master_open": {}, "master_close": {}, "steps_per_task": 8}):
        assert harness.read_metric(
            "expert_overflow_layers_per_step", run) is None
