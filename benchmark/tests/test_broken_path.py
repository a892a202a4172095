"""Drives a whole run of the harness (everything but its look for a
chip: the worker is told to run on the CPU) at the test size, once as it
should be and then with the timed path broken underneath, each fault
planted in the worker by ``tests/tiny/models/tiny.py``: a step that
returns its parameters unchanged, an optimizer update that is not the
configuration's, a part of every minibatch left out of the loss. The
sound run has to come out ``correct``; a broken one must not, and by the
number that reads the worker's own compiled program, ``task_loss_gap``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import paths

MANIFEST = os.path.join(paths.BENCH, "tests", "tiny", "manifest.json")


def _run(capsys, seed=3000000029, trace=0):
    result, code = harness.run_cell(MANIFEST, "tiny_steady", seed,
                                    seconds=4, trace=trace, platform="cpu")
    assert code == 0
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    compared = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("compared: "):
            compared[line.split()[1]] = not line.endswith("NOT OK")
    return result, compared


def test_sound_run_is_correct(capsys):
    result, compared = _run(capsys)
    assert result["correct"] is True, compared
    assert compared["task_loss_gap"] and compared["grad_norm_gap"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize(
    "fault", ["frozen_step", "wrong_update", "part_of_batch"])
def test_broken_timed_path_is_not_correct(fault, capsys, monkeypatch):
    monkeypatch.setenv("BENCH_TEST_FAULT", fault)
    result, compared = _run(capsys)
    assert result["correct"] is False
    assert compared["task_loss_gap"] is False, compared
    if fault == "frozen_step":
        assert compared["loss_drop"] is False, compared


def test_no_chip_no_result():
    with pytest.raises(harness.BenchFailure):
        harness.run_cell(MANIFEST, "tiny_steady", 1, seconds=1, trace=0,
                         platform="tpu")
