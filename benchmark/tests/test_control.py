"""The control of the comparison that decides ``correct``, at a size a
test run can hold (the CPU, ``tests/tiny``, float32): the plain
reference computed one precision below the configuration's (bfloat16
here; float8 under the real configuration's bfloat16) is put in the
program's place and has to be refused by the test configuration's
limits, on three seeds; the reference itself passes them. The same
reading at the cell's own size is taken on the chip by
``tests/calibrate.py`` (PERF.md has the numbers).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark.lib import compare, paths
from benchmark.tests import calibrate

TINY = os.path.join(paths.BENCH, "tests", "tiny")
SEEDS = (11, 3000000023, 123456789)


@pytest.mark.parametrize("seed", SEEDS)
def test_lower_precision_control_is_refused(seed):
    config_file = os.path.join(TINY, "configs", "tiny.json")
    limits = paths.load_json(config_file)["limits"]["compared"]
    reading = calibrate.control_reading(
        config_file, os.path.join(TINY, "traffic", "tiny_steady.json"),
        seed)
    assert reading["control_precision"] == "bf16"
    control = dict(reading["control"], stray_rows_fed=0)
    rows = compare.verdicts(control, limits)
    assert not all(ok for *_, ok in rows), rows


def test_a_number_without_a_limit_is_not_ok():
    rows = compare.verdicts({"loss_gap": 0.0}, {"grad_norm_gap": 1.0})
    assert [ok for *_, ok in rows] == [False, False]
    rows = compare.verdicts({"loss_gap": float("nan")}, {"loss_gap": 1.0})
    assert [ok for *_, ok in rows] == [False]
