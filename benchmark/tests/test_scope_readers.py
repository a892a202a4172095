"""The readers of a step's device phases (PR 36) on a recorded run:
``data/scope_run/`` is laid out as a worker's ``--profile_dir``:
``trace.json`` holds a cut of one traced run of ``joyai_ep16_steady`` on
a TPU v5e (PR 36's first call: the ``XLA Modules`` spans of its first
two task programs and, of the ``XLA Ops`` spans inside them, those of
the three names that took most time in each phase, the ``while``
container that holds a task program, and one more name; of each span's
``args`` only ``hlo_category`` is kept), ``programs/
jit_multi_step.ops.json`` the rows the worker wrote for those names,
but for that one more name, whose row was taken out: a span with no row
(the rows are that call's: its ``other`` fusions are a gather's, which
the rule committed after the call reads by their own ``op_name``).
The expected values were worked out from the file's rows by hand
(written out beside each). A program that writes no table (the
parent's), or a run with no trace, gives every reader nothing to read.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_scope_readers.py -q
"""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.lib import paths
from benchmark.lib import trace as trace_lib
from benchmark.metrics import _scopes

DATA = os.path.join(paths.BENCH, "tests", "data", "scope_run")
ALL = ["gpt2m_steady", "joyai_ep16_steady", "nemotron3n_ep16_steady",
       "sdar_ep8_steady"]
CELLS = {
    "step_forward_ms": ALL, "step_recompute_ms": ALL[1:],
    "step_backward_ms": ALL, "step_optimizer_ms": ALL,
    "step_scoped_pct": ALL,
}

# Sums of the file's ``dur`` (microseconds) by the phase of each span's
# row, over 2 programs of 8 steps, so ms a step = us / 16 / 1000. Each
# name runs once a step: 16 spans a name, three names a phase.
SUMS_US = {
    "forward": 347926.0892960001,    # attn.122, attn.123, attn.124
    "recompute": 221863.96353,       # fusion.3082, fusion.3124, fusion.3166
    "backward": 673482.9355319998,   # attn.126, attn.127, attn.130
    "optimizer": 31.938280000000002,  # multiply_add_fusion.431, .509, .510
    "mixed": 340632.21047,  # fusion.3031, fusion.3229, multiply_add_fusion.502
    "other": 64169.84164200001,      # fusion.3036, fusion.3078, fusion.3120
    "other, no row": 21389.941015999997,  # fusion.3162: its row taken out
}
# name -> (expected, how it was worked out from the file)
EXPECTED = {
    "step_forward_ms": (
        21.745380581000006, "347,926.089 us of 48 forward spans / 16 steps"),
    "step_recompute_ms": (
        13.866497720625, "221,863.964 us of 48 recompute spans / 16"),
    "step_backward_ms": (
        42.09268347074999, "673,482.936 us of 48 backward spans / 16"),
    "step_optimizer_ms": (
        0.0019961425,
        "31.938 us of 48 optimizer spans / 16: what of Adam no fusion "
        "shares with a gradient"),
    "step_scoped_pct": (
        74.47183111977614,
        "100 x (347,926.089 + 221,863.964 + 673,482.936 + 31.938) / "
        "1,669,496.920 us: the four phases over all 352 spans but the "
        "container's (mixed 340,632.210, other 64,169.842 + 21,389.941 "
        "with no row)"),
}


def _recorded(trace_dir=DATA):
    with open(os.path.join(DATA, "trace.json")) as f:
        raw = json.load(f)
    return {
        "trace_dir": trace_dir, "traffic": raw["traffic"],
        "steps_per_task": raw["traffic"]["minibatches_per_task"],
        "trace": trace_lib.Trace(raw["traceEvents"]),
    }


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_on_the_recorded_run(name):
    value = harness.read_metric(name, _recorded())
    assert value == pytest.approx(EXPECTED[name][0], rel=1e-9), (
        EXPECTED[name][1])


def test_the_phases_sum_to_the_operations_time():
    """forward + recompute + backward + optimizer + mixed + other is
    every span of the cut inside the two programs but the container,
    and the container, which holds them all, is counted nowhere."""
    by_phase = _scopes.step_ms_by_phase(_recorded())
    ops, programs = _scopes.program_ops(_recorded())
    assert programs == 2
    total_ms = 1e3 * sum(dur for dur, _ in ops) / 16
    parts = sum(by_phase[p] for p in _scopes.SCOPED + ("mixed", "other"))
    assert parts == pytest.approx(total_ms, rel=1e-12)
    assert parts == pytest.approx(sum(SUMS_US.values()) / 16e3, rel=1e-9)
    assert not any(name.startswith("while") for _, name in ops)
    spans = _recorded()["trace"].lane("XLA Ops")
    assert any(name.startswith("while") for _, _, name in spans)


def test_a_span_with_no_row_is_other():
    run = _recorded()
    by_phase = _scopes.step_ms_by_phase(run)
    assert by_phase["unnamed"] == pytest.approx(
        SUMS_US["other, no row"] / 16e3, rel=1e-9)
    assert by_phase["other"] == pytest.approx(
        (SUMS_US["other"] + SUMS_US["other, no row"]) / 16e3, rel=1e-9)
    table = _scopes.load_table(run)
    names = {name for _, name in _scopes.program_ops(run)[0]}
    assert len(names - set(table)) == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_no_table_gives_the_reader_nothing(name, tmp_path):
    """What the parent gives: a trace and no ``programs/`` beside it;
    and an untraced run, which has no ``trace_dir`` at all."""
    assert harness.read_metric(name, _recorded(str(tmp_path))) is None
    untraced = _recorded()
    untraced["trace_dir"] = None
    assert harness.read_metric(name, untraced) is None
    no_trace = _recorded()
    no_trace["trace"] = None
    assert harness.read_metric(name, no_trace) is None


def test_a_program_is_found_by_bisection():
    """A span is in a program if it starts inside it: one before the
    first program, one between the two and one after the last are in
    none."""
    run = _recorded()
    programs = _scopes.task_programs(run)
    assert len(programs) == 2
    pid, tid = next(key for key, name in run["trace"].thread.items()
                    if name == "XLA Ops")
    first, second = programs
    gap = (first[0] + first[1] + second[0]) / 2
    before = len(_scopes.program_ops(run)[0])
    for at in (first[0] - 1e-3, gap, second[0] + second[1] + 1e-3):
        run["trace"].spans.append({
            "ph": "X", "pid": pid, "tid": tid, "ts": at * 1e6, "dur": 5.0,
            "name": "fusion.1"})
    assert len(_scopes.program_ops(run)[0]) == before


def test_every_reader_has_its_entry():
    manifest = paths.load_json(os.path.join(paths.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, cells in CELLS.items():
        assert os.path.exists(paths.metric_path(name))
        entry = entries[name]
        assert entry["workloads"] == cells
        assert entry["layer"] == "step"
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
        assert (entry["unit"], entry["better"]) == (
            ("%", "higher") if name.endswith("_pct") else ("ms", "lower"))
    # appended at the end, in the issue's order
    assert [m["name"] for m in manifest["per_layer"]][-5:] == [
        "step_forward_ms", "step_recompute_ms", "step_backward_ms",
        "step_optimizer_ms", "step_scoped_pct"]


def test_the_phases_are_the_programs():
    from elasticdl_tpu.utils import hlo_ops

    assert _scopes.SCOPED == hlo_ops.SCOPED
