"""The fifth family (window and full causal attention layers mixed, a
router that reads the layer's input, ReLU-gated experts held by share)
at a test size (``tests/tiny_smallthinker``, the CPU, float32): its
reference's control is refused on three seeds, a whole run of the
harness over it is ``correct`` with the routed rows the reference counts
and is not with a fault planted in the worker (the window layers run as
full causal among them), and its operation and byte counts against
values worked by hand and against the issue's arithmetic.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import compare, counts_smallthinker, paths
from benchmark.tests import calibrate, routed_rows

TINY = os.path.join(paths.BENCH, "tests", "tiny_smallthinker")
CONFIG = os.path.join(TINY, "configs", "tiny-smallthinker.json")
TRAFFIC = os.path.join(TINY, "traffic", "tiny_steady.json")
SEEDS = (13, 3000000019, 987654321)


@pytest.mark.parametrize("seed", SEEDS)
def test_lower_precision_control_is_refused(seed):
    limits = paths.load_json(CONFIG)["limits"]["compared"]
    reading = calibrate.control_reading(CONFIG, TRAFFIC, seed)
    assert reading["control_precision"] == "bf16"
    control = dict(reading["control"], stray_rows_fed=0)
    rows = compare.verdicts(control, limits)
    assert not all(ok for *_, ok in rows), rows
    assert not dict((name, ok) for name, *_, ok in rows)["grad_norm_gap"]


def _run(capsys, seed):
    result, code = harness.run_cell(
        os.path.join(TINY, "manifest.json"), "tiny_smallthinker_steady",
        seed, seconds=4, trace=0, platform="cpu")
    assert code == 0
    compared = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("compared: "):
            compared[line.split()[1]] = not line.endswith("NOT OK")
    return result, compared


def _pairs_of(log: str) -> list:
    line = next(m for m in log.splitlines() if " routing: " in m)
    counts = line.split("attn_visible_pairs=[")[1].split("]")[0]
    return [int(x) for x in counts.split(",")]


def test_a_whole_run_at_the_test_size_is_correct(capsys):
    seed = 3000000031
    result, compared = _run(capsys, seed)
    assert result["correct"] is True, compared
    assert compared["task_loss_gap"] and compared["grad_norm_gap"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    # The worker's third line; the first task's rows are the reference's
    # counts for the same steps (float32 on both sides: no choice turns
    # on rounding): no row was dropped.
    work = os.path.join(paths.ROOT, ".bench_work", "tiny_smallthinker_steady")
    got = routed_rows.program_rows(os.path.join(work, "worker.log"))
    want = routed_rows.reference_rows(
        CONFIG, TRAFFIC, seed, os.path.join(work, "feed.jsonl"))
    assert len(want) == 4 and got == want
    # ``router_init: members_alike``, as the cell: one choice a position
    # and layer reaches the held experts (4 layers x 4 rows x 64
    # positions), but for the tokens whose 4 drawn logits are all
    # negative (one in 16 here, one in 256 at the cell's 8): member 3's
    # zero columns win those.
    whole = 4 * 4 * 64
    assert 0.8 * whole < got[0] < whole
    assert all(0.8 * whole < rows <= whole for rows in got)
    with open(os.path.join(work, "worker.log"), errors="replace") as f:
        log = f.read()
    assert ("6 query heads over 2 key/value heads, head size 16; sliding "
            "window 16, rotary") in log
    assert "full causal, no positions" in log
    assert ("experts relu-gated of width 32, softmax scores, no selection "
            "bias, no shared expert") in log
    # 4 rows x (1 global layer 64 x 65 / 2 + 3 window layers 16 x 17 / 2
    # + 48 x 16) pairs a step: the band is in the timed path.
    pairs = 4 * counts_smallthinker.visible_pairs(paths.load_json(CONFIG), 64)
    assert pairs == 4 * (2080 + 3 * 904)
    assert _pairs_of(log) == [pairs] * 4


@pytest.mark.parametrize(
    "fault", ["frozen_step", "wrong_update", "half_of_batch", "full_causal"])
def test_a_fault_planted_in_the_worker_is_not_correct(fault, capsys,
                                                      monkeypatch):
    """The timed path broken underneath (``tiny-smallthinker.py`` plants
    the fault in the worker): refused, and by the number that reads the
    worker's own compiled program."""
    monkeypatch.setenv("BENCH_TEST_FAULT", fault)
    result, compared = _run(capsys, 3000000033)
    assert result["correct"] is False
    assert compared["task_loss_gap"] is False, compared
    if fault == "frozen_step":
        assert compared["loss_drop"] is False, compared
    if fault == "full_causal":
        # In worker and check alike, so the first gradient says it too;
        # and the counter reads every layer's causal pairs.
        assert compared["grad_norm_gap"] is False, compared
        work = os.path.join(
            paths.ROOT, ".bench_work", "tiny_smallthinker_steady")
        with open(os.path.join(work, "worker.log"), errors="replace") as f:
            assert _pairs_of(f.read()) == [4 * 4 * 2080] * 4


# d 4; 3 query heads over 1 key/value head of 2; experts of width 3, 2
# of 8 held; 5 layers of the pattern G W W W G; vocabulary 7; window 2.
HAND = {
    "hidden_size": 4, "num_hidden_layers": 5, "num_attention_heads": 3,
    "num_key_value_heads": 1, "head_dim": 2, "moe_ffn_hidden_size": 3,
    "moe_num_primary_experts": 2, "router_width": 8, "vocab_size": 7,
    "sliding_window_size": 2, "sliding_window_layout": [0, 1, 1, 1, 0, 1],
}


def test_counts_against_hand_worked_values():
    c = counts_smallthinker
    # q 4 x 6, k and v 4 x 2 each, o 6 x 4.
    assert c.attention_params(HAND) == 24 + 8 + 8 + 24
    assert c.expert_params(HAND) == 3 * 4 * 3 == 36
    # Five layers of 64 + router 32.
    assert c.per_position_matmul_params(HAND) == 5 * 96
    # A row of 4: causal 1 + 2 + 3 + 4; a band of 2: 1 + 2 + 2 + 2.
    assert c.causal_pairs(4) == 10 and c.window_pairs(HAND, 4) == 7
    assert c.window_layers(HAND) == 3
    assert c.visible_pairs(HAND, 4) == 3 * 7 + 2 * 10
    # 3 rows x 3 heads x 2 matmuls x 2 x 2 x 41 pairs.
    assert c.attention_flops_fwd(HAND, 3, 4) == 3 * 3 * 2 * 2 * 2 * 41
    # 12 positions through the projections and the head (4 x 7), 5
    # routed rows.
    assert c.train_flops_per_step(HAND, 3, 4, 5) == 3 * (
        12 * 2 * 480 + 12 * 2 * 28 + 2952 + 5 * 2 * 36)
    # Embedding 7 x 4, head 28 + bias 7, last norm 4; a layer: 64 + two
    # norms of 4 + router 32 + 2 experts of 36.
    assert c.param_count(HAND) == 28 + 28 + 7 + 4 + 5 * (64 + 8 + 32 + 72)
    kernels = c.attention_kernel_step(HAND, 3, 4)
    # 3 rows x 3 heads x 41 pairs; six matmuls over 2.
    assert kernels["flops"] == 3 * 2 * 2 * 2 * (3 * 3 * 41)
    # 5 layers x 12 positions; q-side and k/v-side tensors 6 x 2 x (3 +
    # 1) numbers a position, 2 bytes; the logsumexp 2 x 3 heads x 4.
    assert kernels["bytes"] == 5 * 12 * (6 * 2 * 4 * 2 + 24)
    band = c.window_kernel_step(HAND, 3, 4)
    assert band["flops"] == 3 * 2 * 2 * 2 * (3 * 3 * 21)
    assert band["bytes"] == 3 * 12 * (6 * 2 * 4 * 2 + 24)
    ffn = c.expert_ffn_step(HAND, 5)
    assert ffn["flops"] == 3 * 2 * 5 * 36
    # Held weights 5 layers x 2 experts x 36, three passes; a row
    # crosses 2 x 4 + 3 x 3 = 17 numbers, three passes; 2 bytes each.
    assert ffn["bytes"] == (3 * 360 + 3 * 5 * 17) * 2
    again = c.expert_ffn_step(dict(HAND, remat=True), 5)
    assert again["flops"] == 4 * 2 * 5 * 36
    assert again["bytes"] == (4 * 360 + 4 * 5 * 17) * 2


def test_real_configuration_is_what_the_issue_reckoned():
    cfg = paths.load_json(paths.config_path("smallthinker-21b-a3b-ep8"))
    c = counts_smallthinker
    assert c.attention_params(cfg) == 20971520
    assert c.expert_params(cfg) == 5898240
    layer = c.attention_params(cfg) + 5120 + 163840 + 8 * c.expert_params(cfg)
    assert layer == 68326400
    # The issue's 643,871,792: 8 layers, embedding and head 2 x
    # 48,619,520, the head's bias, the final norm.
    assert c.param_count(cfg) == 8 * layer + 2 * 48619520 + 18992 + 2560
    assert c.param_count(cfg) == 643871792
    assert c.causal_pairs(16384) == 134225920
    assert c.window_pairs(cfg, 16384) == 58722304
    assert c.visible_pairs(cfg, 16384) == 620785664
    # Under members_alike member 0 is sent 16,384 rows a layer.
    rows = 8 * 16384
    step = c.train_flops_per_step(cfg, 1, 16384, rows)
    assert round(step / 1e12, 1) == 52.7
    attention = 3 * c.attention_flops_fwd(cfg, 1, 16384)
    assert round(attention / 1e12, 1) == 26.7
    assert 0.50 < attention / step < 0.52
    assert 0.30 < 3 * 16384 * 2 * 8 * c.attention_params(cfg) / step < 0.32
    assert 0.08 < 3 * rows * 2 * c.expert_params(cfg) / step < 0.10
    window = c.window_kernel_step(cfg, 1, 16384)["flops"]
    both = c.attention_kernel_step(cfg, 1, 16384)["flops"]
    assert round(window / 1e12, 1) == 15.2 and round(both / 1e12, 1) == 26.7
    published = cfg["published"]
    assert [cfg[k] for k in cfg["reduced"]] == [8, 8, 18992]
    assert published == {"num_hidden_layers": 52,
                         "moe_num_primary_experts": 64, "vocab_size": 151936}
    assert cfg["router_width"] == published["moe_num_primary_experts"]
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    # Every published width stands, and the layouts whole.
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta", "moe_ffn_hidden_size",
        "moe_num_active_primary_experts", "sliding_window_size",
        "max_position_embeddings", "rms_norm_eps")] == [
        2560, 28, 4, 128, 1500000, 768, 6, 4096, 16384, 1e-06]
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == (
        [0, 1, 1, 1] * 13)
    assert (cfg["seq_len"], cfg["minibatch"], cfg["remat"]) == (
        16384, 1, True)
    from benchmark.reference import smallthinker as reference
    assert cfg["router_init"] == "members_alike"
    # As many copies of the drawn columns as a token has choices.
    assert reference.router_members(cfg) == 6
    assert reference.visible_pairs(cfg, 16384) == 620785664
    # The rehearsal's bytes are written in and fit the chip.
    assert 0 < cfg["rehearsal"]["minibatch_1_remat"]["total_bytes"] < 16.91e9


def test_zoo_refuses_a_block_the_program_does_not_have():
    from benchmark.lib import zoo_smallthinker

    cfg = paths.load_json(CONFIG)
    made = zoo_smallthinker.model_config(cfg)
    assert (made.scoring, made.selection_bias, made.shared_expert,
            made.expert_form) == ("softmax", False, False, "relu_gated")
    assert (made.n_held, made.router_width, made.top_k) == (4, 16, 3)
    assert made.sliding_window_layout == made.rope_layout == (0, 1, 1, 1)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        zoo_smallthinker.model_config(dict(cfg, norm_topk_prob=False))
    with pytest.raises(ValueError, match="apply_softmax"):
        zoo_smallthinker.model_config(
            dict(cfg, moe_primary_router_apply_softmax=False))


def test_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives a new reader: no span, no counter,
    no table; every reader returns None and none raises."""
    bare = {"master_open": {}, "master_close": {}, "steps_per_task": 8,
            "cfg": paths.load_json(
                paths.config_path("smallthinker-21b-a3b-ep8")),
            "traffic": {}, "tasks": [], "open_t": 0.0, "close_t": 1.0}
    for name in ("smallthinker_mfu_pct", "swa_attn_kernel_roofline",
                 "window_attn_kernel_ms", "window_attn_kernel_roofline",
                 "smallthinker_expert_ffn_roofline",
                 "attn_visible_pairs_per_step"):
        assert harness.read_metric(name, bare) is None, name


def test_pairs_reader_takes_the_pages_growth_over_the_tasks():
    phase = 'edl_tpu_worker_phase_seconds_count{phase="task_log",worker="0"}'
    series = 'edl_tpu_worker_attn_visible_pairs_total{worker="0"}'
    run = {"master_open": {phase: 6.0, series: 6 * 8 * 620785664.0},
           "master_close": {phase: 14.0, series: 14 * 8 * 620785664.0},
           "steps_per_task": 8}
    assert harness.read_metric(
        "attn_visible_pairs_per_step", run) == 620785664.0


def test_window_reader_tells_a_window_call_by_its_module(tmp_path):
    """``attn.N`` spans joined to the operation table: the window
    layers' calls are those whose module holds ``window_attn``."""
    import json

    from benchmark.metrics import _smallthinker

    programs = tmp_path / "programs"
    programs.mkdir()
    rows = [
        {"name": "attn.1", "opcode": "custom-call", "phase": "forward",
         "op_name": "", "module": "SeededLM/block_*/global_attn/attn"},
        {"name": "attn.2", "opcode": "custom-call", "phase": "forward",
         "op_name": "", "module": "SeededLM/block_*/window_attn/attn"},
        {"name": "attn.3", "opcode": "custom-call", "phase": "backward",
         "op_name": "", "module": "SeededLM/block_*/window_attn/attn"},
        {"name": "fusion.9", "opcode": "fusion", "phase": "forward",
         "op_name": "", "module": "SeededLM/block_*/window_attn/q"},
    ]
    (programs / "jit_multi_step.ops.json").write_text(
        json.dumps({"module": "jit_multi_step", "ops": rows}))

    class Trace:
        def lane(self, name, pid=None):
            assert name == "XLA Ops"
            return [(1.0, 0.5, "attn.1"), (2.0, 0.25, "attn.2"),
                    (3.0, 0.75, "attn.3"), (4.0, 9.0, "fusion.9"),
                    (99.0, 1.0, "attn.2")]          # outside the program

        def module_events(self, name):
            return [(0.0, 50.0, "jit_multi_step(1)")]

    run = {"trace": Trace(), "trace_dir": str(tmp_path), "traffic": {},
           "steps_per_task": 4}
    assert _smallthinker.window_attention_seconds_per_step(run) == 0.25
    assert harness.read_metric("window_attn_kernel_ms", run) == 250.0
    assert harness.read_metric("window_attn_kernel_ms", dict(
        run, trace_dir=None)) is None
