"""The fourth family (a block-diffusion objective over a Qwen3-MoE body:
a noised copy beside the clean row, the block-diffusion mask, softmax
routing over experts held by share) at a test size
(``tests/tiny_sdar_moe``, the CPU, float32): its reference's control is
refused on three seeds, a whole run of the harness over it is
``correct`` with the routed rows the reference counts and is not with a
fault planted in the worker (the plain causal mask among them), and its
operation and byte counts against values worked by hand and against the
issue's arithmetic.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import compare, counts_sdar_moe, paths
from benchmark.tests import calibrate, routed_rows

TINY = os.path.join(paths.BENCH, "tests", "tiny_sdar_moe")
CONFIG = os.path.join(TINY, "configs", "tiny-sdar-moe.json")
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
        os.path.join(TINY, "manifest.json"), "tiny_sdar_moe_steady",
        seed, seconds=4, trace=0, platform="cpu")
    assert code == 0
    compared = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("compared: "):
            compared[line.split()[1]] = not line.endswith("NOT OK")
    return result, compared


def test_a_whole_run_at_the_test_size_is_correct(capsys):
    seed = 3000000031
    result, compared = _run(capsys, seed)
    assert result["correct"] is True, compared
    assert compared["task_loss_gap"] and compared["grad_norm_gap"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    # The worker's third line; the first task's rows are the
    # reference's counts for the same steps over the doubled rows it
    # noised itself (float32 on both sides: no choice turns on
    # rounding): no row was dropped.
    work = os.path.join(paths.ROOT, ".bench_work", "tiny_sdar_moe_steady")
    got = routed_rows.program_rows(os.path.join(work, "worker.log"))
    want = routed_rows.reference_rows(
        CONFIG, TRAFFIC, seed, os.path.join(work, "feed.jsonl"))
    assert len(want) == 4 and got == want
    # ``router_init: members_alike``, as the cell: at the seeded weights
    # exactly one choice a position and layer reaches the held experts
    # (2 layers x 4 rows x 2 x 64 positions); the steps then train the
    # members' columns apart (Adam at 1e-3 here, a thousand times the
    # cell's rates), and a few positions' choices with them.
    whole = 2 * 4 * 2 * 64
    assert got[0] == whole
    assert all(abs(rows - whole) < 0.05 * whole for rows in got)
    with open(os.path.join(work, "worker.log"), errors="replace") as f:
        log = f.read()
    assert ("diffusion: traced noising of x(4, 64): blocks of 4, linear "
            "schedule, eps 0.2, mask row 512") in log
    assert ("4 query heads over 2 key/value heads, head size 16; "
            "block-diffusion mask, blocks of 4 over halves of 64") in log
    assert "softmax scores, no selection bias, no shared expert" in log
    assert "diffusion_masked_tokens=[" in log


@pytest.mark.parametrize(
    "fault", ["frozen_step", "wrong_update", "half_of_batch", "causal_mask"])
def test_a_fault_planted_in_the_worker_is_not_correct(fault, capsys,
                                                      monkeypatch):
    """The timed path broken underneath (``tiny-sdar-moe.py`` plants the
    fault in the worker): refused, and by the number that reads the
    worker's own compiled program."""
    monkeypatch.setenv("BENCH_TEST_FAULT", fault)
    result, compared = _run(capsys, 3000000033)
    assert result["correct"] is False
    assert compared["task_loss_gap"] is False, compared
    if fault == "frozen_step":
        assert compared["loss_drop"] is False, compared
    if fault == "causal_mask":
        assert compared["grad_norm_gap"] is False, compared


# d 4; 4 query heads over 2 key/value heads of 3; experts of width 3, 2
# of 8 held; 2 layers; vocabulary 7; blocks of 2.
HAND = {
    "hidden_size": 4, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 3, "moe_intermediate_size": 3,
    "num_experts": 2, "router_width": 8, "vocab_size": 7,
    "block_length": 2,
}


def test_counts_against_hand_worked_values():
    c = counts_sdar_moe
    # q 4 x 12, k and v 4 x 6 each, o 12 x 4.
    assert c.attention_params(HAND) == 48 + 24 + 24 + 48
    assert c.expert_params(HAND) == 3 * 4 * 3 == 36
    # Two layers of 144 + router 32.
    assert c.per_position_matmul_params(HAND) == 2 * 176
    # A row of 4 in blocks of 2: clean on clean 2 x 2 + 2 x 4 = 12 (4 x
    # 6 / 2), noised on clean 2 x 2 = 4 (4 x 2 / 2), noised on their own
    # block 2 x 4 = 8: 24 = 4 x (4 + 2).
    assert c.visible_pairs(HAND, 4) == 24
    assert c.positions(3, 4) == 24
    # 2 layers x 3 rows x 4 heads x 2 matmuls x 2 x 3 x 24 pairs.
    assert c.attention_flops_fwd(HAND, 3, 4) == 2 * 3 * 4 * 2 * 2 * 3 * 24
    # 24 positions through the projections, 12 through the head (4 x 7),
    # 5 routed rows.
    assert c.train_flops_per_step(HAND, 3, 4, 5) == 3 * (
        24 * 2 * 352 + 12 * 2 * 28 + 6912 + 5 * 2 * 36)
    # Embedding 8 x 4, head 28 + bias 7, last norm 4; a layer: 144 + two
    # norms of 4 + q and k norms of 3 + router 32 + 2 experts of 36.
    assert c.param_count(HAND) == 32 + 28 + 7 + 4 + 2 * (144 + 14 + 32 + 72)
    kernels = c.attention_kernel_step(HAND, 3, 4)
    # 3 rows x 4 heads x 24 pairs; six matmuls over 3; two layers.
    assert kernels["flops"] == 2 * 3 * 2 * 2 * 3 * (3 * 4 * 24)
    # 24 positions; q-side and k/v-side tensors 6 x 3 x (4 + 2) numbers
    # a position, 2 bytes; the logsumexp 2 x 4 heads x 4 bytes.
    assert kernels["bytes"] == 2 * 24 * (6 * 3 * 6 * 2 + 32)
    ffn = c.expert_ffn_step(HAND, 5)
    assert ffn["flops"] == 3 * 2 * 5 * 36
    # Held weights 2 layers x 2 experts x 36, three passes; a row
    # crosses 2 x 4 + 3 x 3 = 17 numbers, three passes; 2 bytes each.
    assert ffn["bytes"] == (3 * 144 + 3 * 5 * 17) * 2
    # A recomputed layer runs both calls again: a fourth pass.
    again = c.expert_ffn_step(dict(HAND, remat=True), 5)
    assert again["flops"] == 4 * 2 * 5 * 36
    assert again["bytes"] == (4 * 144 + 4 * 5 * 17) * 2


def test_real_configuration_is_what_the_issue_reckoned():
    cfg = paths.load_json(paths.config_path("sdar-30b-a3b-ep8"))
    c = counts_sdar_moe
    assert c.attention_params(cfg) == 18874368
    assert c.expert_params(cfg) == 4718592
    layer = (c.attention_params(cfg) + 4352 + 262144
             + 16 * c.expert_params(cfg))
    assert layer == 94638336
    # The issue's 645.6M, and the head's bias (18,992).
    assert c.param_count(cfg) == 6 * layer + 77795328 + 18992
    assert c.visible_pairs(cfg, 4096) == 16793600      # 16.79M
    # 6 layers x 16,384 positions x 8 choices x 16 of 128 experts.
    rows = 6 * 16384 * 8 * 16 // 128
    step = c.train_flops_per_step(cfg, 2, 4096, rows)
    assert round(step / 1e12, 1) == 25.9
    attention = 3 * c.attention_flops_fwd(cfg, 2, 4096)
    assert 0.37 < attention / step < 0.39
    projections = 3 * 16384 * 2 * 6 * c.attention_params(cfg)
    assert 0.42 < projections / step < 0.44
    assert 0.10 < 3 * rows * 2 * c.expert_params(cfg) / step < 0.12
    published = cfg["published"]
    assert [cfg[k] for k in cfg["reduced"]] == [6, 16, 18992]
    assert published == {"num_hidden_layers": 48, "num_experts": 128,
                         "vocab_size": 151936}
    assert cfg["router_width"] == published["num_experts"]
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    # Every published width stands.
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta", "moe_intermediate_size",
        "num_experts_per_tok", "intermediate_size", "rms_norm_eps")] == [
        2048, 32, 4, 128, 1000000, 768, 8, 6144, 1e-06]
    assert (cfg["block_length"], cfg["noise_eps"], cfg["seq_len"],
            cfg["minibatch"]) == (4, 0.001, 4096, 2)
    # Every seed does the same work: the 8 members' routers start alike,
    # so the held experts are sent one choice a position and layer.
    from benchmark.reference import sdar_moe as reference
    assert cfg["router_init"] == "members_alike"
    assert reference.router_members(cfg) == 8 == cfg["num_experts_per_tok"]


def test_zoo_refuses_a_block_the_program_does_not_have():
    from benchmark.lib import zoo_sdar_moe

    cfg = paths.load_json(CONFIG)
    made = zoo_sdar_moe.model_config(cfg)
    assert (made.scoring, made.selection_bias, made.shared_expert) == (
        "softmax", False, False)
    assert (made.n_held, made.router_width, made.mask_id) == (4, 16, 512)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        zoo_sdar_moe.model_config(dict(cfg, norm_topk_prob=False))
    with pytest.raises(ValueError, match="mlp_only_layers"):
        zoo_sdar_moe.model_config(dict(cfg, mlp_only_layers=[0]))


def test_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives a new reader: no span, no counter;
    every reader returns None and none raises."""
    bare = {"master_open": {}, "master_close": {}, "steps_per_task": 8,
            "cfg": paths.load_json(paths.config_path("sdar-30b-a3b-ep8")),
            "traffic": {}, "tasks": [], "open_t": 0.0, "close_t": 1.0}
    for name in ("sdar_moe_mfu_pct", "bd_attn_kernel_roofline",
                 "sdar_expert_ffn_roofline",
                 "diffusion_masked_tokens_per_step"):
        assert harness.read_metric(name, bare) is None, name


def test_masked_tokens_reader_takes_the_pages_growth_over_the_tasks():
    phase = 'edl_tpu_worker_phase_seconds_count{phase="task_log",worker="0"}'
    series = 'edl_tpu_worker_diffusion_masked_tokens_total{worker="0"}'
    run = {"master_open": {phase: 6.0, series: 190000.0},
           "master_close": {phase: 14.0, series: 452144.0},
           "steps_per_task": 8}
    assert harness.read_metric(
        "diffusion_masked_tokens_per_step", run) == 262144.0 / 64
