"""The third family (Mamba-2 layers, grouped-query attention, relu^2
experts held by share) at a test size (``tests/tiny_nemotron_h``, the
CPU, float32): its reference's control is refused on three seeds, a
whole run of the harness over it is ``correct`` with the routed rows the
reference counts and is not with a fault planted in the worker, and its
operation and byte counts against values worked by hand and against the
issue's arithmetic.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import compare, counts_nemotron_h, paths
from benchmark.tests import calibrate, routed_rows

TINY = os.path.join(paths.BENCH, "tests", "tiny_nemotron_h")
CONFIG = os.path.join(TINY, "configs", "tiny-nemotron-h.json")
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
        os.path.join(TINY, "manifest.json"), "tiny_nemotron_h_steady",
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
    # reference's counts for the same steps (float32 on both sides: no
    # choice turns on rounding): no row was dropped.
    work = os.path.join(paths.ROOT, ".bench_work", "tiny_nemotron_h_steady")
    got = routed_rows.program_rows(os.path.join(work, "worker.log"))
    want = routed_rows.reference_rows(
        CONFIG, TRAFFIC, seed, os.path.join(work, "feed.jsonl"))
    assert len(want) == 4 and got == want
    with open(os.path.join(work, "worker.log"), errors="replace") as f:
        log = f.read()
    assert "ssd: traced pallas chunk kernel" in log
    assert "4 query heads over 2 key/value heads" in log
    assert "experts relu2 of width 32, shared expert 64" in log


@pytest.mark.parametrize(
    "fault", ["frozen_step", "wrong_update", "half_of_batch"])
def test_a_fault_planted_in_the_worker_is_not_correct(fault, capsys,
                                                      monkeypatch):
    """The timed path broken underneath (``tiny-nemotron-h.py`` plants
    the fault in the worker): refused, and by the number that reads the
    worker's own compiled program."""
    monkeypatch.setenv("BENCH_TEST_FAULT", fault)
    result, compared = _run(capsys, 3000000033)
    assert result["correct"] is False
    assert compared["task_loss_gap"] is False, compared
    if fault == "frozen_step":
        assert compared["loss_drop"] is False, compared


# d 4; Mamba-2: 2 heads of 3, 1 group of state 5, 2 taps, chunk 2;
# attention 4 query heads over 2 key/value heads of 3; experts of width
# 3, shared 6, 2 of 8 held; pattern M*E; vocabulary 7.
HAND = {
    "hidden_size": 4, "hybrid_override_pattern": "M*E",
    "mamba_num_heads": 2, "mamba_head_dim": 3, "n_groups": 1,
    "ssm_state_size": 5, "conv_kernel": 2, "chunk_size": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
    "moe_intermediate_size": 3, "moe_shared_expert_intermediate_size": 6,
    "n_routed_experts": 2, "router_width": 8, "vocab_size": 7,
}


def test_counts_against_hand_worked_values():
    c = counts_nemotron_h
    assert [c.layers(HAND, k) for k in "M*E"] == [1, 1, 1]
    # inner 6, convolution over 6 + 2 x 5 = 16 channels; W_in 4 x (6 +
    # 16 + 2) = 96, W_out 6 x 4 = 24.
    assert c.mamba_inner(HAND) == 6 and c.conv_channels(HAND) == 16
    assert c.mamba_matmul_params(HAND) == 120
    # + convolution (2 + 1) x 16, dt_bias, A_log, D 3 x 2, gated norm 6,
    # the layer's norm 4.
    assert c.mamba_params(HAND) == 120 + 48 + 6 + 6 + 4
    # q 4 x 12, k and v 4 x 6 each, o 12 x 4.
    assert c.attention_params(HAND) == 48 + 24 + 24 + 48
    assert c.expert_params(HAND) == 2 * 4 * 3 == 24
    assert c.shared_expert_params(HAND) == 48
    # 120 + 144 + router 32 + shared 48 + head 28.
    assert c.per_token_matmul_params(HAND) == 372
    # 5 x 2 heads x (3 x 5) + convolution 2 x 2 x 16 + skip 2 x 6.
    assert c.scan_flops_per_token_fwd(HAND) == 150 + 64 + 12
    # seq 3: a token sees 2 positions on average; 2 ops x 4 heads x (3 +
    # 3) x 2.
    assert c.attention_flops_per_token_fwd(HAND, 3) == 96
    # 2 rows x 3 tokens, 5 routed rows.
    assert c.train_flops_per_step(HAND, 2, 3, 5) == 3 * (
        6 * (2 * 372 + 226 + 96) + 5 * 48)
    # Embedding and head 2 x 28 + head bias 7 + last norm 4; Mamba 184;
    # attention 144 + norm 4; experts: router 32 + bias 8 + 2 x 24 +
    # shared 48 + norm 4.
    assert c.param_count(HAND) == 67 + 184 + 148 + 140
    ssd = c.ssd_kernel_step(dict(HAND, remat=True), 2, 4)
    # A token: forward 2 heads x (2 x 2 x 3 + 4 x 3 x 5) + 1 group x 2 x
    # 2 x 5 = 164; backward 2 x (4 x 2 x 3 + 10 x 3 x 5) + 6 x 2 x 5 =
    # 408; 8 tokens, the forward twice.
    assert ssd["flops"] == 8 * (2 * 164 + 408)
    # States 2 x 3 x 5 x 4 / 2 = 60 bytes a token; forward (2 x 6 + 2 x
    # 5) x 2 + 3 x 2 x 4 + 60 = 128; backward (3 x 6 + 4 x 5) x 2 + 6 x
    # 2 x 4 + 60 = 184.
    assert ssd["bytes"] == 8 * (2 * 128 + 184)
    once = c.ssd_kernel_step(HAND, 2, 4)
    assert once["flops"] == 8 * (164 + 408)
    kernels = c.attention_kernel_step(HAND, 2, 3)
    # 2 rows x 4 heads x 6 pairs = 48; six matmuls over 3.
    assert kernels["flops"] == 3 * 2 * 2 * 3 * 48
    # 6 positions; q-side and k/v-side tensors 6 x 3 x (4 + 2) numbers
    # a position, 2 bytes; the logsumexp 2 x 4 heads x 4 bytes.
    assert kernels["bytes"] == 6 * (6 * 3 * 6 * 2 + 32)
    ffn = c.expert_ffn_step(HAND, 5)
    assert ffn["flops"] == 3 * 2 * 5 * 24
    # Held weights 1 layer x 2 experts x 24, three passes; a row
    # crosses 2 x 4 + 2 x 3 = 14 numbers, three passes; 2 bytes each.
    assert ffn["bytes"] == (3 * 48 + 3 * 5 * 14) * 2
    # A recomputed layer runs both products again: a fourth pass.
    again = c.expert_ffn_step(dict(HAND, remat=True), 5)
    assert again["flops"] == 4 * 2 * 5 * 24
    assert again["bytes"] == (4 * 48 + 4 * 5 * 14) * 2


def test_real_configuration_is_what_the_issue_reckoned():
    cfg = paths.load_json(paths.config_path("nemotron-3-nano-ep16"))
    c = counts_nemotron_h
    assert round(c.mamba_params(cfg) / 1e6, 2) == 38.74
    assert round(c.attention_params(cfg) / 1e6, 2) == 23.40
    assert round(c.expert_params(cfg) / 1e6, 2) == 9.98
    assert round(c.shared_expert_params(cfg) / 1e6, 2) == 19.96
    assert round(c.param_count(cfg) / 1e6, 1) == 667.0
    # 4 layers x 16,384 tokens x 6 choices x 8 of 128 experts.
    step = c.train_flops_per_step(cfg, 2, 8192, 24576)
    assert round(step / 1e12, 1) == 35.1
    tokens = 3 * 16384
    mamba = tokens * 4 * (2 * c.mamba_matmul_params(cfg)
                          + c.scan_flops_per_token_fwd(cfg))
    assert 0.44 < mamba / step < 0.46
    published = cfg["published"]
    assert [cfg[k] for k in cfg["reduced"]] == [9, 8, 16384]
    assert published == {"num_hidden_layers": 52, "n_routed_experts": 128,
                         "vocab_size": 131072}
    assert cfg["router_width"] == published["n_routed_experts"]
    whole = cfg["published_hybrid_override_pattern"]
    assert len(whole) == 52 and whole.startswith(
        cfg["hybrid_override_pattern"])
    assert [whole.count(k) for k in "ME*"] == [23, 23, 6]
    # Every published width stands.
    assert [cfg[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "chunk_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "routed_scaling_factor")] == [
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5]


def test_zoo_refuses_a_block_the_program_does_not_have():
    from benchmark.lib import zoo_nemotron_h

    cfg = paths.load_json(CONFIG)
    assert zoo_nemotron_h.model_config(cfg).pattern == "ME*E"
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        zoo_nemotron_h.model_config(dict(cfg, mlp_hidden_act="silu"))
    with pytest.raises(ValueError, match="a pattern of 4"):
        zoo_nemotron_h.model_config(dict(cfg, num_hidden_layers=5))


def test_readers_find_nothing_in_a_program_without_the_spans():
    """What the parent commit gives a new reader: no span, no counter;
    every reader returns None and none raises."""
    bare = {"master_open": {}, "master_close": {}, "steps_per_task": 8,
            "cfg": paths.load_json(paths.config_path("nemotron-3-nano-ep16")),
            "traffic": {}, "tasks": [], "open_t": 0.0, "close_t": 1.0}
    for name in ("ssd_scan_ms", "ssd_scan_roofline",
                 "gqa_attn_kernel_roofline", "relu2_expert_ffn_roofline",
                 "nemotron_h_mfu_pct"):
        assert harness.read_metric(name, bare) is None, name


def test_scan_reader_sums_the_ssd_calls_inside_the_task_programs():
    class Trace:
        def module_events(self, name):
            return [(10.0, 8.0, "jit_multi_step(1)")]

        def lane(self, name):
            return [(9.0, 1.0, "ssd.1"), (11.0, 0.5, "ssd.3"),
                    (12.0, 0.25, "ssd"), (13.0, 4.0, "attn.2"),
                    (14.0, 1.0, "ssd_other"), (19.0, 1.0, "ssd.9")]

    run = {"trace": Trace(), "traffic": {}, "steps_per_task": 4}
    assert harness.read_metric("ssd_scan_ms", run) == 1e3 * 0.75 / 4
