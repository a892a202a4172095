"""The second family (latent attention, sparse experts, multi-token
prediction) at a test size (``tests/tiny_mla_moe``, the CPU, float32):
its reference's control is refused on three seeds, a whole run of the
harness over it is ``correct``, and its operation and byte counts
against values worked by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os

import pytest

from benchmark import run as harness
from benchmark.lib import compare, counts_mla_moe, paths
from benchmark.tests import calibrate, routed_rows

TINY = os.path.join(paths.BENCH, "tests", "tiny_mla_moe")
CONFIG = os.path.join(TINY, "configs", "tiny-mla-moe.json")
TRAFFIC = os.path.join(TINY, "traffic", "tiny_steady.json")
SEEDS = (11, 3000000023, 123456789)


@pytest.mark.parametrize("seed", SEEDS)
def test_lower_precision_control_is_refused(seed):
    limits = paths.load_json(CONFIG)["limits"]["compared"]
    reading = calibrate.control_reading(CONFIG, TRAFFIC, seed)
    assert reading["control_precision"] == "bf16"
    control = dict(reading["control"], stray_rows_fed=0)
    rows = compare.verdicts(control, limits)
    assert not all(ok for *_, ok in rows), rows


def test_a_whole_run_at_the_test_size_is_correct(capsys):
    result, code = harness.run_cell(
        os.path.join(TINY, "manifest.json"), "tiny_mla_moe_steady",
        3000000029, seconds=4, trace=0, platform="cpu")
    assert code == 0
    compared = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("compared: "):
            compared[line.split()[1]] = not line.endswith("NOT OK")
    assert result["correct"] is True, compared
    assert compared["task_loss_gap"] and compared["grad_norm_gap"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    # The worker's third line, with each step's counters; the first
    # task's are the reference's counts for the same steps (float32 on
    # both sides: no choice turns on rounding): no row was dropped.
    work = os.path.join(paths.ROOT, ".bench_work", "tiny_mla_moe_steady")
    got = routed_rows.program_rows(os.path.join(work, "worker.log"))
    want = routed_rows.reference_rows(
        CONFIG, TRAFFIC, 3000000029, os.path.join(work, "feed.jsonl"))
    assert len(want) == 4 and got == want


# d 4, 2 heads of q/k 3 (nope 2, rope 1) and v 2, ranks 3 and 2; dense
# width 5, expert width 3; 2 layers (1 dense), 1 MTP module; 2 of 8
# experts held; vocabulary 7.
HAND = {
    "hidden_size": 4, "num_attention_heads": 2, "qk_nope_head_dim": 2,
    "qk_rope_head_dim": 1, "v_head_dim": 2, "q_lora_rank": 3,
    "kv_lora_rank": 2, "intermediate_size": 5, "moe_intermediate_size": 3,
    "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "n_routed_experts": 2,
    "n_shared_experts": 1, "router_width": 8, "vocab_size": 7,
}


def test_counts_against_hand_worked_values():
    c = counts_mla_moe
    assert c.attention_blocks(HAND) == 3 and c.expert_layers(HAND) == 2
    # W_qa 4x3 + W_qb 3x(2x3) + W_kva 4x(2+1) + W_kvb 2x(2x(2+2)) +
    # W_o (2x2)x4 = 12 + 18 + 12 + 16 + 16.
    assert c.attention_params(HAND) == 74
    assert c.expert_params(HAND) == 3 * 4 * 3 == 36
    # 3 x 74 attention + dense 3x4x5 = 60 + 2 x (router 32 + shared 36)
    # + eh_proj 2x4x4 = 32 + two heads 2 x 4x7 = 56.
    assert c.per_token_matmul_params(HAND) == 222 + 60 + 136 + 32 + 56
    # seq 3: a token sees 2 positions on average; 3 blocks x 2 ops x 2
    # heads x (3 + 2) x 2.
    assert c.attention_flops_per_token_fwd(HAND, 3) == 120
    # 2 rows x 3 tokens, 5 routed rows: 3 x (6 x (2 x 506 + 120) + 5 x 72).
    assert c.train_flops_per_step(HAND, 2, 3, 5) == 3 * (6 * 1132 + 360)
    # Embedding and head 2 x 28 + head bias 7 + last norm 4; a block: 74
    # + norms (4 + 4 + 3 + 2); dense 60; an expert layer: router 32 +
    # bias 8 + 3 experts x 36; MTP: eh 32 + three norms 12.
    assert c.param_count(HAND) == (
        56 + 7 + 4 + 3 * (74 + 13) + 60 + 2 * (32 + 8 + 108) + 44)
    kernels = c.attention_kernel_step(HAND, 2, 3)
    # 2 rows x 2 heads x 6 (query, key) pairs = 24; 3 blocks x 6 matmuls
    # over 2 x (3 + 2) / 2 each... = 3 x 3 x 2 x 5 x 24.
    assert kernels["flops"] == 3 * 3 * 2 * 5 * 24
    # 12 positions (row, head, token): 3 x (6 x 5 x 12 x 2 + 2 x 12 x 4).
    assert kernels["bytes"] == 3 * (720 + 96)
    ffn = c.expert_ffn_step(HAND, 5)
    assert ffn["flops"] == 3 * 2 * 5 * 36
    # Held weights 2 layers x 2 experts x 36, three passes; a row
    # crosses 2 x 4 + 3 x 3 = 17 numbers, three passes; 2 bytes each.
    assert ffn["bytes"] == (3 * 144 + 3 * 5 * 17) * 2


def test_real_configuration_is_what_the_issue_reckoned():
    cfg = paths.load_json(paths.config_path("joyai-llm-flash-ep16"))
    c = counts_mla_moe
    assert c.attention_params(cfg) == 26345472          # 26.35M
    assert c.expert_params(cfg) == 4718592              # 4.72M
    assert round(c.param_count(cfg) / 1e6, 1) == 680.5
    step = c.train_flops_per_step(cfg, 4, 4096, 40960)
    mla = 3 * 16384 * (2 * c.attention_blocks(cfg) * c.attention_params(cfg)
                       + c.attention_flops_per_token_fwd(cfg, 4096))
    assert 0.63 < mla / step < 0.66
    published = cfg["published"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 16, 16160]
    assert published == {"num_hidden_layers": 40, "n_routed_experts": 256,
                         "vocab_size": 129280}
    assert cfg["router_width"] == published["n_routed_experts"]


def test_host_moments_adam_is_adam_to_the_bit():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import adam, adam_hostmoments

    hyper = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    key = jax.random.PRNGKey(0)
    start = {"a": jax.random.normal(key, (64, 32)),
             "b": {"c": jax.random.normal(key, (7,)),
                   "d": jax.random.normal(key, (3, 5, 8))}}

    def three_steps(optimizer):
        w = jax.tree.map(lambda x: x + 0, start)
        state = optimizer.init(w)
        apply = jax.jit(lambda w, g, s: optimizer.update(w, g, s, hyper),
                        donate_argnums=(0, 2))
        for i in range(3):
            w, state = apply(
                w, jax.tree.map(lambda x: jnp.sin(x * (i + 1)), w), state)
        return w, state

    for got, want in zip(jax.tree.leaves(three_steps(adam_hostmoments)),
                         jax.tree.leaves(three_steps(adam))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_reference_optimizer_is_the_zoos(monkeypatch):
    """Warm-up, Adam and the selection bias's plain descent: three steps
    of ``reference/adam_hostmoments.py`` against the program's zoo
    optimizer (optax) on the same gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.reference import adam_hostmoments
    from elasticdl_tpu.core.model_spec import load_module

    zoo = load_module(os.path.join(
        paths.ROOT, "model_zoo", "mla_moe", "mla_moe_lm.py"))
    hyper = {"learning_rate": 1e-2, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
             "bias_update_speed": 0.05, "warmup_steps": 2}
    key = jax.random.PRNGKey(1)
    plain = {"block_1/router": jax.random.normal(key, (8, 4)),
             "block_1/router_b": jax.random.normal(key, (4,)),
             "wte": jax.random.normal(key, (5, 8))}
    tree = lambda w: {"block_1": {"moe": {  # noqa: E731
        "router": w["block_1/router"],
        "router_bias": w["block_1/router_b"]}}, "wte": w["wte"]}
    tx = zoo.optimizer(hyper["learning_rate"], hyper["bias_update_speed"],
                       hyper["warmup_steps"])
    params, opt_state = tree(plain), tx.init(tree(plain))
    w, state = dict(plain), adam_hostmoments.init(plain)
    for i in range(4):
        grads = {k: jnp.sign(jnp.sin(v * (i + 2))) if k.endswith("router_b")
                 else jnp.cos(v * (i + 1)) for k, v in w.items()}
        w, state = jax.jit(
            lambda w, g, s: adam_hostmoments.update(w, g, s, hyper))(
                w, grads, state)
        updates, opt_state = tx.update(tree(grads), opt_state, params)
        params = optax.apply_updates(params, updates)
        if i == 0:      # the warm-up starts from 0: Adam moved nothing
            np.testing.assert_array_equal(w["wte"], plain["wte"])
            assert not np.array_equal(w["block_1/router_b"],
                                      plain["block_1/router_b"])
        for got, want in zip(jax.tree.leaves(tree(w)),
                             jax.tree.leaves(params)):
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-7)


def _page(rows_total, rows_max, task_logs):
    return {
        'edl_tpu_worker_moe_rows_total{worker="0"}': rows_total,
        'edl_tpu_worker_moe_expert_rows_max{worker="0"}': rows_max,
        'edl_tpu_worker_phase_seconds_count{phase="task_log",worker="0"}':
            task_logs,
    }


def test_counter_readers_on_two_pages():
    run = {
        "master_open": _page(1000.0, 70.0, 6.0),
        "master_close": _page(1000.0 + 3 * 8 * 400.0, 90.0, 9.0),
        "steps_per_task": 8, "cfg": {"n_routed_experts": 16},
    }
    assert harness.read_metric("routed_rows_per_step", run) == 400.0
    assert harness.read_metric("expert_rows_max_over_mean", run) == (
        90.0 / (400.0 / 16))
    # A program without the counters: nothing to read, nothing raised.
    bare = {"master_open": {}, "master_close": {}, "steps_per_task": 8,
            "cfg": {"n_routed_experts": 16}, "traffic": {}, "tasks": [],
            "open_t": 0.0, "close_t": 1.0}
    for name in ("routed_rows_per_step", "expert_rows_max_over_mean",
                 "expert_ffn_ms", "expert_ffn_roofline",
                 "mla_moe_flops_util_pct", "mla_attn_kernel_roofline"):
        assert harness.read_metric(name, bare) is None, name
