"""The hybrid LM (``models/nemotron_h.py``) against its plain reference
(``benchmark/reference/nemotron_h.py``, whose scan is the recurrence
taken one position after another) at a tiny size, seeded weights,
float32: logits, loss, every gradient leaf, for a pattern with all three
kinds of layer; the expert layer's shares with relu^2 experts and a
wider shared expert; rows of no group; fused task == stepwise; a save
and a restore."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from elasticdl_tpu.core.model_spec import load_module
from elasticdl_tpu.core.step import _train_step_body, jit_step, jit_task
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.models import mla_moe, nemotron_h
from elasticdl_tpu.models.mla_moe import ExpertLayer
from elasticdl_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM
from tests.test_mla_moe import RUNGS_LANDED_ON, layer_on_a_rung

ZOO = load_module("model_zoo/nemotron_h/nemotron_h_lm.py")

# The reference's names for the sizes (the published config.json's).
CFG = {
    "name": "tiny", "hidden_size": 32, "hybrid_override_pattern": "ME*EM",
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "n_routed_experts": 4, "router_width": 8, "first_held": 2,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "vocab_size": 64, "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.2, "router_bias_std": 0.1,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
}
ROWS, SEQ = 2, 24


def program_config(cfg=CFG, **changes) -> NemotronHConfig:
    base = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_eps=cfg["layer_norm_epsilon"], compute_dtype=jnp.float32,
    )
    base.update(changes)
    return NemotronHConfig(**base)


@pytest.fixture(scope="module")
def seeded():
    weights = reference.weights(CFG, jax.random.PRNGKey(7))
    rows = np.random.default_rng(3).integers(
        0, CFG["vocab_size"], (ROWS, SEQ + 1))
    return weights, jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_reference_tree_is_the_programs(seeded):
    weights, tokens, _ = seeded
    model = NemotronHLM(program_config())
    want = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    got = {"params": reference.to_program_tree(weights, CFG)}
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [x.shape for x in jax.tree.leaves(want)] == [
        x.shape for x in jax.tree.leaves(got)]
    back = reference.from_program_tree(got["params"], CFG)
    assert set(back) == set(weights)
    for name, value in weights.items():
        np.testing.assert_array_equal(back[name], value)


def test_logits_and_loss_match_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = NemotronHLM(program_config())
    out = model.apply({"params": params}, tokens, training=True)
    row_logits = jax.jit(lambda w, row: reference.row_logits(w, row, CFG))
    want = [row_logits(weights, tokens[r]) for r in range(ROWS)]
    np.testing.assert_allclose(
        out["logits"], np.stack([w[0] for w in want]), atol=3e-5)
    assert int(out["metrics"]["moe_rows"]) == sum(int(w[1]) for w in want)
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens, training=False),
        out["logits"], atol=1e-6)
    loss = ZOO.loss(labels, out, jnp.ones((ROWS,)))
    want_loss = reference.loss_terms(weights, tokens, labels, CFG)["loss"]
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)


def test_every_gradient_leaf_matches_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = NemotronHLM(program_config())

    def loss(p):
        out = model.apply({"params": p}, tokens, training=True)
        return ZOO.loss(labels, out, jnp.ones((ROWS,)))

    got = jax.grad(loss)(params)
    _, want = jax.jit(lambda w: reference.loss_and_grads(
        w, tokens, labels, CFG))(weights)
    want = reference.to_program_tree(want, CFG)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == len(reference.layout(CFG))
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            a, b, atol=3e-5 * scale, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))


def test_routing_replay_and_the_selection_bias(seeded, highest):
    """``routing=`` holds the expert layers to choices given; the
    selection bias's "gradient" is its load's direction."""
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = NemotronHLM(program_config())
    own = reference.choices(weights, tokens, CFG)
    assert len(own) == 2 and own[0].shape == (ROWS, SEQ, 3)
    free = model.apply({"params": params}, tokens, training=True)
    held = model.apply({"params": params}, tokens, training=True,
                       routing=own)
    np.testing.assert_allclose(free["logits"], held["logits"], atol=1e-6)
    turned = [jnp.flip(c, axis=1) for c in own]
    other = model.apply({"params": params}, tokens, training=True,
                        routing=turned)
    assert float(jnp.max(jnp.abs(other["logits"] - free["logits"]))) > 1e-3
    grads = jax.grad(lambda p: ZOO.loss(
        labels, model.apply({"params": p}, tokens, training=True),
        jnp.ones((ROWS,))))(params)
    for name, picks in zip(reference.expert_layers(CFG), own):
        np.testing.assert_array_equal(
            grads[name]["moe"]["router_bias"],
            reference.load_direction(picks, CFG))


def _layer_inputs(width=8, held=8, d=32, f=16, fs=24, tokens=24, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)
    return {
        "x": mk(2, tokens // 2, d),
        "params": {
            "router": mk(d, width), "router_bias": mk(width) * 0.3,
            "w_up": mk(held, d, f), "w_down": mk(held, f, d),
            "shared": {"up": {"kernel": mk(d, fs)},
                       "down": {"kernel": mk(fs, d)}},
        },
    }


def _reference_layer(x, params, first, held, k, scaling=2.5):
    z = {"first": first, "held": held, "k": k}
    w = {"router": params["router"], "router_b": params["router_bias"],
         "e_up": params["w_up"], "e_down": params["w_down"],
         "s_up": params["shared"]["up"]["kernel"],
         "s_down": params["shared"]["down"]["kernel"]}
    out, chosen = reference.expert_layer(
        x.reshape(-1, x.shape[-1]), w, z,
        {"routed_scaling_factor": scaling}, "f32")
    local = np.asarray(chosen) - first
    return out, int(((local >= 0) & (local < held)).sum())


def test_shares_add_up_to_the_uncut_layer(highest):
    """Four shares of two relu^2 experts each: their routed parts, with
    the (wider) shared expert counted once, are the uncut reference's
    layer; and no choice of a held expert is lost on the way."""
    width, k = 8, 3
    given = _layer_inputs(width=width, held=width)
    x, params = given["x"], given["params"]
    whole, whole_count = _reference_layer(x, params, 0, width, k)
    assert whole_count == x.shape[0] * x.shape[1] * k
    shared = reference.relu2_mlp(
        x.reshape(-1, x.shape[-1]), params["shared"]["up"]["kernel"],
        params["shared"]["down"]["kernel"], "f32")
    total, rows = shared, 0
    for first in range(0, width, 2):
        cfg = program_config(router_width=width, first_held=first, n_held=2,
                             top_k=k)
        share = dict(params, **{name: params[name][first:first + 2]
                                for name in ("w_up", "w_down")})
        out, counters = ExpertLayer(cfg).apply({"params": share}, x)
        total = total + (out.reshape(shared.shape) - shared)
        rows += int(counters["moe_rows"])
    assert rows == whole_count
    np.testing.assert_allclose(total, whole, atol=2e-5)


@pytest.mark.parametrize("lands", RUNGS_LANDED_ON)
def test_relu2_experts_run_on_the_rung_their_rows_need(highest, monkeypatch,
                                                      lands):
    """The family's layer (relu^2 experts, a wider shared expert) under a
    ladder of three sizes, on each of them."""
    given = _layer_inputs(width=16, held=2, tokens=1024)
    cfg = program_config(router_width=16, first_held=2, n_held=2, top_k=2)
    layer_on_a_rung(monkeypatch, ExpertLayer(cfg), given["params"],
                    given["x"], lands)


def test_relu2_experts_over_an_ep_mesh_add_up(highest):
    """ep = 4 on the virtual CPU devices: experts without a gate matrix
    go through ``ExpertLayer._over_ep`` (two stacked operands, not
    three); result, counters and every gradient are the single-chip
    layer's."""
    from jax.sharding import Mesh

    given = _layer_inputs(width=8, held=8, seed=6)
    x, params = given["x"], given["params"]
    cfg = program_config(router_width=8, first_held=0, n_held=8, top_k=3)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("dp", "ep"))

    def layer(mesh):
        def total(p, x):
            out, counters = ExpertLayer(cfg, mesh).apply({"params": p}, x)
            return jnp.sum(out * out), (out, counters)
        return jax.jit(jax.value_and_grad(total, has_aux=True))(params, x)

    (_, (want, want_counters)), want_grads = layer(None)
    (_, (got, counters)), grads = layer(mesh)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(counters["moe_rows"]) == int(want_counters["moe_rows"])
    assert "w_gate" not in grads
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("f", [16, 1856])
def test_rows_of_no_group_may_hold_anything(monkeypatch, highest, f):
    """The relu^2 layer with the grouped product's dead rows poisoned,
    in its result and in its cotangent (a TPU leaves what the memory
    held there): the layer and its gradients are what they were. At
    1,856 the products run 2,048 wide, and the zero columns hold what
    they like past the live rows too."""
    from elasticdl_tpu.ops.grouped_matmul import grouped_matmul

    def in_a_group(x, sizes):
        return (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(in_a_group(lhs, sizes),
                         grouped_matmul(lhs, rhs, sizes), jnp.nan)

    def forward(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(res, g):
        lhs, rhs, sizes = res
        _, pull = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = pull(jnp.where(in_a_group(lhs, sizes), g, 0))
        return jnp.where(in_a_group(lhs, sizes), d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(forward, backward)
    given = _layer_inputs(width=8, held=3, seed=7, f=f)
    x, params = given["x"], given["params"]
    cfg = program_config(router_width=8, first_held=1, n_held=3, top_k=3,
                         moe_intermediate_size=f)

    def loss(params, x):
        out, _ = ExpertLayer(cfg).apply({"params": params}, x)
        return jnp.sum(out ** 2)

    want = jax.grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(mla_moe, "grouped_matmul", poisoned)
    got = jax.grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * f / 16)


def test_mixer_parts_by_hand():
    """The convolution is causal and depthwise; the group norm
    normalises each group alone."""
    x = jnp.arange(12, dtype=jnp.float32).reshape(1, 6, 2)
    weight = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [2.0, 0.0]])
    y = nemotron_h.causal_depthwise_conv(x, weight, jnp.asarray([0.5, 0.0]))
    # Channel 0: x_{t-2} + 2 x_t; channel 1: x_{t-1}.
    np.testing.assert_allclose(
        y[0, :, 0], [0.5, 4.5, 8.5, 14.5, 20.5, 26.5])
    np.testing.assert_allclose(y[0, :, 1], [0, 1, 3, 5, 7, 9])
    z = jnp.asarray([[3.0, 4.0, 0.0, 10.0]])
    normed = nemotron_h.group_rms_norm(z, jnp.ones((4,)), 2, 0.0)
    np.testing.assert_allclose(
        normed, [[3 / 12.5 ** 0.5, 4 / 12.5 ** 0.5, 0.0, 2 ** 0.5]],
        rtol=1e-6)


def test_remat_gives_the_plain_models_bits(seeded):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)

    def grads(remat):
        model = NemotronHLM(program_config(remat=remat))
        return jax.grad(lambda p: ZOO.loss(
            labels, model.apply({"params": p}, tokens, training=True),
            jnp.ones((ROWS,))))(params)

    for a, b in zip(jax.tree.leaves(grads(True)),
                    jax.tree.leaves(grads(False))):
        np.testing.assert_array_equal(a, b)


def _batch(tokens, labels):
    return {"features": np.asarray(tokens), "labels": np.asarray(labels),
            "mask": np.ones((ROWS,), np.float32)}


def test_fused_task_is_the_steps_one_by_one(seeded):
    """``core/step.py``: the model's counters leave the step beside the
    loss, and a fused task of three steps is three steps."""
    _, tokens, labels = seeded
    model = NemotronHLM(program_config(first_held=0, n_held=8))
    batches = [_batch(jnp.roll(tokens, i, axis=1), jnp.roll(labels, i, axis=1))
               for i in range(3)]
    state = init_train_state(model, ZOO.optimizer(), batches[0])
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    body = _train_step_body(ZOO.loss)
    fused_state, fused = jit_task(body, donate=False)(state, stacked)
    assert set(fused) == {"loss", "moe_rows", "moe_expert_rows_max",
                          "moe_bound_rows", "moe_overflow_layers"}
    step = jit_step(body, donate=False)
    losses = []
    for batch in batches:
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        # All eight experts held: every choice of both expert layers.
        assert int(metrics["moe_rows"]) == ROWS * SEQ * 3 * 2
    np.testing.assert_allclose(fused["loss"], losses, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(fused_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)


def test_state_is_saved_and_restored(tmp_path, seeded):
    from elasticdl_tpu.checkpoint.hooks import CheckpointHook, restore_from_dir

    _, tokens, labels = seeded
    model = NemotronHLM(program_config())
    batch = _batch(tokens, labels)
    state = init_train_state(model, ZOO.optimizer(), batch)
    state, _ = jit_step(_train_step_body(ZOO.loss), donate=False)(
        state, batch)
    hook = CheckpointHook(str(tmp_path), checkpoint_steps=1,
                          async_save=False)
    assert hook.save_final(state)
    hook.flush()
    fresh = init_train_state(model, ZOO.optimizer(), batch, seed=5)
    restored = restore_from_dir(fresh, str(tmp_path))
    assert int(restored.step) == int(state.step) == 1
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(restored.params)[0],
            jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(
            a, b, err_msg=jax.tree_util.keystr(path))
    for a, b in zip(jax.tree.leaves(restored.opt_state),
                    jax.tree.leaves(state.opt_state)):
        np.testing.assert_array_equal(a, b)


class _Lines(logging.Handler):
    def __init__(self, *loggers):
        super().__init__()
        self.lines = []
        self._loggers = loggers

    def emit(self, record):
        self.lines.append(record.getMessage())

    def __enter__(self):
        for logger in self._loggers:
            logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        for logger in self._loggers:
            logger.removeHandler(self)


def test_lines_say_the_scan_the_head_counts_and_the_experts_form():
    from elasticdl_tpu.ops import flash_attention as flash
    from elasticdl_tpu.ops import ssd_scan as ssd

    for cached in (ssd.log_traced, flash.log_traced,
                   mla_moe.log_traced_experts):
        cached.cache_clear()
    model = NemotronHLM(program_config())
    tokens = jnp.zeros((ROWS, SEQ), jnp.int32)
    with _Lines(ssd.logger, flash.logger, mla_moe.logger) as log:
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    assert sum(line.startswith("ssd: traced pallas chunk kernel")
               for line in log.lines) == 1
    assert any("x(2, 24, 4, 8), 2 groups, state 8, chunk 8: 3 chunks" in line
               and line.endswith("backward kernel") for line in log.lines)
    assert any("4 query heads over 2 key/value heads" in line
               for line in log.lines)
    assert any(line.endswith(
        "grouped product ragged_dot, experts relu2 of width 16, shared "
        "expert 24") for line in log.lines)
    # The published width: the line says where the products run.
    with _Lines(mla_moe.logger) as log:
        mla_moe.log_traced_experts(program_config(
            moe_intermediate_size=1856, shared_intermediate_size=3712,
            first_held=0, n_held=8, router_width=128, top_k=6),
            mla_moe.rows_ladder(98304, 8, 128), 1)
    mla_moe.log_traced_experts.cache_clear()
    assert log.lines == [
        "experts: traced drop-free layer holding experts [0, 8) of router "
        "width 128, top-6, rows bound 12288 / 24576 of 98304, grouped product "
        "ragged_dot, "
        "experts relu2 of width 1856, products at 2048 (zero columns), "
        "shared expert 3712"]


@pytest.mark.parametrize("fused", [True, False])
def test_worker_runs_the_zoo_module_and_counts(tmp_path, fused):
    """The unchanged master and worker run the zoo module; every trained
    task gets the routing line and the page's counters move."""
    from elasticdl_tpu.testing.cluster import MiniCluster
    from elasticdl_tpu.testing.data import (
        create_lm_record_file,
        model_zoo_dir,
    )
    from elasticdl_tpu.worker import worker as worker_mod

    train = create_lm_record_file(
        str(tmp_path / "t.rec"), 16, seed=5, seq_len=16, vocab=256)
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="nemotron_h.nemotron_h_lm.custom_model",
        training_data=train, minibatch_size=4,
        num_minibatches_per_task=2, fuse_task_steps=fused,
    )
    with _Lines(worker_mod.logger) as log:
        cluster.run()
    assert cluster.finished
    trained = [m for m in log.lines if " trained: " in m]
    routing = [m for m in log.lines if " routing: " in m]
    assert len(trained) == len(routing) == 2
    # The zoo's CONFIG holds all 8 experts of its two expert layers:
    # 4 rows x 16 tokens x top-2 x 2 layers, each of a task's 2 steps.
    for line in routing:
        assert "moe_rows=[256, 256]" in line.replace(", ", ",").replace(
            ",", ", "), line
