"""Transformer LM: single-chip forward, dp/sp/tp mesh training parity,
expert-parallel MoE, sharding placement."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.core.step import build_train_step
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    transformer_sharding_rules,
)
from elasticdl_tpu.parallel import rules as rules_lib
from elasticdl_tpu.parallel.mesh import make_mesh
from elasticdl_tpu.parallel.mesh_runner import MeshRunner


def _zoo_module():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "model_zoo", "transformer", "transformer_lm.py",
    )
    spec = importlib.util.spec_from_file_location("transformer_lm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CFG = TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_len=32, compute_dtype=jnp.float32,
)


def _batch(b=8, s=16, vocab=32, seed=0):
    rng = np.random.RandomState(seed)
    start = rng.randint(0, vocab, (b, 1))
    seq = (start + np.arange(s + 1)[None, :]) % vocab  # learnable: +1 chain
    return {
        "features": seq[:, :-1].astype(np.int32),
        "labels": seq[:, 1:].astype(np.int32),
        "mask": np.ones((b,), np.float32),
    }


def _lm_loss():
    return _zoo_module().loss


def test_single_device_forward():
    model = TransformerLM(CFG)
    batch = _batch()
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["features"],
        training=False,
    )
    logits = model.apply(variables, batch["features"], training=False)
    assert logits.shape == (8, 16, 32)
    assert logits.dtype == jnp.float32


def _runner(mesh, model):
    zoo = _zoo_module()
    rule = rules_lib.regex_param_rule(
        transformer_sharding_rules(), mesh=mesh
    )
    return MeshRunner(
        mesh=mesh, param_rule=rule, batch_rule=zoo.batch_sharding_rule
    )


def test_mesh_training_matches_single_device():
    """3 optimizer steps on a (2,2,2) dp/sp/tp mesh == unsharded steps."""
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    loss_fn = _lm_loss()

    # Unsharded reference.
    model0 = TransformerLM(CFG)
    state0 = init_train_state(
        model0, optax.adam(1e-2), _batch(), seed=0
    )
    step0 = build_train_step(loss_fn)

    model1 = TransformerLM(CFG, mesh=mesh)
    runner = _runner(mesh, model1)
    state1 = runner.init_state(model1, optax.adam(1e-2), _batch(), seed=0)
    step1 = runner.train_step(loss_fn)

    for i in range(3):
        batch = _batch(seed=i)
        state0, m0 = step0(state0, batch)
        state1, m1 = step1(state1, batch)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m0["loss"]), rtol=2e-4, atol=2e-4
        )


def test_mesh_params_actually_sharded():
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    model = TransformerLM(CFG, mesh=mesh)
    runner = _runner(mesh, model)
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)

    wi = state.params["block_0"]["mlp"]["wi"]["kernel"]
    assert wi.sharding.spec == P(None, "tp")
    q = state.params["block_0"]["attn"]["query"]["kernel"]
    assert q.sharding.spec == P(None, "tp", None)
    # Adam moments co-shard with their param (slot co-location).
    mu_wi = state.opt_state[0].mu["block_0"]["mlp"]["wi"]["kernel"]
    assert mu_wi.sharding.spec == P(None, "tp")


def test_moe_expert_parallel():
    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, moe_every=2,
        compute_dtype=jnp.float32,
    )
    mesh = make_mesh((2, 4), ("dp", "ep"), devices=jax.devices()[:8])
    model = TransformerLM(cfg, mesh=mesh)
    runner = _runner(mesh, model)
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)

    wi = state.params["block_1"]["moe"]["wi"]
    assert wi.shape == (4, 32, 64)
    # Mesh has no tp axis, so the hidden dim replicates; experts on ep.
    assert wi.sharding.spec == P("ep", None, None)

    step = runner.train_step(_lm_loss())
    losses = []
    for i in range(8):
        state, metrics = step(state, _batch(seed=i % 2))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_mesh_wiring_end_to_end(tmp_path):
    """Production wiring: record files → MiniCluster (same path as
    worker/main.py MESH strategy) → spec-driven rules activate — params
    land tp-sharded without any hand-assembly."""
    from elasticdl_tpu.testing.cluster import MiniCluster
    from elasticdl_tpu.testing.data import (
        create_lm_record_file,
        model_zoo_dir,
    )

    path = create_lm_record_file(
        str(tmp_path / "lm.rec"), 128, seq_len=16
    )
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    cluster = MiniCluster(
        model_zoo_dir(),
        "transformer.transformer_lm.custom_model",
        training_data=path,
        minibatch_size=16,
        num_epochs=1,
        mesh=mesh,
    )
    results = cluster.run()
    assert cluster.finished
    assert np.isfinite(results[0]["final_loss"])
    worker = cluster.workers[0]
    assert worker._spec.model.mesh is mesh
    wi = worker.state.params["block_0"]["mlp"]["wi"]["kernel"]
    assert wi.sharding.spec == P(None, "tp")


def test_remat_matches_plain():
    """remat=True changes memory, not math: same loss trajectory."""
    import dataclasses

    batch = _batch()
    losses = {}
    for remat in (False, True):
        cfg = dataclasses.replace(CFG, remat=remat)
        model = TransformerLM(cfg)
        state = init_train_state(model, optax.adam(1e-2), batch, seed=0)
        step = build_train_step(_lm_loss())
        run = []
        for i in range(3):
            state, m = step(state, _batch(seed=i))
            run.append(float(m["loss"]))
        losses[remat] = run
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-5, atol=1e-6)


def test_remat_gives_the_plain_models_bits_where_no_kernel_is_traced():
    """The quick twin of ``test_remat_matches_plain``: on the CPU path
    (the dense reference: no value bears the kernel's names, the policy
    keeps nothing) ``remat=True`` gives the loss and every gradient leaf
    of ``remat=False`` to the bit, each compiled as one program."""
    import dataclasses

    batch = _batch(b=2)
    loss_fn = _lm_loss()
    params = TransformerLM(CFG).init(
        jax.random.PRNGKey(0), batch["features"])["params"]

    def loss_and_grads(remat):
        model = TransformerLM(dataclasses.replace(CFG, remat=remat))

        def program_loss(p):
            out = model.apply({"params": p}, batch["features"],
                              training=True)
            return loss_fn(batch["labels"], out, batch["mask"])

        return jax.jit(jax.value_and_grad(program_loss))(params)

    loss, grads = loss_and_grads(True)
    want_loss, want = loss_and_grads(False)
    assert float(loss) == float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            got, ref, err_msg=jax.tree_util.keystr(path))


def test_a_recomputed_block_keeps_its_arguments_and_the_kernels_two(
        kernels_traced, capsys):
    """What a block under ``nn.remat`` with the kernels' policy holds
    for its backward pass: its arguments, o and the logsumexp of its
    attention kernel, nothing else."""
    import dataclasses

    import flax.linen as nn
    import jax.ad_checkpoint

    from elasticdl_tpu.models.transformer import Block
    from elasticdl_tpu.ops import flash_attention as flash

    seq = 128                       # the shortest the default blocks tile
    cfg = dataclasses.replace(CFG, max_len=seq, remat=True)
    x = jnp.zeros((2, seq, cfg.d_model), jnp.float32)
    params = jax.eval_shape(
        lambda: Block(cfg).init(jax.random.PRNGKey(0), x))["params"]
    block = nn.remat(Block, static_argnums=(2,),
                     policy=flash.remat_policy())(cfg)
    jax.ad_checkpoint.print_saved_residuals(
        lambda params, x: block.apply({"params": params}, x, True),
        params, x)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if " from the argument " not in line]
    heads = 2 * cfg.n_heads
    assert len(kept) == 2
    assert kept[0].startswith(f"f32[{heads},{seq},{cfg.head_dim}] ")
    assert kept[1].startswith(
        f"f32[{heads},{seq}] named 'flash_attention_lse' ")


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "remat"])
def test_the_models_gradient_runs_each_forward_kernel_once(
        kernels_traced, remat):
    """Two kernel calls a block (forward, backward) in the gradient
    of the whole model, recomputed or not."""
    import dataclasses

    from tests.test_flash_attention import count_calls

    cfg = dataclasses.replace(CFG, max_len=128, remat=remat)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, cfg.max_len), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    loss_fn = _lm_loss()

    def program_loss(p):
        out = model.apply({"params": p}, tokens, training=True)
        return loss_fn(tokens, out, jnp.ones((2,)))

    jaxpr = jax.make_jaxpr(jax.grad(program_loss))(params)
    assert count_calls(jaxpr.jaxpr) == 2 * cfg.n_layers


def test_moe_top2_routing():
    """k=2: combine weights are the renormalized top-2 gates (sum to 1,
    exactly two nonzero experts per token); training still learns."""
    import dataclasses

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, moe_every=2, moe_top_k=2,
        compute_dtype=jnp.float32,
    )
    mesh = make_mesh((2, 4), ("dp", "ep"), devices=jax.devices()[:8])
    model = TransformerLM(cfg, mesh=mesh)
    runner = _runner(mesh, model)
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)
    step = runner.train_step(_lm_loss())
    losses = []
    for i in range(10):
        state, metrics = step(state, _batch(seed=i % 2))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]

    # Inspect the combine weights directly on a single device.
    from elasticdl_tpu.models.transformer import MoE

    moe = MoE(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 32), jnp.float32)
    variables = moe.init({"params": jax.random.PRNGKey(0)}, x)

    # Recompute the routing exactly as the layer does.
    gates = jax.nn.softmax(
        x @ variables["params"]["router"]["kernel"]
        + variables["params"]["router"]["bias"], axis=-1
    )
    top_vals, _ = jax.lax.top_k(gates, 2)
    want = top_vals / top_vals.sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(want.sum(-1)), 1.0, rtol=1e-6)


def test_training_learns_on_dp_sp_tp():
    """Loss drops markedly on the deterministic +1-chain task."""
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    model = TransformerLM(CFG, mesh=mesh)
    runner = _runner(mesh, model)
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)
    step = runner.train_step(_lm_loss())
    first = None
    for i in range(20):
        state, metrics = step(state, _batch(seed=i % 4))
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.7, (first, last)


def test_moe_scatter_matches_dense_when_dropfree():
    """Capacity dispatch with C >= T*k is drop-free and must equal the
    dense one-hot dispatch exactly (same params, same routing)."""
    import dataclasses

    from elasticdl_tpu.models.transformer import MoE

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 32), jnp.float32)
    for k in (1, 2):
        cfg_d = TransformerConfig(
            vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_len=32, moe_experts=4, moe_top_k=k,
            compute_dtype=jnp.float32, moe_dispatch="dense",
        )
        cfg_s = dataclasses.replace(
            cfg_d, moe_dispatch="scatter", moe_capacity_factor=100.0
        )
        variables = MoE(cfg_d).init({"params": jax.random.PRNGKey(0)}, x)
        out_d = MoE(cfg_d).apply(variables, x)
        out_s = MoE(cfg_s).apply(variables, x)
        np.testing.assert_allclose(
            np.asarray(out_s), np.asarray(out_d), rtol=1e-5, atol=1e-5
        )


def test_moe_scatter_drops_over_capacity():
    """A tiny capacity factor drops tokens (they contribute zero)
    without NaNs or shape surprises."""
    from elasticdl_tpu.models.transformer import MoE

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, compute_dtype=jnp.float32,
        moe_dispatch="scatter", moe_capacity_factor=0.25,
    )
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 32), jnp.float32)
    variables = MoE(cfg).init({"params": jax.random.PRNGKey(0)}, x)
    out = MoE(cfg).apply(variables, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    # With C = ceil(16/4 * 0.25) = 1 per expert, most tokens drop -> the
    # output has genuinely zero rows (dropped tokens).
    row_norms = np.linalg.norm(np.asarray(out).reshape(-1, 32), axis=1)
    assert (row_norms == 0.0).any()


def test_moe_scatter_expert_parallel():
    """Scatter dispatch under a dp x ep mesh: experts shard over ep,
    training learns, and the mesh forward equals the single-device
    forward (the all-to-all exchange is exact)."""
    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, moe_every=2,
        compute_dtype=jnp.float32, moe_dispatch="scatter",
        moe_capacity_factor=100.0,
    )
    mesh = make_mesh((2, 4), ("dp", "ep"), devices=jax.devices()[:8])
    model = TransformerLM(cfg, mesh=mesh)
    runner = _runner(mesh, model)
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)
    wi = state.params["block_1"]["moe"]["wi"]
    assert wi.sharding.spec == P("ep", None, None)
    step = runner.train_step(_lm_loss())
    losses = []
    for i in range(8):
        state, metrics = step(state, _batch(seed=i % 2))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]

    # Forward equivalence mesh vs single device on identical params.
    single = TransformerLM(cfg, mesh=None)
    params_host = jax.device_get(state.params)
    batch = _batch()
    tokens = jnp.asarray(batch["features"], jnp.int32)
    out_mesh = jax.jit(
        lambda p, t: model.apply({"params": p}, t)
    )(state.params, tokens)
    out_single = jax.jit(
        lambda p, t: single.apply({"params": p}, t)
    )(params_host, tokens)
    np.testing.assert_allclose(
        np.asarray(out_mesh), np.asarray(out_single),
        rtol=2e-4, atol=2e-4,
    )


def test_moe_dispatch_validated():
    import dataclasses

    from elasticdl_tpu.models.transformer import MoE

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, compute_dtype=jnp.float32,
        moe_dispatch="gshard",
    )
    x = jnp.zeros((2, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="moe_dispatch"):
        MoE(cfg).init({"params": jax.random.PRNGKey(0)}, x)


def test_generate_with_scatter_moe():
    """KV-cache decoding through a scatter-dispatch MoE block: the
    capacity math must hold at t = B*1 tokens per decode step."""
    from elasticdl_tpu.models.transformer import TransformerLM, generate

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, moe_experts=4, moe_every=2,
        compute_dtype=jnp.float32, moe_dispatch="scatter",
    )
    model = TransformerLM(cfg)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (2, 4)), jnp.int32
    )
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, prompt, training=False
    )
    toks = generate(cfg, variables["params"], prompt, max_new_tokens=5)
    assert toks.shape == (2, 5)
    assert ((np.asarray(toks) >= 0) & (np.asarray(toks) < 32)).all()

    # Single-token decode steps force dense dispatch (capacity ~1 at
    # t=B would silently drop colliding tokens); the prefill keeps
    # scatter, which with capacity >= T is drop-free and numerically
    # equals dense. So with a drop-free capacity factor and identical
    # params, scatter and dense configs must generate IDENTICAL tokens
    # — not just finite ones.
    import dataclasses

    cfg_safe = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    cfg_dense = dataclasses.replace(cfg, moe_dispatch="dense")
    toks_safe = generate(
        cfg_safe, variables["params"], prompt, max_new_tokens=5
    )
    toks_dense = generate(
        cfg_dense, variables["params"], prompt, max_new_tokens=5
    )
    np.testing.assert_array_equal(
        np.asarray(toks_safe), np.asarray(toks_dense)
    )
