"""The latent-attention sparse-expert LM (``models/mla_moe.py``) against
its plain reference (``benchmark/reference/mla_moe.py``) at a tiny size,
seeded weights, float32: logits, both loss terms, every gradient leaf;
the expert layer's shares, its drop-free dispatch and its counters; the
interleaved RoPE; the flash kernels with a v head narrower than q/k's."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe as reference
from elasticdl_tpu.core.model_spec import load_module
from elasticdl_tpu.core.step import build_multi_step, build_train_step
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.models import mla_moe
from elasticdl_tpu.models.mla_moe import (
    ExpertLayer,
    MlaBlock,
    MlaMoeConfig,
    MlaMoeLM,
    held_experts_part,
    rope_interleaved,
)
from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.ops.ring_attention import dense_attention

ZOO = load_module("model_zoo/mla_moe/mla_moe_lm.py")

# The reference's names for the sizes (the published config.json's).
CFG = {
    "name": "tiny", "hidden_size": 32, "num_attention_heads": 2,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "q_lora_rank": 24, "kv_lora_rank": 16, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts": 4, "router_width": 8,
    "first_held": 2, "num_experts_per_tok": 3, "vocab_size": 64,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "routed_scaling_factor": 2.5,
    "mtp_loss_weight": ZOO.MTP_LOSS_WEIGHT, "initializer_range": 0.2,
    "router_bias_std": 0.1,
}
ROWS, SEQ = 2, 16


def program_config(cfg=CFG, **changes) -> MlaMoeConfig:
    base = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"], router_width=cfg["router_width"],
        first_held=cfg["first_held"], n_held=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        compute_dtype=jnp.float32,
    )
    base.update(changes)
    return MlaMoeConfig(**base)


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
    model = MlaMoeLM(program_config())
    want = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    got = {"params": reference.to_program_tree(weights, CFG)}
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [x.shape for x in jax.tree.leaves(want)] == [
        x.shape for x in jax.tree.leaves(got)]


def test_logits_and_both_loss_terms_match_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = MlaMoeLM(program_config())
    out = model.apply({"params": params}, tokens, training=True)
    row_logits = jax.jit(lambda w, row: reference.row_logits(w, row, CFG))
    want = [row_logits(weights, tokens[r]) for r in range(ROWS)]
    np.testing.assert_allclose(
        out["logits"], np.stack([w[0] for w in want]), atol=2e-5)
    np.testing.assert_allclose(
        out["mtp_logits"], np.stack([w[1] for w in want]), atol=2e-5)
    # Evaluation returns the main logits alone.
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens, training=False),
        out["logits"], atol=1e-6)
    main, mtp = ZOO.loss_terms(labels, out, jnp.ones((ROWS,)))
    row_loss = jax.jit(lambda w, row, lab: reference.row_loss(w, row, lab, CFG))
    terms = [row_loss(weights, tokens[r], labels[r])[1] for r in range(ROWS)]
    assert float(main) == pytest.approx(
        np.mean([t["main"] for t in terms]), abs=1e-5)
    assert float(mtp) == pytest.approx(
        np.mean([t["mtp"] for t in terms]), abs=1e-5)
    assert float(ZOO.loss(labels, out, jnp.ones((ROWS,)))) == pytest.approx(
        float(main) + ZOO.MTP_LOSS_WEIGHT * float(mtp), abs=1e-6)
    assert int(out["metrics"]["moe_rows"]) == sum(
        int(t["routed_rows"]) for t in terms)


def test_every_gradient_leaf_matches_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = MlaMoeLM(program_config())

    def program_loss(p):
        out = model.apply({"params": p}, tokens, training=True)
        return ZOO.loss(labels, out, jnp.ones((ROWS,)))

    loss, grads = jax.value_and_grad(program_loss)(params)
    want_loss, want = jax.jit(
        lambda w, t, l: reference.loss_and_grads(w, t, l, CFG)
    )(weights, tokens, labels)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    want = reference.to_program_tree(want, CFG)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * scale, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))
    # The selection bias only selects: what comes back for it is its
    # load's direction (the loop above held it to the reference's).
    assert set(np.unique(grads["block_1"]["moe"]["router_bias"])) <= {
        -1.0, 0.0, 1.0}


def _own_choices(model, params, tokens):
    _, sown = model.apply({"params": params}, tokens, training=True,
                          mutable=["intermediates"])
    blocks = sown["intermediates"]
    return [blocks[name]["moe"]["chosen"][0]
            for name in reference.expert_blocks(CFG)]


def test_routing_replay_holds_the_layers_to_the_choices_given(
        seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = MlaMoeLM(program_config())
    own = _own_choices(model, params, tokens)
    want = jax.jit(lambda w, t: reference.choices(w, t, CFG))(weights, tokens)
    assert len(own) == 3            # block_1, block_2, the MTP block
    for a, b in zip(own, want):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))
    out = model.apply({"params": params}, tokens, training=True)
    again = model.apply({"params": params}, tokens, training=True,
                        routing=own)
    np.testing.assert_array_equal(again["logits"], out["logits"])
    # Other experts than its own: every token to the three after them.
    other = [(c + 3) % CFG["router_width"] for c in own]
    moved = model.apply({"params": params}, tokens, training=True,
                        routing=other)
    main, mtp, chosen = jax.jit(lambda w, t, held: reference.hidden_states(
        w, t, CFG, held=held))(
            weights, tokens, dict(zip(reference.expert_blocks(CFG), other)))
    for name, held in zip(reference.expert_blocks(CFG), other):
        np.testing.assert_array_equal(chosen[name], held)
    np.testing.assert_allclose(
        moved["logits"],
        main @ weights["head_w"] + weights["head_b"], atol=2e-5)
    assert int(moved["metrics"]["moe_rows"]) == sum(
        int(reference.held_count(c, CFG)) for c in other)
    assert float(jnp.abs(moved["logits"] - out["logits"]).max()) > 1e-3


def test_selection_bias_moves_against_its_load_and_nothing_else_does(
        seeded, highest):
    """One step of the zoo's optimizer: every selection bias moves by the
    speed, down where its expert got more than the mean of the choices
    and up where it got fewer (counted here in numpy); Adam moves the
    rest."""
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = MlaMoeLM(program_config())
    own = _own_choices(model, params, tokens)
    speed = 0.25
    tx = ZOO.optimizer(1e-3, speed)

    def loss(p):
        out = model.apply({"params": p}, tokens, training=True)
        return ZOO.loss(labels, out, jnp.ones((ROWS,)))

    grads = jax.grad(loss)(params)
    updates, _ = tx.update(grads, tx.init(params), params)
    width = CFG["router_width"]
    for name, chosen in zip(reference.expert_blocks(CFG), own):
        load = np.bincount(np.asarray(chosen).ravel(), minlength=width)
        assert load.sum() == ROWS * SEQ * CFG["num_experts_per_tok"]
        np.testing.assert_allclose(
            updates[name]["moe"]["router_bias"],
            -speed * np.sign(load - load.mean()), atol=1e-7)
        np.testing.assert_array_equal(
            reference.load_direction(chosen, CFG),
            np.sign(load - load.mean()))
    step = np.abs(np.asarray(updates["block_1"]["moe"]["router"]))
    assert 0 < step.max() <= 1.001e-3


def _layer_inputs(width=8, held=8, d=32, f=16, tokens=24, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)
    return {
        "x": mk(2, tokens // 2, d),
        "params": {
            "router": mk(d, width), "router_bias": mk(width) * 0.3,
            "w_gate": mk(held, d, f), "w_up": mk(held, d, f),
            "w_down": mk(held, f, d),
            "shared": {n: {"kernel": mk(*s)} for n, s in (
                ("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))},
        },
    }


def _reference_layer(x, params, first, held, k, scaling=2.5):
    """The reference's expert layer over (tokens, d) rows."""
    z = {"first": first, "held": held, "k": k}
    w = {"router": params["router"], "router_b": params["router_bias"],
         "e_gate": params["w_gate"], "e_up": params["w_up"],
         "e_down": params["w_down"],
         **{f"s_{n}": params["shared"][n]["kernel"]
            for n in ("gate", "up", "down")}}
    out, chosen = reference.expert_layer(
        x.reshape(-1, x.shape[-1]), w, z,
        {"routed_scaling_factor": scaling}, "f32")
    local = np.asarray(chosen) - first
    return out, int(((local >= 0) & (local < held)).sum())


def test_shares_add_up_to_the_uncut_layer(highest):
    """Four shares of two experts each: their routed parts, with the
    shared expert counted once, are the uncut reference's layer."""
    width, k = 8, 3
    given = _layer_inputs(width=width, held=width)
    x, params = given["x"], given["params"]
    whole, whole_count = _reference_layer(x, params, 0, width, k)
    assert int(whole_count) == x.shape[0] * x.shape[1] * k
    shared = reference.gated_mlp(
        x.reshape(-1, x.shape[-1]),
        *(params["shared"][n]["kernel"] for n in ("gate", "up", "down")),
        "f32")
    total, rows = shared, 0
    for first in range(0, width, 2):
        cfg = program_config(
            router_width=width, first_held=first, n_held=2, top_k=k,
            moe_intermediate_size=16)
        share = dict(params, **{
            name: params[name][first:first + 2]
            for name in ("w_gate", "w_up", "w_down")})
        out, counters = ExpertLayer(cfg).apply({"params": share}, x)
        total = total + (out.reshape(shared.shape) - shared)
        rows += int(counters["moe_rows"])
    assert rows == int(whole_count)
    np.testing.assert_allclose(total, whole, atol=2e-5)


@pytest.mark.parametrize("held_first", [0, 2])
def test_no_row_is_lost_when_every_token_chooses_one_expert(
        held_first, highest):
    """A selection bias that sends every token to experts 2, 3, 4: far
    over any capacity a dropping dispatch would give them."""
    width, k = 8, 3
    given = _layer_inputs(width=width, held=4, seed=1)
    x, params = given["x"], given["params"]
    params["router_bias"] = jnp.where(
        (jnp.arange(width) >= 2) & (jnp.arange(width) < 5), 50.0, 0.0)
    cfg = program_config(
        router_width=width, first_held=held_first, n_held=4, top_k=k,
        moe_intermediate_size=16)
    out, counters = ExpertLayer(cfg).apply({"params": params}, x)
    want, count = _reference_layer(x, params, held_first, 4, k)
    tokens = x.shape[0] * x.shape[1]
    # Experts [0, 4) hold 2 and 3 of the chosen three; [2, 6) all three.
    assert int(count) == tokens * (2 if held_first == 0 else 3)
    assert int(counters["moe_rows"]) == int(count)
    assert int(counters["moe_expert_rows_max"]) == tokens
    np.testing.assert_allclose(
        out.reshape(want.shape), want, atol=2e-5)


def test_routing_counters_match_a_count_in_numpy(highest):
    width, k, first, held = 8, 3, 1, 5
    given = _layer_inputs(width=width, held=held, seed=2)
    x, params = given["x"], given["params"]
    cfg = program_config(
        router_width=width, first_held=first, n_held=held, top_k=k,
        moe_intermediate_size=16)
    _, counters = ExpertLayer(cfg).apply({"params": params}, x)
    rows = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    scores = 1 / (1 + np.exp(-rows @ np.asarray(params["router"],
                                                 np.float64)))
    biased = scores + np.asarray(params["router_bias"], np.float64)
    chosen = np.argsort(-biased, axis=1)[:, :k]
    per_expert = np.bincount(chosen.ravel(), minlength=width)[
        first:first + held]
    assert int(counters["moe_rows"]) == per_expert.sum()
    assert int(counters["moe_expert_rows_max"]) == per_expert.max()


def test_held_part_under_any_split_of_the_rows(highest):
    """The grouped products' static bound is tokens x k rows: the part is
    the same whether three or all eight experts are held."""
    rng = np.random.default_rng(5)
    t, k, d, f, n = 12, 2, 8, 4, 8
    rows = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    chosen = jnp.asarray(
        np.stack([rng.permutation(n)[:k] for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1, (t, k)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(n, d, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(n, f, d)), jnp.float32)
    whole, sizes = held_experts_part(rows, chosen, weights, wg, wu, wd, 0, n)
    assert int(sizes.sum()) == t * k
    parts = [held_experts_part(rows, chosen, weights, wg[a:b], wu[a:b],
                               wd[a:b], a, n)[0]
             for a, b in ((0, 3), (3, 8))]
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-4)


@pytest.mark.parametrize("f,width,tokens", [
    (16, 8, 24), (1856, 8, 24), (16, 64, 512)],
    ids=["16", "1856", "under_the_bound"])
def test_rows_of_no_group_may_hold_anything(monkeypatch, highest, f, width,
                                            tokens):
    """A grouped product says nothing of the rows past its groups, in its
    result or in its cotangent (a TPU leaves what the memory held; the
    CPU writes zeros, which hid it). With both poisoned the layer and its
    gradients are what they were; so too at a width whose products run
    zero-padded (1,856 at 2,048: gate and up each padded, cut at 2,048),
    and where the work runs over a bound's rows (3 experts of a router
    64 wide: 512 of 1,536 sorted places, most of them of no group)."""
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
    given = _layer_inputs(width=width, held=3, seed=7, f=f, tokens=tokens)
    x, params = given["x"], given["params"]
    cfg = program_config(router_width=width, first_held=1, n_held=3, top_k=3,
                         moe_intermediate_size=f)
    assert mla_moe.rows_ladder(tokens * 3, 3, width)[0] == min(
        tokens * 3, 512)

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


FORMS = pytest.mark.parametrize(
    "form", ["silu_gated", "relu2", "relu_gated"])


@pytest.mark.parametrize("choices,n,width,ladder", [
    (131072, 16, 128, (32768, 65536, 131072)),      # sdar_ep8_steady
    (131072, 16, 256, (16384, 32768, 131072)),      # joyai_ep16_steady
    (98304, 8, 128, (12288, 24576, 98304)),         # nemotron3n_ep16_steady
    (98304, 8, 64, (24576, 49152, 98304)),          # smallthinker_ep8_steady
    (4096, 2, 16, (1024, 2048, 4096)),
    (4096, 1, 64, (512, 4096)),     # 128 and 256 rows: one block twice
    (1024, 2, 16, (512, 1024)),     # the second rung meets the choices
    (2048, 8, 16, (2048,)),         # the first does
    (1000, 1, 16, (512, 1000)),     # choices of no whole block
    (96, 4, 8, (96,)), (1024, 16, 16, (1024,)),     # every expert held
])
def test_the_ladder_is_whole_blocks_rising_to_the_choices(choices, n, width,
                                                          ladder):
    got = mla_moe.rows_ladder(choices, n, width)
    assert got == ladder
    assert got[-1] == choices
    assert all(a < b for a, b in zip(got, got[1:]))
    assert all(rung % 512 == 0 for rung in got[:-1])
    # A rung holds its factor of the held share.
    for rung, factor in zip(got[:-1], mla_moe.RUNG_FACTORS):
        assert rung >= factor * choices * n / width


# Routings of ``_bounded_inputs`` by the rows the two held experts get
# of ``tokens`` x 4 token-choices.
ROUTINGS = ("free", "one_held", "at_the_bound", "all_held")


def _bounded_inputs(form, routing="free", dtype=jnp.float32, seed=11,
                    tokens=256):
    """``tokens`` x top 4 over a router 16 wide, experts 2 and 3 held: an
    eighth of the ``tokens * 4`` token-choices expected, so 256 tokens
    have the ladder (512, 1024) and 1,024 tokens (1024, 2048, 4096).
    ``free``: a random routing (an eighth is held); ``one_held``: every
    token chooses expert 2 once, ``tokens`` rows exactly;
    ``at_the_bound``: every token chooses both held experts, twice that;
    ``all_held``: every choice is a held expert's."""
    t, k, d, f, n, width, first = tokens, 4, 32, 16, 2, 16, 2
    rng = np.random.default_rng(seed)
    mk = lambda scale, *shape: jnp.asarray(
        rng.normal(0, scale, shape), jnp.float32)
    absent = np.stack([rng.permutation(np.arange(4, width))[:k]
                       for _ in range(t)])
    chosen = {
        "free": np.stack([rng.permutation(width)[:k] for _ in range(t)]),
        "one_held": np.concatenate(
            [np.full((t, 1), 2), absent[:, :3]], axis=1),
        "at_the_bound": np.concatenate(
            [np.tile([2, 3], (t, 1)), absent[:, :2]], axis=1),
        "all_held": np.tile([2, 3, 3, 2], (t, 1)),
    }[routing]
    assert mla_moe.rows_ladder(t * k, n, width) == {
        256: (512, 1024), 1024: (1024, 2048, 4096)}[tokens]
    return dict(
        rows=mk(1.0, t, d).astype(dtype), chosen=jnp.asarray(chosen, jnp.int32),
        weights=jnp.asarray(rng.uniform(0.1, 1, (t, k)), jnp.float32),
        w_gate=None if form == "relu2" else mk(d ** -0.5, n, d, f),
        w_up=mk(d ** -0.5, n, d, f), w_down=mk(f ** -0.5, n, f, d),
        first_held=first, form=form)


def _part_and_gradients(given, router_width, part_of=held_experts_part):
    """(part, held rows, the gradient of rows, weights and every weight
    stack) under a fixed random cotangent, compiled as one program."""
    names = [name for name in ("rows", "weights", "w_gate", "w_up", "w_down")
             if given[name] is not None]
    pull = jnp.asarray(np.random.default_rng(1).normal(
        size=given["rows"].shape), jnp.float32)

    def loss(*moving):
        part, sizes = part_of(**dict(given, **dict(zip(names, moving))),
                              router_width=router_width)
        return jnp.sum(part.astype(jnp.float32) * pull), (part, sizes)

    (_, (part, sizes)), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(len(names))), has_aux=True))(
            *(given[name] for name in names))
    return part, sizes, dict(zip(names, grads))


def _one_choice_after_another(rows, chosen, weights, w_gate, w_up, w_down,
                              first_held, router_width, form):
    """The plain layer: every choice of a held expert by that expert's
    own matrices, float32."""
    n = w_up.shape[0]
    act = {"silu_gated": jax.nn.silu, "relu_gated": jax.nn.relu,
           "relu2": mla_moe.relu2}[form]
    dot = lambda a, b: jnp.einsum(
        "td,tdf->tf", a, b, precision=jax.lax.Precision.HIGHEST)
    rows32 = rows.astype(jnp.float32)
    out = jnp.zeros_like(rows32)
    for j in range(chosen.shape[1]):
        local = chosen[:, j] - first_held
        held = (local >= 0) & (local < n)
        e = jnp.clip(local, 0, n - 1)
        hidden = (act(dot(rows32, w_up[e])) if w_gate is None else
                  act(dot(rows32, w_gate[e])) * dot(rows32, w_up[e]))
        out = out + jnp.where(held, weights[:, j], 0.0)[:, None] * dot(
            hidden, w_down[e])
    held_rows = jnp.sum((chosen >= first_held) & (chosen < first_held + n))
    return out.astype(rows.dtype), held_rows


@pytest.fixture
def places_run(monkeypatch):
    """The sorted places every executed ``_part_over`` ran over, in
    order (a conditional traces every branch and runs one)."""
    seen = []
    plain = mla_moe._part_over

    def noting(form, rows, weights, w_up, w_down, order, *rest):
        jax.debug.callback(lambda: seen.append(order.shape[0]))
        return plain(form, rows, weights, w_up, w_down, order, *rest)

    monkeypatch.setattr(mla_moe, "_part_over", noting)
    yield seen
    jax.effects_barrier()


@FORMS
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tokens,routing,rung", [
    (256, "free", 512), (1024, "free", 1024), (1024, "at_the_bound", 2048)],
    ids=["the_first_of_two", "the_first_of_three", "the_second_of_three"])
def test_under_the_bound_the_layer_is_the_one_over_every_choice(
        highest, places_run, form, dtype, tol, tokens, routing, rung):
    """A held share of 1/8: the work over a rung's rows (512 of 1,024
    sorted places; 1,024 or 2,048 of 4,096, as the routing's held rows
    need) gives the part, the held rows and every gradient of the work
    over all of them (the layer told its experts are the whole router:
    one path, no conditional)."""
    given = _bounded_inputs(form, routing, dtype=dtype, tokens=tokens)
    got = _part_and_gradients(given, router_width=16)
    jax.effects_barrier()
    assert places_run == [rung, rung]       # forward, recomputed to pull
    want = _part_and_gradients(given, router_width=2)
    jax.effects_barrier()
    assert places_run[2:] == [tokens * 4]
    ladder = mla_moe.rows_ladder(tokens * 4, 2, 16)
    assert (0, *ladder)[ladder.index(rung)] < int(want[1].sum()) <= rung
    np.testing.assert_array_equal(got[1], want[1])
    def close(a, b, name, scale=1.0):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=tol,
            atol=tol * scale, err_msg=name)

    close(got[0], want[0], "part")
    for name in want[2]:
        assert got[2][name].shape == given[name].shape
        # PR 37's limit, but for a weight stack's gradient over 1,024
        # tokens: a sum over up to 2,048 rows of a group, which the
        # grouped product's transpose adds up in other blocks over a
        # rung's rows than over all 4,096 (float32 reads 0.9-2.7e-5 apart
        # on elements up to 35-91, 2 to 10 times the limit; every other
        # leaf and case holds it), so by the gradient's largest element.
        wide = tokens == 1024 and name in ("w_gate", "w_up", "w_down")
        close(got[2][name], want[2][name], name,
              float(np.abs(want[2][name]).max()) if wide else 1.0)


@FORMS
@pytest.mark.parametrize("tokens,routing,places", [
    (256, "all_held", 1024), (256, "at_the_bound", 512),
    (1024, "all_held", 4096), (1024, "at_the_bound", 2048),
    (1024, "one_held", 1024)])
def test_rows_past_the_bound_take_the_path_over_every_choice(
        highest, places_run, form, tokens, routing, places):
    """Every choice a held expert's: rows past every rung, so the step
    runs over every token-choice, forward and backward, and is the plain
    layer; held rows of a rung's size exactly still fit under it."""
    given = _bounded_inputs(form, routing, tokens=tokens)
    part, sizes, grads = _part_and_gradients(given, router_width=16)
    jax.effects_barrier()
    assert places_run == [places, places]
    want, held_rows, want_grads = _part_and_gradients(
        given, 16, _one_choice_after_another)
    assert int(sizes.sum()) == int(held_rows) == places
    np.testing.assert_allclose(part, want, rtol=1e-5, atol=1e-5)
    for name, grad in grads.items():
        scale = max(1.0, float(jnp.max(jnp.abs(want_grads[name]))))
        np.testing.assert_allclose(grad, want_grads[name], rtol=1e-5,
                                   atol=1e-5 * scale, err_msg=name)


def _primitives(jaxpr, name):
    from tests.test_flash_attention import count_calls
    return count_calls(jaxpr.jaxpr, name)


# A selection bias that sends 1,024 tokens' top 2 of 16 to the held
# experts 2 and 3 as a rung of the ladder (512, 1024, 2048) needs: left
# alone (about 256 held rows), every token to expert 2 and never to 3
# (1,024 exactly), every token to both (2,048: past every rung).
LANDS = pytest.mark.parametrize("pull,rows,rung", [
    ((0.0, 0.0), None, 512), ((50.0, -50.0), 1024, 1024),
    ((50.0, 50.0), 2048, 2048)],
    ids=["the_first_rung", "the_second_rung", "every_token_choice"])


def _pulled(params, pull):
    return dict(params, router_bias=jnp.zeros(16).at[2:4].set(
        jnp.asarray(pull)))


RUNGS_LANDED_ON = ["the_first_rung", "the_second_rung", "every_token_choice"]


def layer_on_a_rung(monkeypatch, layer, params, x, lands, **given):
    """For the families' own files: ``layer`` (an ``ExpertLayer`` that
    holds experts 2 and 3 of 16, top 2) over the 1,024 tokens ``x``, its
    ladder (512, 1024, 2048), held to a routing that ``lands`` on a rung
    (128 rows; every token to expert 2, 1,024; every token to both,
    2,048): the counters read that rung and the result is the layer's
    without a ladder."""
    choose = {"the_first_rung": (np.arange(1024) < 128, (2, 8), 128, 512),
              "the_second_rung": (True, (2, 8), 1024, 1024),
              "every_token_choice": (True, (2, 3), 2048, 2048)}
    some, to, rows, rung = choose[lands]
    routing = jnp.asarray(np.where(
        np.reshape(some, (-1, 1)), to, (8, 9)) * np.ones((1024, 1), int),
        jnp.int32).reshape(2, 512, 2)
    assert mla_moe.rows_ladder(2048, 2, 16) == (512, 1024, 2048)
    out, counters = layer.apply({"params": params}, x, routing=routing,
                                **given)
    assert int(counters["moe_rows"]) == rows
    assert int(counters["moe_bound_rows"]) == rung
    assert int(counters["moe_overflow_layers"]) == (rung == 2048)
    monkeypatch.setattr(mla_moe, "RUNG_FACTORS", ())    # one path: no bound
    want, counters = layer.apply({"params": params}, x, routing=routing,
                                 **given)
    assert int(counters["moe_bound_rows"]) == 2048
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=2e-6)


@FORMS
@LANDS
def test_an_overflowing_layer_is_counted_and_a_fitting_one_is_not(
        highest, monkeypatch, form, pull, rows, rung):
    """The layer's own counters: ``moe_bound_rows`` reads the rung the
    step's held rows need, ``moe_overflow_layers`` 1 only where they
    pass every rung and the layer runs over every token-choice (and
    loses nothing there or on a rung)."""
    width, k = 16, 2
    given = _layer_inputs(width=width, held=2, tokens=1024, seed=3)
    x, params = given["x"], given["params"]
    cfg = program_config(
        router_width=width, first_held=2, n_held=2, top_k=k,
        moe_intermediate_size=16, expert_form=form)
    if form == "relu2":
        del params["w_gate"], params["shared"]["gate"]
    assert mla_moe.rows_ladder(1024 * k, 2, width) == (512, 1024, 2048)
    pulled = _pulled(params, pull)
    out, counters = ExpertLayer(cfg).apply({"params": pulled}, x)
    assert int(counters["moe_rows"]) == rows or (
        rows is None and 0 < int(counters["moe_rows"]) < rung)
    assert int(counters["moe_bound_rows"]) == rung
    assert int(counters["moe_overflow_layers"]) == (rung == 1024 * k)
    monkeypatch.setattr(mla_moe, "RUNG_FACTORS", ())    # one path: no bound
    want, counters = ExpertLayer(cfg).apply({"params": pulled}, x)
    assert int(counters["moe_bound_rows"]) == 1024 * k
    assert int(counters["moe_overflow_layers"]) == 0
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=2e-6)


@FORMS
@pytest.mark.parametrize("tokens,k,pull,rung", [
    (256, 4, (0.0, 0.0), 512), (1024, 2, (0.0, 0.0), 512),
    (1024, 2, (50.0, -50.0), 1024), (1024, 2, (50.0, 50.0), 2048)],
    ids=["the_first_of_two", "the_first_of_three", "the_second_of_three",
         "every_token_choice"])
def test_a_recomputed_block_runs_no_product_a_second_time(
        places_run, form, tokens, k, pull, rung):
    """A block under ``nn.remat`` with the kernels' policy, its expert
    layer under a ladder: the gradients are the plain block's, forward
    and backward run over the same rung, and the rematted program holds
    the grouped products of the plain one (the rule's own residuals are
    the layer's arguments, so the recomputed forward conditional is dead
    code), where a recomputed layer without the rule would run its
    forward products again."""
    import flax.linen as nn

    from elasticdl_tpu.ops.flash_attention import remat_policy

    cfg = program_config(
        router_width=16, first_held=2, n_held=2, top_k=k, expert_form=form)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, tokens // 2, cfg.hidden_size)), jnp.float32)
    ladder = mla_moe.rows_ladder(tokens * k, 2, 16)
    assert ladder == ((512, 1024) if tokens == 256 else (512, 1024, 2048))
    params = MlaBlock(cfg).init(jax.random.PRNGKey(0), x)["params"]
    params = dict(params, moe=_pulled(params["moe"], pull))
    jax.effects_barrier()
    del places_run[:]               # ``init`` ran the layer too

    def gradient(block):
        def loss(params, x):
            return jnp.sum(block.apply({"params": params}, x)[0] ** 2)
        return jax.grad(loss, (0, 1))

    plain = gradient(MlaBlock(cfg))
    rematted = gradient(nn.remat(MlaBlock, policy=remat_policy())(cfg))
    got = jax.jit(rematted)(params, x)
    jax.effects_barrier()
    # Forward and the backward's own run (the fixture's callback keeps
    # the recomputed forward alive too): one rung.
    assert set(places_run) == {rung} and len(places_run) >= 2
    for got, want in zip(jax.tree.leaves(got),
                         jax.tree.leaves(jax.jit(plain)(params, x))):
        scale = max(1.0, float(jnp.max(jnp.abs(want))))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    conds, products = (
        [_primitives(jax.make_jaxpr(fn)(params, x), name)
         for fn in (plain, rematted)]
        for name in ("cond", "ragged_dot_general"))
    assert conds == [2, 2]          # forward, backward
    # Forward: 2 products a branch. Backward: a branch runs its 2 again
    # and pulls through each twice (rows, weights).
    assert products == [len(ladder) * (2 + 2 + 4)] * 2


def test_a_layer_that_holds_the_whole_router_traces_no_conditional():
    given = _bounded_inputs("silu_gated")
    traced = jax.make_jaxpr(
        lambda rows, weights: held_experts_part(
            **dict(given, rows=rows, weights=weights), router_width=2)[0])(
                given["rows"], given["weights"])
    assert _primitives(traced, "cond") == 0
    assert _primitives(traced, "ragged_dot_general") == 2
    bounded = jax.make_jaxpr(
        lambda rows, weights: held_experts_part(
            **dict(given, rows=rows, weights=weights), router_width=16)[0])(
                given["rows"], given["weights"])
    assert _primitives(bounded, "cond") == 1


def test_interleaved_rope_turns_the_pairs_by_hand():
    """Rotary size 4, theta 100: pair i of position p turns by
    p * 100^(-i/2): angles p and p / 10."""
    x = jnp.asarray(np.arange(1, 13, dtype=np.float32).reshape(1, 3, 4))
    got = np.asarray(rope_interleaved(x, 100.0))
    for p in range(3):
        a, b, c, d = np.asarray(x[0, p], np.float64)
        want = [a * np.cos(p) - b * np.sin(p), a * np.sin(p) + b * np.cos(p),
                c * np.cos(p / 10) - d * np.sin(p / 10),
                c * np.sin(p / 10) + d * np.cos(p / 10)]
        np.testing.assert_allclose(got[0, p], want, rtol=1e-5, atol=1e-5)
    # Position 1, the pair (1, 0): (cos 1, sin 1).
    unit = jnp.zeros((1, 2, 2)).at[0, 1, 0].set(1.0)
    np.testing.assert_allclose(
        rope_interleaved(unit, 100.0)[0, 1], [np.cos(1), np.sin(1)],
        rtol=1e-6)
    # The reference's own RoPE (rows without the batch axis) agrees.
    np.testing.assert_allclose(
        reference.rope(x[0], 100.0), got[0], rtol=1e-6, atol=1e-6)


def _narrow_v(seed, s=64, d=24, dv=16):
    rng = np.random.RandomState(seed)
    mk = lambda width: jnp.asarray(rng.randn(2, s, 2, width), jnp.float32) * 0.3
    return mk(d), mk(d), mk(dv)


# Blocks of 16: the grid of whole tiles; one 64 block: the strip walk
# inside a grid tile (``SUB_TILE`` is lowered for it); blocks of 32 and
# 16 over sub-tiles of 16 and 8: grids of 2 x 2 and 4 x 4 tiles whose
# diagonal tiles are walked (the benchmark cell's S = 4,096 is the
# latter at 1024 over 256).
@pytest.mark.parametrize(
    "blocks,sub,tiles",
    [((16, 16), None, (16, 0, 0)), ((32, 16), None, (8, 0, 0)),
     ((64, 64), 16, (0, 1, 0)), ((32, 32), 16, (1, 2, 1)),
     ((16, 16), 8, (6, 4, 6))])
def test_flash_with_a_narrower_v_head_matches_dense(blocks, sub, tiles,
                                                    monkeypatch):
    from elasticdl_tpu.ops import flash_attention as flash

    if sub:
        monkeypatch.setattr(flash, "SUB_TILE", sub)
    assert flash.tile_plan(64, 64, block_q=blocks[0],
                           block_k=blocks[1]).tiles == tiles
    q, k, v = _narrow_v(4)
    scale = q.shape[-1] ** -0.5

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        assert out.shape == v.shape
        return jnp.sum(out ** 2), out

    def kernels(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=True)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=True, scale=scale)

    (_, got), got_grads = jax.value_and_grad(
        lambda *a: loss(kernels, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        lambda *a: loss(dense, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize(
    "remat,kept",
    [(False, ""),
     (True, "; under remat the block keeps o and logsumexp (136.3 MB), the "
            "forward kernel is not run again")],
    ids=["no_remat", "remat"],
)
def test_attention_line_reports_how_the_cells_grid_is_spent(monkeypatch,
                                                            remat, kept):
    """The worker's attention line for the benchmark cell's shape (4 x
    4,096 tokens, 32 heads of 192 over 128, bfloat16): the head sizes,
    the grid's spending as ``tile_plan`` counts it and, with remat on,
    what the recomputed block keeps of the kernel."""
    from elasticdl_tpu.ops import flash_attention as flash

    cfg = program_config(
        hidden_size=64, num_heads=32, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, remat=remat,
        compute_dtype=jnp.bfloat16)
    plan = flash.tile_plan(4096, 4096)
    assert plan.tiles == (6, 4, 6)
    assert (plan.computed, plan.total) == (10, 16)
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash.logger.addHandler(handler)
    flash.log_traced.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        x = jax.ShapeDtypeStruct((4, 4096, cfg.hidden_size), jnp.float32)
        jax.eval_shape(
            lambda x: mla_moe.LatentAttention(cfg).init(
                jax.random.PRNGKey(0), x), x)
    finally:
        flash.logger.removeHandler(handler)
        flash.log_traced.cache_clear()
    assert records == [
        "attention: traced pallas flash kernel for q(4, 4096, 32, 192): tpu "
        "backend, shape tiles the kernel blocks; head sizes q/k 192, v 128; "
        "grid 4x4 of blocks 1024x1024: 6 tiles whole and unmasked, 4 "
        "diagonal tiles walked 10 of 16 sub-tiles 256x256, 6 skipped; "
        "backward: one kernel, 5 products a tile" + kept
    ]


def test_remat_gives_the_plain_models_bits_where_no_kernel_is_traced(
        seeded):
    """On the CPU path (the dense reference: no value bears the kernel's
    names, the policy keeps nothing) ``remat=True`` recomputes every
    block and gives the loss and every gradient leaf of ``remat=False``
    to the bit, each compiled as one program (op by op a block under
    remat is still run as a program of its own, and rounds as one)."""
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)

    def loss_and_grads(remat):
        model = MlaMoeLM(program_config(remat=remat))

        def program_loss(p):
            out = model.apply({"params": p}, tokens, training=True)
            return ZOO.loss(labels, out, jnp.ones((ROWS,)))

        return jax.jit(jax.value_and_grad(program_loss))(params)

    loss, grads = loss_and_grads(True)
    want_loss, want = loss_and_grads(False)
    assert float(loss) == float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            got, ref, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("use_experts", [False, True],
                         ids=["dense_block", "expert_block"])
def test_a_recomputed_block_keeps_its_arguments_and_the_kernels_two(
        kernels_traced, capsys, use_experts):
    """What a block under ``nn.remat`` with the kernels' policy holds
    for its backward pass: its arguments (and RoPE's two constants), o
    and the logsumexp of its attention kernel, nothing else."""
    import flax.linen as nn
    import jax.ad_checkpoint

    from elasticdl_tpu.ops import flash_attention as flash

    cfg = program_config()
    seq = 128                       # the shortest the default blocks tile
    x = jnp.zeros((ROWS, seq, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(
        lambda: MlaBlock(cfg, use_experts=use_experts).init(
            jax.random.PRNGKey(0), x))["params"]
    block = nn.remat(MlaBlock, policy=flash.remat_policy())(
        cfg, use_experts=use_experts)
    jax.ad_checkpoint.print_saved_residuals(
        lambda params, x: block.apply({"params": params}, x)[0], params, x)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if " from the argument " not in line
            and not line.endswith("from a constant")]
    heads = ROWS * cfg.num_heads
    assert len(kept) == 2
    assert kept[0].startswith(f"f32[{heads},{seq},{cfg.v_head_dim}] ")
    assert kept[1].startswith(
        f"f32[{heads},{seq}] named 'flash_attention_lse' ")


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "remat"])
def test_the_models_gradient_runs_each_forward_kernel_once(
        kernels_traced, remat):
    """Two kernel calls a block (forward, backward) in the gradient
    of the whole model, recomputed or not: 3 blocks and the MTP block."""
    from tests.test_flash_attention import count_calls

    model = MlaMoeLM(program_config(remat=remat))
    tokens = jnp.zeros((ROWS, 128), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))["params"]

    def program_loss(p):
        out = model.apply({"params": p}, tokens, training=True)
        return ZOO.loss(tokens, out, jnp.ones((ROWS,)))

    jaxpr = jax.make_jaxpr(jax.grad(program_loss))(params)
    assert count_calls(jaxpr.jaxpr) == 2 * 4


def test_wide_heads_raise_the_kernels_vmem_limit_and_others_do_not():
    from elasticdl_tpu.ops import flash_attention as flash

    narrow = flash._compiler_params(("parallel",), 64, 64)
    assert narrow.vmem_limit_bytes is None
    assert flash._compiler_params(("parallel",), 128, 128) == (
        flash.pltpu.CompilerParams(dimension_semantics=("parallel",)))
    wide = flash._compiler_params(("parallel",), 192, 128)
    assert wide.vmem_limit_bytes == flash.WIDE_HEAD_VMEM_BYTES
    assert flash._blocks(4096, 4096, 0, 0) == (1024, 1024)


def test_step_metrics_carry_the_models_counters(seeded):
    """``core/step.py``: the model's ``metrics`` leave the step beside
    the loss, per step of a fused task too."""
    _, tokens, labels = seeded
    model = MlaMoeLM(program_config(first_held=0, n_held=8))
    batch = {"features": np.asarray(tokens), "labels": np.asarray(labels),
             "mask": np.ones((ROWS,), np.float32)}
    state = init_train_state(model, ZOO.optimizer(), batch)
    state, metrics = build_train_step(ZOO.loss)(state, batch)
    assert set(metrics) == {"loss", "moe_rows", "moe_expert_rows_max",
                            "moe_bound_rows", "moe_overflow_layers"}
    # All eight experts held: every choice of every expert layer (and
    # the MTP block's) is a held one.
    layers = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] + 1
    assert int(metrics["moe_rows"]) == (
        ROWS * SEQ * CFG["num_experts_per_tok"] * layers)
    stacked = jax.tree.map(lambda x: np.stack([x, x]), batch)
    state, metrics = build_multi_step(ZOO.loss)(state, stacked)
    assert metrics["moe_rows"].shape == (2,)
    assert metrics["loss"].shape == (2,)


def test_expert_layer_line_is_logged_once():
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    mla_moe.logger.addHandler(handler)
    mla_moe.log_traced_experts.cache_clear()
    try:
        for _ in range(2):
            mla_moe.log_traced_experts(program_config(), (96,), 1)
        mla_moe.log_traced_experts(
            program_config(), mla_moe.rows_ladder(131072, 16, 128), 1)
    finally:
        mla_moe.logger.removeHandler(handler)
        mla_moe.log_traced_experts.cache_clear()
    assert records == [
        "experts: traced drop-free layer holding experts [2, 6) of router "
        f"width 8, top-3, rows bound {bound}, grouped product ragged_dot"
        for bound in ("96 of 96", "32768 / 65536 of 131072")]


@pytest.mark.parametrize("ep,width,held,k,tokens", [
    (4, 8, 8, 3, 24), (2, 16, 4, 4, 256)],
    ids=["whole_router_over_4", "a_quarter_over_2_under_the_bound"])
def test_over_an_ep_mesh_the_members_parts_add_up(highest, ep, width, held,
                                                  k, tokens):
    """On the virtual CPU devices: each member of ``ep`` holds its share
    of the held experts; the layer's result is the single-chip layer's.
    Four members of a router wholly held (every member's bound is all
    24 x 3 choices: one path); two members holding two experts each of
    a router 16 wide, each on its own ladder (512 of 1,024 rows, or all
    of them), the conditional inside ``shard_map``."""
    from jax.sharding import Mesh

    given = _layer_inputs(width=width, held=held, seed=6, tokens=tokens)
    x, params = given["x"], given["params"]
    cfg = program_config(router_width=width, first_held=0, n_held=held,
                         top_k=k, moe_intermediate_size=16)
    per = mla_moe.rows_ladder(tokens * k, held // ep, width)
    assert per == ((tokens * k,) if held == width else (512, 1024))
    want, want_counters = ExpertLayer(cfg).apply({"params": params}, x)
    mesh = Mesh(np.asarray(jax.devices()[:ep]).reshape(1, ep), ("dp", "ep"))
    layer = lambda p, x: ExpertLayer(cfg, mesh).apply({"params": p}, x)
    got, counters = jax.jit(layer)(params, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("moe_rows", "moe_expert_rows_max", "moe_overflow_layers"):
        assert int(counters[name]) == int(want_counters[name]), name
    # Every member's rung, summed: all on their first.
    assert int(counters["moe_bound_rows"]) == ep * per[0]
    assert _primitives(jax.make_jaxpr(layer)(params, x), "cond") == (
        len(per) > 1)
    rules = dict(mla_moe.mla_moe_sharding_rules())
    assert rules[r"moe/w_(gate|up|down)"][0] == "ep"


class _Lines(logging.Handler):
    """Collects a program logger's messages (its loggers do not
    propagate, so caplog does not see them)."""

    def __init__(self, logger):
        super().__init__()
        self.lines = []
        self._logger = logger

    def emit(self, record):
        self.lines.append(record.getMessage())

    def __enter__(self):
        self._logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self)


@pytest.mark.parametrize("fused", [True, False])
def test_worker_logs_the_routing_line_and_counts(tmp_path, fused):
    """The unchanged worker runs the zoo module; every trained task gets
    a third line with each step's counters, and the page's counters move
    with them."""
    from elasticdl_tpu.testing.cluster import MiniCluster
    from elasticdl_tpu.testing.data import (
        create_lm_record_file,
        model_zoo_dir,
    )
    from elasticdl_tpu.worker import worker as worker_mod

    train = create_lm_record_file(
        str(tmp_path / "t.rec"), 32, seed=5, seq_len=16, vocab=256)
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mla_moe.mla_moe_lm.custom_model",
        training_data=train, minibatch_size=4,
        num_minibatches_per_task=4, fuse_task_steps=fused,
    )
    def page():
        return {
            f["name"]: f["series"][0]["value"]
            for f in cluster.workers[0]._metrics.snapshot()["families"]
            if f["name"].startswith("edl_tpu_worker_moe") and f["series"]
        }

    # The registry is the process's: other tests' workers count there.
    before = page().get("edl_tpu_worker_moe_rows_total", 0)
    bound_before = page().get("edl_tpu_worker_moe_bound_rows_total", 0)
    with _Lines(worker_mod.logger) as log:
        cluster.run()
    assert cluster.finished
    trained = [m for m in log.lines if " trained: " in m]
    routing = [m for m in log.lines if " routing: " in m]
    assert len(trained) == len(routing) == 2
    total, largest = 0, 0
    for line_t, line_r in zip(trained, routing):
        assert line_r.startswith(f"Task {line_t.split()[1]} routing: ")
        fields = dict(
            part.split("=", 1) for part in
            line_r.split("routing: ", 1)[1].replace(", ", ",").split())
        rows = [int(x) for x in fields["moe_rows"].strip("[]").split(",")]
        maxes = [int(x) for x in
                 fields["moe_expert_rows_max"].strip("[]").split(",")]
        assert len(rows) == len(maxes) == 4
        # The zoo's CONFIG holds all 8 experts: 4 rows x 16 tokens x
        # top-2, over two expert layers and the MTP block.
        assert rows == [4 * 16 * 2 * 3] * 4
        # Every expert held: the ladder is every token-choice alone, and
        # no layer is counted as past it.
        assert fields["moe_bound_rows"] == fields["moe_rows"]
        assert fields["moe_overflow_layers"] == "[0,0,0,0]"
        total += sum(rows)
        largest = max(maxes)
    assert page()["edl_tpu_worker_moe_rows_total"] - before == total
    assert page()["edl_tpu_worker_moe_bound_rows_total"] - bound_before == (
        total)
    assert page()["edl_tpu_worker_moe_expert_rows_max"] == largest
