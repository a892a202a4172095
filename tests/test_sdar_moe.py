"""The block-diffusion LM (``models/sdar_moe.py``) against its plain
reference (``benchmark/reference/sdar_moe.py``, whose mask is the four
rules written densely and whose noising is its own) at a tiny size,
seeded weights, float32: the noise, logits, loss, every gradient leaf;
what the mask means; the expert layer's shares under softmax scores with
no shared expert; fused task == stepwise; a save and a restore; the
worker's counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as reference
from elasticdl_tpu.core.model_spec import load_module
from elasticdl_tpu.core.step import _train_step_body, jit_step, jit_task
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.models import mla_moe, sdar_moe
from elasticdl_tpu.models.mla_moe import ExpertLayer
from elasticdl_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeLM
from tests.test_nemotron_h import _Lines
from tests.test_mla_moe import RUNGS_LANDED_ON, layer_on_a_rung

ZOO = load_module("model_zoo/sdar_moe/sdar_moe_lm.py")

# The reference's names for the sizes (the published config.json's, and
# the configuration's own for what the published file does not give).
CFG = {
    "name": "tiny", "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "moe_intermediate_size": 16, "num_experts": 4, "router_width": 8,
    "first_held": 2, "num_experts_per_tok": 3, "vocab_size": 64,
    "initializer_range": 0.2, "block_length": 4, "noise_eps": 1e-3,
    "noise_seed": 11,
}
ROWS, SEQ = 2, 24


def program_config(cfg=CFG, **changes) -> SdarMoeConfig:
    base = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        block_length=cfg["block_length"], noise_eps=cfg["noise_eps"],
        noise_seed=cfg["noise_seed"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        compute_dtype=jnp.float32,
    )
    base.update(changes)
    return SdarMoeConfig(**base)


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


# ------------------------------------------------------------------ the noise

def test_noise_is_one_function_of_the_row_jitted_or_not():
    cfg = program_config()
    rows = jnp.asarray(np.random.default_rng(0).integers(0, 64, (6, 4096)))
    masked, p = sdar_moe.noise(rows, cfg)
    again, p_again = jax.jit(lambda r: sdar_moe.noise(r, cfg))(rows)
    # The masking is a comparison of integers: the same bits. p, the
    # weight's float, may differ in its last place (a division under
    # jit is a multiplication by the reciprocal).
    np.testing.assert_array_equal(masked, again)
    np.testing.assert_allclose(p, p_again, rtol=3e-7)
    # A row's noise is its own whatever stands beside it.
    alone, _ = sdar_moe.noise(rows[3:4], cfg)
    np.testing.assert_array_equal(alone[0], masked[3])
    # Rows differ; one token changed is another draw; so is the seed.
    assert float(jnp.mean(masked[0] != masked[1])) > 0.2
    changed = rows.at[0, 17].add(1)
    assert float(jnp.mean(sdar_moe.noise(changed, cfg)[0][0] != masked[0])) > 0.2
    other = sdar_moe.noise(rows, program_config(noise_seed=12))[0]
    assert float(jnp.mean(other != masked)) > 0.2
    # t ~ U(0, 1) a block: about half the tokens, p constant on a block
    # and inside [eps, 1].
    assert 0.45 < float(jnp.mean(masked)) < 0.55
    blocks = np.asarray(p).reshape(6, -1, 4)
    assert (blocks == blocks[..., :1]).all()
    assert 1e-3 <= blocks.min() and blocks.max() <= 1.0
    assert abs(float(jnp.mean(masked / p)) - 1.0) < 0.1


def test_the_references_own_noising_gives_the_same_bits(seeded):
    _, tokens, _ = seeded
    masked, p = sdar_moe.noise(tokens, program_config())
    for r in range(ROWS):
        want_m, want_p = reference.noising(tokens[r], CFG)
        np.testing.assert_array_equal(masked[r], want_m)
        np.testing.assert_allclose(p[r], want_p, rtol=3e-7)
    assert masked.any() and not masked.all()


# ------------------------------------------------- against the reference

def test_reference_tree_is_the_programs(seeded):
    weights, tokens, _ = seeded
    model = SdarMoeLM(program_config())
    want = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    got = {"params": reference.to_program_tree(weights, CFG)}
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [x.shape for x in jax.tree.leaves(want)] == [
        x.shape for x in jax.tree.leaves(got)]
    # One row past the vocabulary held: MASK's.
    assert got["params"]["token_embed"]["embedding"].shape == (65, 32)
    assert got["params"]["lm_head"]["kernel"].shape == (32, 64)
    back = reference.from_program_tree(got["params"], CFG)
    assert set(back) == set(weights)
    for name, value in weights.items():
        np.testing.assert_array_equal(back[name], value)


def test_logits_and_loss_match_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SdarMoeLM(program_config())
    out = model.apply({"params": params}, tokens, training=True)
    row_logits = jax.jit(lambda w, row: reference.row_logits(w, row, CFG))
    want = [row_logits(weights, tokens[r]) for r in range(ROWS)]
    assert out["logits"].shape == (ROWS, SEQ, CFG["vocab_size"])
    np.testing.assert_allclose(
        out["logits"], np.stack([w[0] for w in want]), atol=3e-5)
    assert int(out["metrics"]["moe_rows"]) == sum(int(w[1]) for w in want)
    np.testing.assert_array_equal(out["targets"], tokens)
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens, training=False),
        out["logits"], atol=1e-6)
    terms = reference.loss_terms(weights, tokens, CFG)
    assert int(out["metrics"]["diffusion_masked_tokens"]) == int(
        terms["masked"]) == int(jnp.sum(out["weights"] > 0))
    loss = ZOO.loss(labels, out, jnp.ones((ROWS,)))
    np.testing.assert_allclose(loss, terms["loss"], rtol=2e-6)
    # The labels are not read; a padded row is left out of the sum and
    # of the count.
    assert float(ZOO.loss(labels * 0, out, jnp.ones((ROWS,)))) == float(loss)
    one = reference.loss_terms(weights, tokens[:1], CFG)["loss"]
    np.testing.assert_allclose(
        ZOO.loss(labels, out, jnp.asarray([1.0, 0.0])), one, rtol=2e-6)


def test_every_gradient_leaf_matches_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SdarMoeLM(program_config())

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
    # MASK is never a target: the head has no output for it, and its
    # embedding row learns (masked positions read it).
    assert float(jnp.abs(got["token_embed"]["embedding"][-1]).max()) > 0


def test_lower_precisions_of_the_reference_differ_in_order(seeded):
    """bf16 is nearer the reference than the control one lower."""
    weights, tokens, labels = seeded
    grads = {p: jax.jit(lambda w, p=p: reference.loss_and_grads(
        w, tokens, labels, CFG, p))(weights)[1]
        for p in ("f32", "bf16", "fp8")}

    def gap(p):
        return max(
            float(jnp.linalg.norm(grads[p][n] - grads["f32"][n])
                  / (jnp.linalg.norm(grads["f32"][n]) + 1e-30))
            for n in grads["f32"])

    assert 0 < gap("bf16") < gap("fp8")


def test_softmax_routing_is_the_references_and_can_be_held(seeded, highest):
    """The layer's own choices (softmax over the whole width, top k,
    no bias) are the reference's; ``routing=`` holds the layers to
    choices given."""
    weights, tokens, _ = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SdarMoeLM(program_config())
    own = reference.choices(weights, tokens, CFG)
    assert len(own) == 2 and own[0].shape == (ROWS, 2 * SEQ, 3)
    free, state = model.apply({"params": params}, tokens, training=True,
                              mutable=["intermediates"])
    for i, picks in enumerate(own):
        got = state["intermediates"][f"block_{i}"]["moe"]["chosen"][0]
        np.testing.assert_array_equal(
            np.sort(got, axis=-1), np.sort(picks, axis=-1))
    assert int(free["metrics"]["moe_rows"]) == int(
        reference.routed_rows(weights, tokens, CFG))
    held = model.apply({"params": params}, tokens, training=True,
                       routing=own)
    np.testing.assert_allclose(free["logits"], held["logits"], atol=1e-6)
    turned = [jnp.flip(c, axis=1) for c in own]
    other = model.apply({"params": params}, tokens, training=True,
                        routing=turned)
    assert float(jnp.max(jnp.abs(other["logits"] - free["logits"]))) > 1e-3


# -------------------------------------------------------- the mask's meaning

def _fixed_noise(pattern):
    """A noising that does not read the row: ``pattern`` (L,) bool."""
    def noise(tokens, cfg):
        masked = jnp.broadcast_to(pattern, tokens.shape)
        return masked, jnp.full(tokens.shape, 0.5, jnp.float32)
    return noise


def test_a_noised_block_sees_itself_and_the_clean_blocks_before(
        monkeypatch, seeded):
    """With the noise held fixed (the program's is a function of the
    whole row): a change to a LATER clean block, or to which tokens of
    ANOTHER block are masked, leaves a noised block's logits as they
    were; a change to an EARLIER clean block moves them."""
    weights, tokens, _ = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SdarMoeLM(program_config())
    block = CFG["block_length"]
    pattern = jnp.arange(SEQ) % 2 == 0
    monkeypatch.setattr(sdar_moe, "noise", _fixed_noise(pattern))

    def logits(tokens):
        return model.apply({"params": params}, tokens, training=False)

    base = logits(tokens)
    at = 3                                   # the block that changes
    span = slice(at * block, (at + 1) * block)
    later = tokens.at[:, span].set((tokens[:, span] + 1) % 64)
    moved = logits(later)
    np.testing.assert_array_equal(moved[:, :at * block], base[:, :at * block])
    # Blocks after it see its clean tokens; the block itself sees its
    # own unmasked ones.
    assert float(jnp.abs(moved[:, span] - base[:, span]).max()) > 1e-4
    assert float(jnp.abs(
        moved[:, (at + 1) * block:] - base[:, (at + 1) * block:]).max()) > 1e-4
    # Another masking of block ``at`` alone: only its own logits move.
    monkeypatch.setattr(sdar_moe, "noise", _fixed_noise(
        pattern.at[span].set(~pattern[span])))
    renoised = logits(tokens)
    np.testing.assert_array_equal(
        renoised[:, :at * block], base[:, :at * block])
    np.testing.assert_array_equal(
        renoised[:, (at + 1) * block:], base[:, (at + 1) * block:])
    assert float(jnp.abs(renoised[:, span] - base[:, span]).max()) > 1e-4


def test_rotary_by_halves_and_positions_of_the_doubled_row():
    """(x1 cos - x2 sin, x2 cos + x1 sin) over the whole head; a noised
    token sits where its clean token sits."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 6, 2, 8)),
                    jnp.float32)
    positions = jnp.asarray([0, 1, 2, 0, 1, 2])
    got = sdar_moe.rope_halves(x, positions, 1e6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])        # angle 0
    np.testing.assert_array_equal(got[:, 3], x[:, 3])
    j = np.arange(4)
    angle = 2 * 1e6 ** (-2 * j / 8)
    want = np.concatenate([
        x[0, 5, :, :4] * np.cos(angle) - x[0, 5, :, 4:] * np.sin(angle),
        x[0, 5, :, 4:] * np.cos(angle) + x[0, 5, :, :4] * np.sin(angle)], -1)
    np.testing.assert_allclose(got[0, 5], want, atol=1e-6)
    np.testing.assert_allclose(
        got[0, 5], reference.rotary(x[0], positions, 1e6)[5], atol=1e-6)


# ----------------------------------------------------------- the expert layer

def _layer_inputs(width=16, d=32, f=16, tokens=24, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)
    return {
        "x": mk(2, tokens // 2, d),
        "params": {"router": mk(d, width), "w_gate": mk(width, d, f),
                   "w_up": mk(width, d, f), "w_down": mk(width, f, d)},
    }


def test_the_eight_shares_add_up_to_the_uncut_layer(highest):
    """Eight shares of two experts each, as eight chips of the
    deployment hold them: their routed parts are the uncut reference's
    layer (nothing is shared, so nothing is counted once), and no choice
    of a held expert is lost on the way."""
    width, k = 16, 3
    given = _layer_inputs(width=width)
    x, params = given["x"], given["params"]
    w = {"router": params["router"], "e_gate": params["w_gate"],
         "e_up": params["w_up"], "e_down": params["w_down"]}
    whole, chosen = reference.expert_layer(
        x.reshape(-1, x.shape[-1]), w, {"first": 0, "held": width, "k": k},
        {}, "f32")
    total, rows = jnp.zeros_like(whole), 0
    for first in range(0, width, 2):
        cfg = program_config(router_width=width, first_held=first, n_held=2,
                             top_k=k)
        share = dict(params, **{name: params[name][first:first + 2]
                                for name in ("w_gate", "w_up", "w_down")})
        out, counters = ExpertLayer(cfg).apply({"params": share}, x)
        total = total + out.reshape(whole.shape)
        rows += int(counters["moe_rows"])
    assert rows == chosen.size == x.shape[0] * x.shape[1] * k
    np.testing.assert_allclose(total, whole, atol=2e-5)


@pytest.mark.parametrize("lands", RUNGS_LANDED_ON)
def test_the_layer_runs_on_the_rung_its_rows_need(highest, monkeypatch,
                                                  lands):
    """The family's layer (softmax scores, no selection bias, no shared
    expert) under a ladder of three sizes, on each of them."""
    given = _layer_inputs(width=16, tokens=1024)
    cfg = program_config(router_width=16, first_held=2, n_held=2, top_k=2)
    share = dict(given["params"], **{name: given["params"][name][2:4]
                                     for name in ("w_gate", "w_up", "w_down")})
    layer_on_a_rung(monkeypatch, ExpertLayer(cfg), share, given["x"], lands)


# 3 members of 2 experts each, this one the second; a token chooses 3.
ALIKE = dict(CFG, num_experts=2, router_width=6, first_held=2,
             num_experts_per_tok=3, router_init="members_alike")


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_members_alike_sends_this_member_one_choice_a_position(seed,
                                                               highest):
    """``router_init: members_alike`` (the cell's): every member of the
    expert-parallel group starts with this member's router columns, so a
    token's choices are one expert on each member and the held experts
    are sent exactly one row a position and layer, whatever the seed;
    every other leaf is the draw it was."""
    key = jax.random.PRNGKey(seed)
    weights = reference.weights(ALIKE, key)
    plain = reference.weights(dict(ALIKE, router_init="independent"), key)
    assert reference.router_members(ALIKE) == 3
    for name, leaf in weights.items():
        if name.endswith("/router"):
            np.testing.assert_array_equal(leaf, jnp.tile(leaf[:, :2], (1, 3)))
            assert leaf.shape == plain[name].shape
            assert 0.1 < float(jnp.std(leaf)) < 0.3   # normal(0, 0.2)
        else:
            np.testing.assert_array_equal(leaf, plain[name])
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, ALIKE["vocab_size"], (ROWS, SEQ)))
    positions = ROWS * 2 * SEQ
    for picks in reference.choices(weights, tokens, ALIKE):
        picks = np.sort(np.asarray(picks), axis=-1)
        # The same local expert on members 0, 1 and 2.
        np.testing.assert_array_equal(picks // 2, np.broadcast_to(
            [0, 1, 2], picks.shape))
        assert (picks % 2 == picks[..., :1] % 2).all()
    assert int(reference.routed_rows(weights, tokens, ALIKE)) == 2 * positions
    out = SdarMoeLM(program_config(ALIKE)).apply(
        {"params": reference.to_program_tree(weights, ALIKE)}, tokens,
        training=True)
    assert int(out["metrics"]["moe_rows"]) == 2 * positions
    # Drawn independently the count is the seed's draw.
    assert int(reference.routed_rows(plain, tokens, ALIKE)) != 2 * positions


@pytest.mark.parametrize("changes, said", [
    ({"router_init": "balanced"}, "one of"),
    ({"num_experts_per_tok": 2}, "as many experts as there are members"),
    ({"first_held": 1}, "one whole member"),
    ({"router_width": 8}, "one whole member"),
])
def test_members_alike_refuses_a_group_it_cannot_lay_out(changes, said):
    with pytest.raises(ValueError, match=said):
        reference.weights(dict(ALIKE, **changes), jax.random.PRNGKey(0))


def test_the_layer_has_no_bias_and_no_shared_expert_where_told():
    given = _layer_inputs(width=8)
    cfg = program_config(router_width=8, first_held=0, n_held=8)
    made = ExpertLayer(cfg).init(jax.random.PRNGKey(0), given["x"])
    assert set(made["params"]) == {"router", "w_gate", "w_up", "w_down"}
    # The two accepted families' layers are what they were.
    from elasticdl_tpu.models.mla_moe import MlaMoeConfig
    made = ExpertLayer(MlaMoeConfig(hidden_size=32)).init(
        jax.random.PRNGKey(0), given["x"])
    assert set(made["params"]) == {
        "router", "router_bias", "w_gate", "w_up", "w_down", "shared"}


def test_softmax_weights_are_the_chosen_probabilities_renormalised():
    given = _layer_inputs(width=8, seed=4)
    x, params = given["x"], given["params"]
    rows = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(rows @ params["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, 2)
    want = sum(
        (top[:, j] / top.sum(-1))[:, None] * jnp.stack([
            reference.gated_mlp(
                rows[t:t + 1], params["w_gate"][e], params["w_up"][e],
                params["w_down"][e], "f32")[0]
            for t, e in enumerate(np.asarray(chosen[:, j]))])
        for j in range(2))
    cfg = program_config(router_width=8, first_held=0, n_held=8, top_k=2)
    with jax.default_matmul_precision("highest"):
        out, _ = ExpertLayer(cfg).apply({"params": params}, x)
    np.testing.assert_allclose(out.reshape(want.shape), want, atol=2e-5)


# ------------------------------------------------------------ the normal path

def test_remat_gives_the_plain_models_bits(seeded):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)

    def grads(remat):
        model = SdarMoeLM(program_config(remat=remat))
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
    """``core/step.py``: the model's counters, the masked tokens among
    them, leave the step beside the loss, and a fused task of three
    steps is three steps."""
    _, tokens, labels = seeded
    cfg = program_config(first_held=0, n_held=8)
    model = SdarMoeLM(cfg)
    batches = [_batch(jnp.roll(tokens, i, axis=1), jnp.roll(labels, i, axis=1))
               for i in range(3)]
    state = init_train_state(model, ZOO.optimizer(), batches[0])
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    body = _train_step_body(ZOO.loss)
    fused_state, fused = jit_task(body, donate=False)(state, stacked)
    assert set(fused) == {"loss", "moe_rows", "moe_expert_rows_max",
                          "moe_bound_rows", "moe_overflow_layers",
                          "diffusion_masked_tokens"}
    step = jit_step(body, donate=False)
    losses = []
    for i, batch in enumerate(batches):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        # All eight experts held: every choice of both layers, over the
        # doubled row.
        assert int(metrics["moe_rows"]) == ROWS * 2 * SEQ * 3 * 2
        assert int(metrics["diffusion_masked_tokens"]) == int(
            sdar_moe.noise(jnp.asarray(batch["features"]), cfg)[0].sum())
        assert int(fused["diffusion_masked_tokens"][i]) == int(
            metrics["diffusion_masked_tokens"])
    np.testing.assert_allclose(fused["loss"], losses, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(fused_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)


def test_state_is_saved_and_restored(tmp_path, seeded):
    from elasticdl_tpu.checkpoint.hooks import CheckpointHook, restore_from_dir

    _, tokens, labels = seeded
    model = SdarMoeLM(program_config())
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


def test_lines_say_the_noise_the_mask_and_the_experts_scores():
    from elasticdl_tpu.ops import flash_attention as flash

    for cached in (sdar_moe.log_traced_noising, flash.log_traced,
                   mla_moe.log_traced_experts):
        cached.cache_clear()
    model = SdarMoeLM(program_config())
    tokens = jnp.zeros((ROWS, SEQ), jnp.int32)
    with _Lines(sdar_moe.logger, flash.logger, mla_moe.logger) as log:
        for _ in range(2):
            jax.eval_shape(lambda: model.init(
                {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    assert [line for line in log.lines if line.startswith("diffusion:")] == [
        "diffusion: traced noising of x(2, 24): blocks of 4, linear "
        "schedule, eps 0.001, mask row 64, loss in place over masked tokens"]
    assert any(
        "4 query heads over 2 key/value heads, head size 8; block-diffusion "
        "mask, blocks of 4 over halves of 24" in line for line in log.lines)
    assert any(line.endswith(
        "grouped product ragged_dot, softmax scores, no selection bias, no "
        "shared expert") for line in log.lines)


def test_the_tpu_branch_traces_the_kernels_under_the_mask(
        kernels_traced, monkeypatch):
    """At a size the kernels plan, the model's TPU branch calls them
    under the mask (two custom calls a layer: forward, backward; a
    recomputed layer keeps o and the logsumexp) and the line says what
    the plan skips."""
    import functools

    from elasticdl_tpu.ops import flash_attention as flash
    from tests.test_flash_attention import count_calls

    monkeypatch.setattr(flash, "SUB_TILE", 8)
    monkeypatch.setattr(flash, "DEFAULT_BLOCK_Q", 128)
    monkeypatch.setattr(flash, "DEFAULT_BLOCK_K", 128)
    monkeypatch.setattr(sdar_moe, "flash_attention", functools.partial(
        flash.flash_attention, interpret=True))
    cfg = program_config(remat=True, num_layers=1)
    model = SdarMoeLM(cfg)
    tokens = jnp.zeros((1, 512), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))["params"]
    flash.log_traced.cache_clear()

    def loss(p):
        return ZOO.loss(tokens, model.apply(
            {"params": p}, tokens, training=True), jnp.ones((1,)))

    with _Lines(flash.logger) as log:
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert count_calls(jaxpr.jaxpr) == 2
    line, = [m for m in log.lines if "pallas flash kernel" in m]
    assert ("block-diffusion mask, blocks of 4 over halves of 512; grid 8x8 "
            "of blocks 128x128: 12 tiles whole and unmasked, 12 boundary "
            "tiles walked") in line
    assert "40 skipped" in line and "under remat the block keeps" in line


@pytest.mark.parametrize("fused", [True, False])
def test_worker_runs_the_zoo_module_and_counts(tmp_path, fused):
    """The unchanged master and worker run the zoo module; every trained
    task's third line carries the masked tokens beside the routed rows
    and the page's counter moves."""
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
        model_def="sdar_moe.sdar_moe_lm.custom_model",
        training_data=train, minibatch_size=4,
        num_minibatches_per_task=2, fuse_task_steps=fused,
    )
    with _Lines(worker_mod.logger) as log:
        before = _page_counter()
        cluster.run()
    assert cluster.finished
    trained = [m for m in log.lines if " trained: " in m]
    routing = [m for m in log.lines if " routing: " in m]
    assert len(trained) == len(routing) == 2
    # The zoo's CONFIG holds all 8 experts of its two layers: 4 rows x
    # 32 positions of the doubled row x top-2 x 2 layers, each step.
    masked = 0
    for line in routing:
        assert "moe_rows=[512, 512]" in line, line
        counts = line.split("diffusion_masked_tokens=[")[1].split("]")[0]
        masked += sum(int(x) for x in counts.split(","))
    assert 0 < masked < 4 * 16 * 4
    assert _page_counter() - before == masked


def _page_counter():
    """``edl_tpu_worker_diffusion_masked_tokens_total`` as the master's
    page would show it (the process's registry)."""
    from elasticdl_tpu.observability.registry import default_registry

    series = default_registry().counter(
        "worker_diffusion_masked_tokens_total").snapshot()["series"]
    return sum(int(row["value"]) for row in series)
