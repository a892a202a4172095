"""The window-and-global sparse-expert LM (``models/smallthinker.py``)
against its plain reference (``benchmark/reference/smallthinker.py``,
whose mask is the rule written densely) at a tiny size, seeded weights,
float32: logits, loss, every gradient leaf; the layer pattern; the
router that reads the layer's input; ReLU-gated experts and their
shares; the expert layer's default trace; fused task == stepwise; the
worker's counter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker as reference
from elasticdl_tpu.core.model_spec import load_module
from elasticdl_tpu.core.step import _train_step_body, jit_step, jit_task
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.models import mla_moe, smallthinker
from elasticdl_tpu.models.mla_moe import ExpertLayer
from elasticdl_tpu.models.smallthinker import (
    SmallThinkerConfig,
    SmallThinkerLM,
)
from tests.test_nemotron_h import _Lines
from tests.test_mla_moe import RUNGS_LANDED_ON, layer_on_a_rung

ZOO = load_module("model_zoo/smallthinker/smallthinker_lm.py")

# The reference's names for the sizes: the published config.json's. A
# group of 3 query heads a key/value head (the cell's is 7: no power of
# two), a whole period of the pattern and one layer more, a window a
# third of the row, routers drawn independently, 4 of 8 experts held.
CFG = {
    "name": "tiny", "hidden_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 1500000, "rms_norm_eps": 1e-6,
    "sliding_window_size": 8,
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 4,
    "router_width": 8, "first_held": 2,
    "moe_num_active_primary_experts": 3, "vocab_size": 64,
    "initializer_range": 0.2,
}
ROWS, SEQ = 2, 24


def program_config(cfg=CFG, **changes) -> SmallThinkerConfig:
    layers = cfg["num_hidden_layers"]
    base = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=layers, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window_size"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"][:layers]),
        rope_layout=tuple(cfg["rope_layout"][:layers]),
        moe_intermediate_size=cfg["moe_ffn_hidden_size"],
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        compute_dtype=jnp.float32,
    )
    base.update(changes)
    return SmallThinkerConfig(**base)


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


# ------------------------------------------------- against the reference

def test_reference_tree_is_the_programs(seeded):
    weights, tokens, _ = seeded
    model = SmallThinkerLM(program_config())
    want = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    got = {"params": reference.to_program_tree(weights, CFG)}
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [x.shape for x in jax.tree.leaves(want)] == [
        x.shape for x in jax.tree.leaves(got)]
    # The two kinds of attention bear different names in the tree.
    assert [next(k for k in got["params"][f"block_{i}"] if "attn" in k
                 and "norm" not in k) for i in range(5)] == [
        "global_attn", "window_attn", "window_attn", "window_attn",
        "global_attn"]
    back = reference.from_program_tree(got["params"], CFG)
    assert set(back) == set(weights)
    for name, value in weights.items():
        np.testing.assert_array_equal(back[name], value)


def test_logits_loss_and_counters_match_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SmallThinkerLM(program_config())
    out = model.apply({"params": params}, tokens, training=True)
    row_logits = jax.jit(lambda w, row: reference.row_logits(w, row, CFG))
    want = [row_logits(weights, tokens[r]) for r in range(ROWS)]
    assert out["logits"].shape == (ROWS, SEQ, CFG["vocab_size"])
    np.testing.assert_allclose(
        out["logits"], np.stack([w[0] for w in want]), atol=3e-5)
    assert int(out["metrics"]["moe_rows"]) == sum(int(w[1]) for w in want)
    # 3 window layers of 8 (8 x 9 / 2 + 16 x 8 = 164 pairs a row), 2
    # global ones (24 x 25 / 2 = 300), by hand and by the reference's
    # rule.
    assert int(out["metrics"]["attn_visible_pairs"]) == ROWS * (
        3 * 164 + 2 * 300) == ROWS * reference.visible_pairs(CFG, SEQ)
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens, training=False),
        out["logits"], atol=1e-6)
    terms = reference.loss_terms(weights, tokens, labels, CFG)
    loss = ZOO.loss(labels, out, jnp.ones((ROWS,)))
    np.testing.assert_allclose(loss, terms["loss"], rtol=2e-6)


def test_every_gradient_leaf_matches_the_reference(seeded, highest):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SmallThinkerLM(program_config())

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
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


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


def test_routing_is_the_references_and_can_be_held(seeded, highest):
    weights, tokens, _ = seeded
    params = reference.to_program_tree(weights, CFG)
    model = SmallThinkerLM(program_config())
    own = reference.choices(weights, tokens, CFG)
    assert len(own) == 5 and own[0].shape == (ROWS, SEQ, 3)
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


# ------------------------------------------------------ the layer's meaning

def test_the_router_reads_the_layers_input_not_the_stream_after_attention(
        seeded, highest, monkeypatch):
    """A layer's choices are made from what the layer received. With
    every attention's output projection zeroed but one layer's, only
    the layers AFTER that one may choose otherwise: the layer itself
    reads its input, which the attention has not touched. A router on
    the post-attention stream would move that layer's own choices."""
    weights, tokens, _ = seeded
    quiet = dict(weights)
    for name in weights:
        if name.endswith("/wo"):
            quiet[name] = jnp.zeros_like(weights[name])
    loud = dict(quiet, **{"block_1/wo": 5.0 * weights["block_1/wo"]})
    model = SmallThinkerLM(program_config())

    def chosen(w):
        _, state = model.apply(
            {"params": reference.to_program_tree(w, CFG)}, tokens,
            training=True, mutable=["intermediates"])
        return [np.sort(np.asarray(
            state["intermediates"][f"block_{i}"]["moe"]["chosen"][0]), -1)
            for i in range(5)]

    before, after = chosen(quiet), chosen(loud)
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])    # its own input
    assert (before[2] != after[2]).any()
    # The planted fault: the router handed the post-attention stream.
    real = ExpertLayer.__call__

    def late(self, x, routing=None, router_input=None):
        return real(self, x, routing, router_input=None)

    monkeypatch.setattr(ExpertLayer, "__call__", late)
    faulty = chosen(loud)
    assert (faulty[1] != after[1]).any()
    monkeypatch.undo()
    # And the reference agrees with the program, not with the fault.
    own = reference.choices(loud, tokens, CFG)
    np.testing.assert_array_equal(np.sort(own[1], -1), after[1])


def test_window_layers_see_the_band_and_global_layers_everything_before(
        seeded, highest):
    """Moving a token 10 places back changes a window layer's output at
    the query (window 8) only through the layers in between; in a model
    of ONE window layer it changes nothing there, in one of one global
    layer it does. No positions in a global layer: its q and k are not
    rotated."""
    tokens = seeded[1][:1]
    changed = tokens.at[0, 5].set((tokens[0, 5] + 1) % CFG["vocab_size"])
    for windowed, moved in ((1, False), (0, True)):
        cfg = dict(CFG, num_hidden_layers=1,
                   sliding_window_layout=[windowed], rope_layout=[windowed])
        weights = reference.weights(cfg, jax.random.PRNGKey(1))
        model = SmallThinkerLM(program_config(cfg))
        params = {"params": reference.to_program_tree(weights, cfg)}
        a = model.apply(params, tokens)[0, 15]
        b = model.apply(params, changed)[0, 15]
        assert bool(jnp.max(jnp.abs(a - b)) > 1e-6) == moved
        # Position 12 sees key 5 either way (12 - 5 < 8).
        assert float(jnp.max(jnp.abs(
            model.apply(params, tokens)[0, 12]
            - model.apply(params, changed)[0, 12]))) > 1e-6
    rotated = []
    real = smallthinker.rope_halves
    try:
        smallthinker.rope_halves = lambda x, *a: rotated.append(x) or real(
            x, *a)
        SmallThinkerLM(program_config()).apply(
            {"params": reference.to_program_tree(seeded[0], CFG)}, tokens)
    finally:
        smallthinker.rope_halves = real
    # q and k of the three window layers, none of the two global ones.
    assert len(rotated) == 6


def test_a_window_no_multiple_of_the_block_runs_dense_under_the_rule(
        monkeypatch):
    """Where the kernels have no plan for the band (``supports`` says
    so) the TPU branch runs ``dense_attention`` under the same
    ``visible``."""
    from elasticdl_tpu.ops import flash_attention as flash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = program_config(num_layers=1, sliding_window_layout=(1,),
                         rope_layout=(1,), sliding_window=200)
    model = SmallThinkerLM(cfg)
    tokens = jnp.zeros((1, 1024), jnp.int32)
    flash.log_traced.cache_clear()
    with _Lines(flash.logger) as log:
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, tokens, training=False))
    line, = log.lines
    assert "dense reference" in line and "no plan" in line
    assert "sliding window 200" in line


# ----------------------------------------------------------- the expert layer

def _layer_inputs(tokens=48, d=16, f=8, width=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * 0.3, jnp.float32)
    return {
        "x": mk(2, tokens // 2, d), "read": mk(2, tokens // 2, d),
        "params": {"router": mk(d, width), "w_gate": mk(width, d, f),
                   "w_up": mk(width, d, f), "w_down": mk(width, f, d)},
    }


def test_the_eight_shares_add_up_to_the_uncut_layer(highest):
    """Eight shares of two ReLU-gated experts each, as eight chips of
    the deployment hold them, the router reading another tensor than
    the experts: their routed parts are the uncut reference's layer
    (nothing is shared, so nothing is counted once), and no choice of a
    held expert is lost on the way."""
    width, k = 16, 3
    given = _layer_inputs(width=width)
    x, read, params = given["x"], given["read"], given["params"]
    w = {"router": params["router"], "e_gate": params["w_gate"],
         "e_up": params["w_up"], "e_down": params["w_down"]}
    flat = lambda t: t.reshape(-1, t.shape[-1])  # noqa: E731
    whole, chosen = reference.expert_layer(
        flat(read), flat(x), w, {"first": 0, "held": width, "k": k}, "f32")
    total, rows = jnp.zeros_like(whole), 0
    for first in range(0, width, 2):
        cfg = program_config(router_width=width, first_held=first, n_held=2,
                             top_k=k, moe_intermediate_size=8, hidden_size=16)
        share = dict(params, **{name: params[name][first:first + 2]
                                for name in ("w_gate", "w_up", "w_down")})
        out, counters = ExpertLayer(cfg).apply(
            {"params": share}, x, router_input=read)
        total = total + out.reshape(whole.shape)
        rows += int(counters["moe_rows"])
    assert rows == chosen.size == x.shape[0] * x.shape[1] * k
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # The gate's activation is ReLU: a SiLU-gated layer gives another
    # result on the same weights.
    silu, _ = ExpertLayer(program_config(
        router_width=width, first_held=0, n_held=width, top_k=k,
        moe_intermediate_size=8, hidden_size=16, expert_form="silu_gated",
    )).apply({"params": params}, x, router_input=read)
    assert float(jnp.max(jnp.abs(silu.reshape(whole.shape) - whole))) > 1e-3


@pytest.mark.parametrize("lands", RUNGS_LANDED_ON)
def test_the_layer_runs_on_the_rung_its_rows_need(highest, monkeypatch,
                                                  lands):
    """The family's layer (ReLU-gated experts, the router reading
    another tensor) under a ladder of three sizes, on each of them."""
    given = _layer_inputs(width=16, tokens=1024)
    cfg = program_config(router_width=16, first_held=2, n_held=2, top_k=2,
                         moe_intermediate_size=8, hidden_size=16)
    share = dict(given["params"], **{name: given["params"][name][2:4]
                                     for name in ("w_gate", "w_up", "w_down")})
    layer_on_a_rung(monkeypatch, ExpertLayer(cfg), share, given["x"], lands,
                    router_input=given["read"])


def test_a_form_and_its_gate_have_to_agree():
    given = _layer_inputs(width=4)
    p = given["params"]
    rows = given["x"].reshape(-1, 16)
    chosen = jnp.zeros((rows.shape[0], 1), jnp.int32)
    weights = jnp.ones((rows.shape[0], 1), jnp.float32)
    with pytest.raises(ValueError, match="need w_gate"):
        mla_moe.held_experts_part(rows, chosen, weights, None, p["w_up"],
                                  p["w_down"], 0, 4, "relu_gated")
    with pytest.raises(ValueError, match="have no gate"):
        mla_moe.held_experts_part(rows, chosen, weights, p["w_gate"],
                                  p["w_up"], p["w_down"], 0, 4, "relu2")


# 4 members of 2 experts each, this one the first; a token chooses 3.
ALIKE = dict(CFG, moe_num_primary_experts=2, router_width=8, first_held=0,
             moe_num_active_primary_experts=3, router_init="members_alike")


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_members_alike_sends_member_0_one_choice_a_position(seed, highest):
    """``router_init: members_alike`` (the cell's) with fewer choices
    than members: the drawn columns stand once for each of members 0, 1
    and 2 (as many copies as choices) and member 3's are zero, so a
    token's choices are its best local expert on members 0, 1 and 2,
    whichever way ties are broken, and member 0, held here, is sent
    exactly one row a position and layer, whatever the seed; program
    and reference choose alike."""
    key = jax.random.PRNGKey(seed)
    weights = reference.weights(ALIKE, key)
    plain = reference.weights(dict(ALIKE, router_init="independent"), key)
    assert reference.router_members(ALIKE) == 3
    for name, leaf in weights.items():
        if name.endswith("/router"):
            np.testing.assert_array_equal(
                leaf[:, :6], jnp.tile(leaf[:, :2], (1, 3)))
            np.testing.assert_array_equal(leaf[:, 6:], 0.0)
            assert leaf.shape == plain[name].shape
            assert 0.1 < float(jnp.std(leaf[:, :2])) < 0.3   # normal(0, 0.2)
        else:
            np.testing.assert_array_equal(leaf, plain[name])
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, ALIKE["vocab_size"], (ROWS, SEQ)))
    positions = ROWS * SEQ
    own = reference.choices(weights, tokens, ALIKE)
    lost = 0
    for picks in own:
        picks = np.sort(np.asarray(picks), axis=-1)
        # The same local expert on members 0, 1 and 2; but where both
        # drawn logits are negative (one token in 4 here, one in 256 at
        # the cell's 8 held) member 3's zero columns win.
        zero = (picks // 2 == 3).any(axis=-1)
        np.testing.assert_array_equal(
            picks[~zero] // 2, np.broadcast_to([0, 1, 2], picks[~zero].shape))
        assert (picks[~zero] % 2 == picks[~zero][..., :1] % 2).all()
        # (With 2 zero columns under 3 choices here, such a token still
        # takes one copy; at the cell 16 zero columns take all 6.)
        lost += int((~(picks // 2 == 0).any(axis=-1)).sum())
    assert lost < 0.5 * 5 * positions
    assert int(reference.routed_rows(weights, tokens, ALIKE)) == (
        5 * positions - lost)
    out, state = SmallThinkerLM(program_config(ALIKE)).apply(
        {"params": reference.to_program_tree(weights, ALIKE)}, tokens,
        training=True, mutable=["intermediates"])
    assert int(out["metrics"]["moe_rows"]) == 5 * positions - lost
    for i, picks in enumerate(own):
        np.testing.assert_array_equal(
            np.sort(state["intermediates"][f"block_{i}"]["moe"]["chosen"][0],
                    axis=-1), np.sort(picks, axis=-1))


@pytest.mark.parametrize("changes, said", [
    ({"first_held": 1}, "one whole member"),
    ({"moe_num_active_primary_experts": 5}, "no more"),
    ({"router_init": "alike"}, "one of"),
])
def test_members_alike_refuses_a_group_it_cannot_lay_out(changes, said):
    with pytest.raises(ValueError, match=said):
        reference.router_members(dict(ALIKE, **changes))


# ------------------------------------------------------------ the normal path

def test_remat_gives_the_plain_models_bits(seeded):
    weights, tokens, labels = seeded
    params = reference.to_program_tree(weights, CFG)

    def grads(remat):
        model = SmallThinkerLM(program_config(remat=remat))
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
    """``core/step.py``: the model's counters, the visible pairs among
    them, leave the step beside the loss, and a fused task of three
    steps is three steps."""
    _, tokens, labels = seeded
    model = SmallThinkerLM(program_config(first_held=0, n_held=8))
    batches = [_batch(jnp.roll(tokens, i, axis=1), jnp.roll(labels, i, axis=1))
               for i in range(3)]
    state = init_train_state(model, ZOO.optimizer(), batches[0])
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    body = _train_step_body(ZOO.loss)
    fused_state, fused = jit_task(body, donate=False)(state, stacked)
    assert set(fused) == {"loss", "moe_rows", "moe_expert_rows_max",
                          "moe_bound_rows", "moe_overflow_layers",
                          "attn_visible_pairs"}
    step = jit_step(body, donate=False)
    losses = []
    for i, batch in enumerate(batches):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert int(metrics["moe_rows"]) == ROWS * SEQ * 3 * 5
        assert int(metrics["attn_visible_pairs"]) == int(
            fused["attn_visible_pairs"][i]) == ROWS * (3 * 164 + 2 * 300)
    np.testing.assert_allclose(fused["loss"], losses, rtol=1e-6)
    # Adam's first steps turn a gradient's last bits into whole steps
    # where the gradient is next to nothing.
    for a, b in zip(jax.tree.leaves(fused_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)


def test_state_is_saved_and_restored(tmp_path, seeded):
    from elasticdl_tpu.checkpoint.hooks import CheckpointHook, restore_from_dir

    _, tokens, labels = seeded
    model = SmallThinkerLM(program_config())
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


def test_the_tpu_branch_traces_both_kinds_of_kernel_and_says_the_plan(
        kernels_traced, monkeypatch):
    """At a size the kernels plan, a window layer's TPU branch calls the
    plan kernels under the band and a global layer's the causal grid's
    (two custom calls a layer: forward, backward; a recomputed layer
    keeps o and the logsumexp), each under the scope ``attn`` inside
    its own module, and the two lines say what each plan skips."""
    from elasticdl_tpu.ops import flash_attention as flash
    from tests.test_flash_attention import _count_traces, count_calls

    monkeypatch.setattr(flash, "SUB_TILE", 8)
    monkeypatch.setattr(flash, "DEFAULT_BLOCK_Q", 128)
    monkeypatch.setattr(flash, "DEFAULT_BLOCK_K", 128)
    monkeypatch.setattr(smallthinker, "flash_attention", functools.partial(
        flash.flash_attention, interpret=True))
    counts = _count_traces(
        monkeypatch, "_fwd_plan_kernel", "_bwd_plan_kernel",
        "_fwd_grid_kernel", "_bwd_grid_kernel")
    cfg = program_config(remat=True, num_layers=2, sliding_window=256,
                         sliding_window_layout=(0, 1), rope_layout=(0, 1))
    model = SmallThinkerLM(cfg)
    tokens = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, training=False))["params"]
    flash.log_traced.cache_clear()

    def loss(p):
        return ZOO.loss(tokens, model.apply(
            {"params": p}, tokens, training=True), jnp.ones((1,)))

    with _Lines(flash.logger) as log:
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert count_calls(jaxpr.jaxpr) == 4
    assert all(counts.get(name) for name in (
        "_fwd_plan_kernel", "_bwd_plan_kernel", "_fwd_grid_kernel",
        "_bwd_grid_kernel")), counts
    window, = [m for m in log.lines if "sliding window" in m]
    causal, = [m for m in log.lines if "full causal" in m]
    assert (
        "6 query heads over 2 key/value heads, head size 8; sliding window "
        "256, rotary; grid 8x8 of blocks 128x128: 7 tiles whole and "
        "unmasked, 14 boundary tiles walked (8 diagonal, 6 at the window's "
        "edge, 136 of 256 sub-tiles 8x8), 43 skipped; one key/value head "
        "read in place by 3 query heads") in window
    assert "under remat the block keeps" in window
    assert ("full causal, no positions; grid 8x8 of blocks 128x128: 28 "
            "tiles whole and unmasked, 8 diagonal tiles walked") in causal
    # The kernel calls sit under the scope ``attn`` inside their own
    # module: the device operations are ``attn.N``, the table's module
    # column ``.../window_attn/attn`` or ``.../global_attn/attn``.
    stacks = {str(eqn.source_info.name_stack)
              for eqn in _pallas_calls(jaxpr.jaxpr)}
    assert any(s.endswith("window_attn/attn") for s in stacks), stacks
    assert any(s.endswith("global_attn/attn") for s in stacks), stacks


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


@pytest.mark.parametrize("fused", [True, False])
def test_worker_runs_the_zoo_module_and_counts(tmp_path, fused):
    """The unchanged master and worker run the zoo module; every trained
    task's third line carries the visible pairs beside the routed rows
    and the page's counter moves by them."""
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
        model_def="smallthinker.smallthinker_lm.custom_model",
        training_data=train, minibatch_size=4,
        num_minibatches_per_task=2, fuse_task_steps=fused,
    )
    with _Lines(worker_mod.logger) as log:
        before = _page_counter()
        cluster.run()
    assert cluster.finished
    routing = [m for m in log.lines if " routing: " in m]
    assert len(routing) == 2
    # The zoo's CONFIG: 4 layers, 1 global (16 x 17 / 2 = 136 pairs a
    # row) and 3 under a window of 8 (36 + 8 x 8 = 100), 4 rows a step;
    # all 8 experts held: 4 rows x 16 positions x top-2 x 4 layers.
    pairs = 4 * (136 + 3 * 100)
    for line in routing:
        assert f"attn_visible_pairs=[{pairs}, {pairs}]" in line, line
        assert "moe_rows=[512, 512]" in line, line
    assert _page_counter() - before == 4 * pairs


def _page_counter():
    """``edl_tpu_worker_attn_visible_pairs_total`` as the master's page
    would show it (the process's registry)."""
    from elasticdl_tpu.observability.registry import default_registry

    series = default_registry().counter(
        "worker_attn_visible_pairs_total").snapshot()["series"]
    return sum(int(row["value"]) for row in series)
