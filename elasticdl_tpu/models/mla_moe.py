"""Decoder-only LM of the DeepSeek-V3 block family: multi-head latent
attention, a leading dense layer followed by sparse-expert layers with a
shared expert, and a depth-1 multi-token-prediction module.

A sibling of ``models/transformer.py`` (whose GPT-2 program stays what it
is): it shares ``_Constrain``, ``_LMHead``, the attention kernels and
the losses, and nothing else. Pre-RMSNorm blocks, no biases:

- **MLA**: queries through a low-rank bottleneck (``q_lora_rank``), keys
  and values through one shared latent (``kv_lora_rank``) plus one
  rotary key part shared by all heads. RoPE turns the pairs (2i, 2i+1)
  of the rotary part. q and k have head size ``qk_nope + qk_rope``, v
  has ``v_head_dim``: the flash kernels take both as their arguments'
  shapes say.
- **Expert layer** (:class:`ExpertLayer`): the router scores ALL
  ``router_width`` experts (sigmoid, float32), picks the top k of score +
  selection bias, and weighs the chosen by their normalised scores. The
  layer is told which experts it holds (``first_held``, ``n_held``) and
  computes its own experts' part of the result, drop-free: token-choices
  sorted by expert, grouped matrix products over the held groups, the
  weighted rows gathered back. What absent experts would add is left
  out. The held experts own ``n_held / router_width`` of the router, so
  the work runs over a static bound of rows, not over all ``T*k``
  token-choices: a ladder of static sizes (:func:`rows_ladder`: that
  share times ``RUNG_FACTORS``, then ``T*k`` itself), the smallest
  rung that holds a step's held rows chosen on the device by the traced
  count (``moe_bound_rows`` counts the rung's rows; a layer whose held
  rows pass every rung runs over every token-choice and
  ``moe_overflow_layers`` counts it), so no row is ever dropped.
  Under a mesh with an ``ep`` axis each member holds ``n_held / ep`` of
  them, bounds its own share, and the parts are summed over ``ep``. The
  selection bias only selects; what the backward pass returns for it is
  its load's direction (:func:`_load_tap`), for an optimizer that moves
  it by plain descent (auxiliary-loss-free balancing; the zoo's does).
  ``routing=`` holds the layers to choices given from outside (routing
  replay: another precision's, another engine's).
- **MTP**: h'_i = W_eh [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))], one more
  expert block, a last RMSNorm, and the main model's embedding and head.

In training the model returns ``{"logits", "mtp_logits", "metrics"}``
(or the fused-head triples under those keys): ``metrics`` are int32
counters that leave the step beside the loss (``core/step.py``). In
evaluation it returns the main logits alone.
"""

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.models.transformer import _Constrain, _LMHead
from elasticdl_tpu.ops.flash_attention import (
    describe_kept as describe_attention_kept,
    describe_tiles as describe_attention_tiles,
    flash_attention,
    log_traced as log_traced_attention,
    remat_policy as attention_remat_policy,
    supports as flash_supports,
)
from elasticdl_tpu.ops.grouped_matmul import (
    GROUPED_PRODUCT,
    grouped_matmul,
    product_width,
    zero_padded,
)
from elasticdl_tpu.ops.ring_attention import dense_attention

logger = get_logger("experts")

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_layers: int = 3             # leading dense layers included
    first_k_dense: int = 1
    intermediate_size: int = 128    # the dense layers' MLP width
    moe_intermediate_size: int = 32  # every expert's width
    num_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # The router always scores ``router_width`` experts; this member
    # holds ``n_held`` of them, from ``first_held`` on.
    router_width: int = 8
    first_held: int = 0
    n_held: int = 8
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    mtp_layers: int = 1             # 0 or 1
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16
    fused_head: bool = False
    # What an expert computes (``EXPERT_FORMS``), and the shared
    # expert's width where it is not a routed one's (0: the same).
    expert_form: str = "silu_gated"
    shared_intermediate_size: int = 0
    # How the router scores (``SCORINGS``), and whether the layer has a
    # selection bias and a shared expert (this family has both).
    scoring: str = "sigmoid"
    selection_bias: bool = True
    shared_expert: bool = True

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_moe_sharding_rules() -> Tuple[Tuple[str, P], ...]:
    """Regex path -> PartitionSpec (``parallel/rules.py``): the stacked
    expert weights on ``ep``, heads and MLP widths on ``tp``."""
    return (
        (r"moe/w_(gate|up|down)", P("ep", None, None)),
        (r"attn/(q_b|kv_b)/kernel", P(None, "tp", None)),
        (r"attn/out/kernel", P("tp", None, None)),
        (r"(mlp|shared)/(gate|up)/kernel", P(None, "tp")),
        (r"(mlp|shared)/down/kernel", P("tp", None)),
        (r"token_embed/embedding", P("tp", None)),
        (r"lm_head/kernel", P(None, "tp")),
        (r"lm_head/bias", P("tp")),
    )


class RMSNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * g, the statistics in float32."""
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.ones_init(), (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (x32 * inv * scale).astype(self.dtype)


def rope_interleaved(x, theta: float, offset=0):
    """Rotary embedding over the last axis of ``x`` (B, S, ..., R): the
    pair (2i, 2i+1) is turned by position * theta^(-2i/R). float32
    inside, ``x``'s dtype out."""
    r = x.shape[-1]
    seq = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (offset + jnp.arange(seq, dtype=jnp.float32))[:, None] * inv_freq
    shape = (1, seq) + (1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    )
    return turned.reshape(x.shape).astype(x.dtype)


def _dense(features, dtype, name):
    return nn.DenseGeneral(features, use_bias=False, dtype=dtype, name=name)


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt = cfg.compute_dtype
        wsc = _Constrain(self.mesh)
        h, nope, rope, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        b, s, _ = x.shape
        c_q = RMSNorm(cfg.rms_eps, dt, name="q_norm")(
            _dense(cfg.q_lora_rank, dt, "q_a")(x)
        )
        q = wsc(_dense((h, nope + rope), dt, "q_b")(c_q),
                "dp", None, "tp", None)
        kv = _dense(cfg.kv_lora_rank + rope, dt, "kv_a")(x)
        c_kv = RMSNorm(cfg.rms_eps, dt, name="kv_norm")(
            kv[..., :cfg.kv_lora_rank]
        )
        k_rope = rope_interleaved(
            kv[..., cfg.kv_lora_rank:], cfg.rope_theta
        )                                               # (B, S, rope)
        k_v = wsc(_dense((h, nope + dv), dt, "kv_b")(c_kv),
                  "dp", None, "tp", None)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], cfg.rope_theta)],
            axis=-1,
        )
        k = jnp.concatenate(
            [k_v[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope))],
            axis=-1,
        )
        v = k_v[..., nope:]
        scale = cfg.qk_head_dim ** -0.5
        backend = jax.default_backend()
        heads = f"head sizes q/k {nope + rope}, v {dv}"
        if self.mesh is None and backend == "tpu" and flash_supports(q.shape):
            log_traced_attention(
                "pallas flash kernel",
                f"tpu backend, shape tiles the kernel blocks; {heads}; "
                + describe_attention_tiles(s)
                + ("; " + describe_attention_kept(v)
                   if cfg.remat else ""), q.shape,
            )
            o = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            log_traced_attention(
                "dense reference",
                (f"backend is {backend}" if backend != "tpu" else
                 "a mesh, or a shape that does not tile the kernel blocks")
                + f"; {heads}", q.shape,
            )
            o = dense_attention(q, k, v, causal=True, scale=scale)
        o = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=dt,
            name="out",
        )(o)
        return wsc(o, "dp", None, None)


class GatedMlp(nn.Module):
    """W_down(act(W_gate x) * (W_up x)), ``act`` SiLU unless told."""
    width: int
    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None
    act: Callable = nn.silu

    @nn.compact
    def __call__(self, x):
        dt = self.cfg.compute_dtype
        wsc = _Constrain(self.mesh)
        gate = _dense(self.width, dt, "gate")(x)
        up = _dense(self.width, dt, "up")(x)
        hidden = wsc(self.act(gate) * up, "dp", None, "tp")
        return _dense(self.cfg.hidden_size, dt, "down")(hidden)


def relu2(x):
    return jnp.square(nn.relu(x))


class Relu2Mlp(nn.Module):
    """W_down(relu(W_up x)^2): two matrices, no gate."""
    width: int
    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        dt = self.cfg.compute_dtype
        wsc = _Constrain(self.mesh)
        hidden = wsc(relu2(_dense(self.width, dt, "up")(x)),
                     "dp", None, "tp")
        return _dense(self.cfg.hidden_size, dt, "down")(hidden)


# An expert's form: (the shared expert's module, whether the routed
# experts have a gate matrix, the activation: of the gate's product
# where there is one, else of the up projection's). ``silu_gated``:
# DeepSeek-V3's and Qwen3-MoE's, three matrices; ``relu2``:
# Nemotron-H's, two; ``relu_gated``: SmallThinker's ReGLU, three.
EXPERT_FORMS = {
    "silu_gated": (GatedMlp, True, nn.silu),
    "relu2": (Relu2Mlp, False, relu2),
    "relu_gated": (functools.partial(GatedMlp, act=nn.relu), True, nn.relu),
}

# A router's scores of ALL its experts from their float32 logits (T,
# width): ``sigmoid``, each expert on its own (DeepSeek-V3's,
# Nemotron-H's); ``softmax``, probabilities over the whole width
# (Qwen3-MoE's). Either way the chosen experts' scores are normalised
# to sum to one.
SCORINGS = {
    "sigmoid": jax.nn.sigmoid,
    "softmax": functools.partial(jax.nn.softmax, axis=-1),
}


# How far over their expected rows the rungs of a member's static row
# bounds reach (:func:`rows_ladder`). The held experts own ``n /
# router_width`` of the router and get that share of the ``T*k``
# token-choices when the routing is balanced; a step's layer runs over
# the smallest rung that holds its held rows, and over all ``T*k``
# (exact, and as slow as before any bound) where they pass the last, so
# the first factor is what most layers of most steps need and the last
# what the heaviest need. Readings (a v5e; PERF.md, PR 37, calls 1 and
# 2), layers a step that passed a bound of 2: ``sdar_ep8_steady``
# (98-114k rows a step over 6 layers, 32,768 a layer) and
# ``nemotron3n_ep16_steady`` (21.6-29.6k over 4, 12,288 a layer) none in
# 784 steps of 6 seeds; ``joyai_ep16_steady`` (16,384 a layer) 1 or 2 of
# its 5 layers in EVERY step of seed 3000000011 (44.8-70.0k rows a
# step, the fullest expert of each layer 31-38k together: tokens without
# context crowd one or two held experts of a layer, and one expert can
# hold all 16,384 tokens) and in 18 of 120 steps of seed 3700000031. Of
# 4: none in 888 steps, ``joyai``'s 360 on 3 seeds with the heavy one
# among them; it clears a layer of two experts with every token each.
# What 4 costs beside 2 where 2 holds (a recomputed layer, ms): 29.8 /
# 20.7 ``sdar``, 25.6 / 21.1 ``nemotron3n``, 18.4 / 14.2 ``joyai``,
# against 41.3, 50.2 and 39.0 over every token-choice: as one constant
# 4 was ``joyai``'s need and the other cells' cost, so both are rungs.
RUNG_FACTORS = (2, 4)

# A rung is whole blocks of this many rows (a grouped product's row
# tile, and a sub-multiple of every cell's ``T*k``).
_ROW_BLOCK = 512


def rows_ladder(choices: int, n: int, router_width: int) -> Tuple[int, ...]:
    """The static sizes an expert layer's work may run over, strictly
    increasing: the share of ``choices`` (``T*k``) that ``n`` held
    experts of a router ``router_width`` wide expect, times each of
    ``RUNG_FACTORS``, in whole blocks of 512 rows, for as long as that
    stays under ``choices``; then ``choices`` itself, the path over every
    token-choice. Where every expert is held (or the first rung already
    reaches ``choices``) that is the whole ladder and one path is
    traced."""
    rungs = []
    for factor in RUNG_FACTORS:
        blocks = -(-factor * choices * n // (router_width * _ROW_BLOCK))
        rung = blocks * _ROW_BLOCK
        if rung >= choices:
            break
        if not rungs or rung > rungs[-1]:
            rungs.append(rung)
    return (*rungs, choices)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _spread(rows, order, inverse, live, k):
    """rows (T, d) -> (bound, d): token-choice ``order[j]`` (token
    ``order[j] // k``) at sorted place j, for the ``bound`` first
    sorted places (``order`` is that long; ``inverse`` is always all
    ``T*k``): a plain gather. Its transpose is :func:`_gather_back`.
    ``live`` (bound,) marks the sorted places that belong to a group: a
    grouped product's cotangent is unspecified at the others (on a TPU:
    whatever the memory held), and is cut off before it is summed into
    the tokens'."""
    return rows[order // k]


def _spread_fwd(rows, order, inverse, live, k):
    return _spread(rows, order, inverse, live, k), (order, inverse, live)


def _spread_bwd(k, res, g):
    order, inverse, live = res
    g = jnp.where(live[:, None], g, 0).astype(g.dtype)
    return _gather_back(g, order, inverse, live, k), None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_back(sorted_rows, order, inverse, live, k):
    """sorted rows (bound, d) -> (T, d): every token's k rows, summed in
    float32. Over all ``T*k`` places a plain gather by ``inverse`` (so
    that both directions of the path over every token-choice are
    gathers). Under a bound the ``bound`` rows are added into their
    tokens instead (rows of no group are zero by then): ``T*k`` gathers
    from the shorter source cost what they cost from the full one, 4.5
    ms a pass at the cells' shapes, and the scatter-add reads the
    bound's rows alone (``tools/bench_ssd_scan.py bound``; PERF.md, PR
    37: a recomputed layer 21.1 against 27.1 ms on ``nemotron3n``, 20.7
    against 25.9 on ``sdar``, 14.2 against 13.6 on ``joyai`` at an
    eighth or a quarter of the places, 18.4 against 23.1 and 25.6
    against 36.0 at twice that)."""
    t, d = inverse.shape[0] // k, sorted_rows.shape[1]
    if sorted_rows.shape[0] == inverse.shape[0]:
        picked = sorted_rows[inverse].reshape(t, k, d).astype(jnp.float32)
        return picked.sum(axis=1).astype(sorted_rows.dtype)
    summed = jnp.zeros((t, d), jnp.float32).at[order // k].add(
        sorted_rows.astype(jnp.float32))
    return summed.astype(sorted_rows.dtype)


def _gather_back_fwd(sorted_rows, order, inverse, live, k):
    return (_gather_back(sorted_rows, order, inverse, live, k),
            (order, inverse, live))


def _gather_back_bwd(k, res, g):
    order, inverse, live = res
    return _spread(g, order, inverse, live, k), None, None, None


_spread.defvjp(_spread_fwd, _spread_bwd)
_gather_back.defvjp(_gather_back_fwd, _gather_back_bwd)


def _sorted_choices(chosen, first_held, n):
    """Token-choices sorted by held expert, those of absent experts
    last: (``order`` (T*k,) the choice at every sorted place, ``inverse``
    its inverse, ``sizes`` (n,) the rows of every held expert, ``held``
    (T, k) whether a choice's expert is held)."""
    t, k = chosen.shape
    local = chosen - first_held
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n).reshape(t * k)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32), unique_indices=True
    )
    sizes = jnp.sum(
        group[:, None] == jnp.arange(n, dtype=group.dtype)[None, :],
        axis=0, dtype=jnp.int32,
    )
    return order, inverse, sizes, held


def _part_over(form, rows, weights, w_up, w_down, order, inverse, sizes,
               held):
    """The held experts' part computed over the first ``len(order)``
    sorted places (the bound: all ``T*k``, or fewer where the held rows
    are known to fit): the spread, both products, the activation and
    the row weights run over that many rows; the gather back reaches
    every token. ``w_up`` (n, d, wide) and ``w_down`` (n, wide, d) are
    the compute-type copies the products run on; ``form`` is the
    experts' (``EXPERT_FORMS``): where it has a gate, ``w_up`` is the
    gate's columns and then the up projection's, (n, d, 2 wide)."""
    _, gated, act = EXPERT_FORMS[form]
    t, k = held.shape
    bound = order.shape[0]
    dt = rows.dtype
    live = jnp.arange(bound, dtype=jnp.int32) < jnp.sum(sizes)
    sorted_rows = _spread(rows, order, inverse, live, k)
    if gated:
        wide = w_down.shape[1]
        gate_up = grouped_matmul(sorted_rows, w_up, sizes)
        hidden = act(gate_up[:, :wide]) * gate_up[:, wide:]
    else:
        hidden = act(grouped_matmul(sorted_rows, w_up, sizes))
    out = grouped_matmul(hidden, w_down, sizes)
    # Past the held rows a grouped product leaves what it likes, in its
    # result and in its cotangent: those rows are cut off (selected
    # away, here and in ``_spread``'s backward; a zero weight would
    # carry a NaN on) before anything multiplies them.
    row_weight = jnp.where(held, weights, 0.0).reshape(t * k)[order]
    out = jnp.where(live[:, None], out, 0) * row_weight[:, None].astype(dt)
    return _gather_back(out.astype(dt), order, inverse, live, k)


def _rungs_passed(ladder, held):
    """How many rungs of ``ladder`` below its last ``held`` rows pass:
    the place in the ladder of the smallest that holds them (0 where
    the ladder is one rung)."""
    return sum((held > rung).astype(jnp.int32) for rung in ladder[:-1])


def _on_the_rung(ladder, run, sizes, *operands):
    """``run(places, *operands)`` over the first sorted places of the
    smallest rung of ``ladder`` that holds the held rows, over every
    place (``None``) where they pass every rung below the last; chosen
    on the device by the traced count (:func:`_rungs_passed`)."""
    return jax.lax.switch(
        _rungs_passed(ladder, jnp.sum(sizes)),
        [functools.partial(run, places) for places in (*ladder[:-1], None)],
        *operands,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _part_under(ladder, form, rows, weights, w_up, w_down, order, inverse,
                sizes, held):
    """:func:`_part_over` at the smallest rung of ``ladder`` the held
    rows fit under, at ``T*k`` where they fit under none: no row is ever
    dropped. One custom rule around the conditional, so that JAX neither
    differentiates through it (each branch would be handed the others'
    residuals as arrays of zeros) nor keeps anything of its forward: the
    rule's residuals are its own arguments, and its backward is one
    conditional whose branches run their path again and pull the
    cotangent through it, on the rung the same ``sizes`` choose. Under a
    block's ``nn.remat`` the recomputed forward conditional is dead
    code, so a step still runs a layer's products forward twice and
    backward once."""
    def forward(places, rows, weights, w_up, w_down, order, *rest):
        return _part_over(form, rows, weights, w_up, w_down, order[:places],
                          *rest)

    return _on_the_rung(ladder, forward, sizes, rows, weights, w_up, w_down,
                        order, inverse, sizes, held)


def _part_under_fwd(ladder, form, *operands):
    return _part_under(ladder, form, *operands), operands


def _part_under_bwd(ladder, form, operands, g):
    def backward(places, g, rows, weights, w_up, w_down, order, *rest):
        _, pull = jax.vjp(
            lambda *moving: _part_over(form, *moving, order[:places], *rest),
            rows, weights, w_up, w_down,
        )
        return pull(g)

    *_, sizes, _ = operands
    moved = _on_the_rung(ladder, backward, sizes, g, *operands)
    return (*moved, None, None, None, None)


_part_under.defvjp(_part_under_fwd, _part_under_bwd)


def held_experts_part(rows, chosen, weights, w_gate, w_up, w_down,
                      first_held, router_width, form=None):
    """The held experts' part of an expert layer's result, drop-free.

    rows (T, d); chosen (T, k) int32 expert ids over the router's whole
    width ``router_width``; weights (T, k); w_gate/w_up (n, d, f),
    w_down (n, f, d): the experts ``first_held .. first_held + n``
    (``first_held`` may be traced: a member's place on ``ep``).
    ``form`` names what an expert computes (``EXPERT_FORMS``):
    ``relu2`` ``w_down(relu(w_up x)^2)`` (``w_gate`` None),
    ``silu_gated`` and ``relu_gated`` ``w_down(act(w_gate x) * (w_up
    x))``; not given, it is ``relu2`` without a gate and ``silu_gated``
    with one. Token-choices are sorted by expert with those of absent
    experts last; the spread, the grouped products over the n held
    groups and what lies between them run over the smallest rung of
    :func:`rows_ladder` that holds the step's held rows, all ``T*k``
    where none below does; no capacity, so no choice of a held expert
    is ever dropped. Returns (part (T, d), rows of every held expert
    (n,) int32)."""
    t, k = chosen.shape
    n = w_up.shape[0]
    order, inverse, sizes, held = _sorted_choices(chosen, first_held, n)
    # The two products run at ``product_width`` of the experts' width:
    # the compute-type copies of the weights are zero-padded to it (the
    # parameters and their gradients keep their shapes: a pad's
    # transpose cuts), and ``hidden`` stays that wide from the first
    # product to the second. The added columns are act(0) = 0 in every
    # live row and meet zero rows of ``w_down``.
    wide = product_width(w_up.shape[2])

    def widened(w, axis):
        return zero_padded(w.astype(rows.dtype), axis, wide)

    form = form or ("relu2" if w_gate is None else "silu_gated")
    if EXPERT_FORMS[form][1] != (w_gate is not None):
        raise ValueError(
            f"experts of form {form} "
            + ("have no gate" if w_gate is not None else "need w_gate"))
    up = widened(w_up, 2)
    if w_gate is not None:
        up = jnp.concatenate([widened(w_gate, 2), up], axis=2)
    operands = (rows, weights, up, widened(w_down, 1), order, inverse, sizes,
                held)
    ladder = rows_ladder(t * k, n, router_width)
    if len(ladder) == 1:
        return _part_over(form, *operands), sizes
    return _part_under(ladder, form, *operands), sizes


@functools.lru_cache(maxsize=None)
def log_traced_experts(cfg: MlaMoeConfig, ladder: Tuple[int, ...], ep: int):
    """One static line per traced expert layer shape (every layer of
    every trace asks again), like the attention's: what is held, what
    the router scores, the static sizes of the rows a member's work may
    run over (:func:`rows_ladder`: the rungs, then the ``T*k``
    token-choices it falls back to), which grouped product runs, and the
    width it runs at where that is not the experts' own
    (:func:`product_width`)."""
    *rungs, choices = ladder
    f = cfg.moe_intermediate_size
    shared = cfg.shared_intermediate_size or f
    wide = product_width(f)
    products = f", products at {wide} (zero columns)" if wide != f else ""
    logger.info(
        "experts: traced drop-free layer holding experts [%d, %d) of "
        "router width %d, top-%d, rows bound %s of %d, grouped product "
        "%s%s%s%s",
        cfg.first_held, cfg.first_held + cfg.n_held, cfg.router_width,
        cfg.top_k, " / ".join(map(str, rungs or ladder)), choices,
        GROUPED_PRODUCT,
        f", {cfg.n_held // ep} a member over ep={ep}" if ep > 1 else "",
        products if cfg.expert_form == "silu_gated" else (
            f", experts {cfg.expert_form.replace('_', '-')} of width "
            f"{f}{products}"
            + (f", shared expert {shared}" if cfg.shared_expert else "")),
        "" if (cfg.scoring, cfg.selection_bias, cfg.shared_expert) == (
            "sigmoid", True, True) else (
            f", {cfg.scoring} scores"
            + ("" if cfg.selection_bias else ", no selection bias")
            + ("" if cfg.shared_expert else ", no shared expert")),
    )


@jax.custom_vjp
def _load_tap(bias, load):
    """Zero, added to the layer's result. Its "gradient" for the
    selection bias is not the loss's (the bias only selects, the loss
    does not see it) but the load's: sign(an expert's token-choices -
    the mean over the router's width), so that plain gradient descent at
    the bias update speed is the auxiliary-loss-free balancing rule,
    b += speed * sign(mean - load) (Wang et al. 2024; DeepSeek-V3,
    section 2.1.2), in the same backward pass as everything else."""
    return jnp.zeros((), jnp.float32)


def _load_tap_fwd(bias, load):
    return _load_tap(bias, load), load


def _load_tap_bwd(load, _):
    load = load.astype(jnp.float32)
    return jnp.sign(load - jnp.mean(load)), None


_load_tap.defvjp(_load_tap_fwd, _load_tap_bwd)


def balanced_adam(lr, bias_update_speed, warmup_steps=0):
    """The optimizer of a model with :class:`ExpertLayer`s: Adam at
    ``lr``, reached linearly from 0 over ``warmup_steps`` steps where
    given (the first step then moves nothing); the selection biases
    (``router_bias``) by plain descent at ``bias_update_speed``, which
    with :func:`_load_tap` is the auxiliary-loss-free balancing rule."""
    import optax

    def kinds(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "selection_bias" if path[-1].key == "router_bias"
            else "weights", params)

    rate = optax.linear_schedule(0.0, lr, warmup_steps) if warmup_steps else lr
    return optax.multi_transform(
        {"weights": optax.adam(rate),
         "selection_bias": optax.sgd(bias_update_speed)}, kinds)


class ExpertLayer(nn.Module):
    """Shared expert (where the configuration has one) + the held routed
    experts' weighted part.

    ``routing`` (B, S, k) int32, where given, are the experts every
    token goes to in place of the layer's own top k (routing replay:
    the weights are still this layer's scores of them).
    ``router_input`` (B, S, d), where given, is what the router reads
    in place of ``x`` (a router placed before attention reads the
    layer's input while the experts take the stream after it); the
    experts' rows are ``x`` either way.

    ``cfg`` is any configuration with the expert layer's fields
    (``n_held``, ``first_held``, ``router_width``, ``top_k``,
    ``routed_scaling_factor``, ``moe_intermediate_size``,
    ``shared_intermediate_size``, ``expert_form``, ``scoring``,
    ``selection_bias``, ``shared_expert``, ``compute_dtype``): this
    family's, ``models/nemotron_h.py``'s, ``models/sdar_moe.py``'s or
    ``models/smallthinker.py``'s (a shared expert's module reads
    ``hidden_size`` too)."""
    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, routing=None, router_input=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, s, d = x.shape
        n, f, k = cfg.n_held, cfg.moe_intermediate_size, cfg.top_k
        wsc = _Constrain(self.mesh)
        router = self.param(
            "router", nn.initializers.normal(0.02),
            (d, cfg.router_width), jnp.float32,
        )
        # Selects only: it enters no product. What ``jax.grad`` returns
        # for it is the load's direction (:func:`_load_tap`).
        bias = self.param(
            "router_bias", nn.initializers.zeros_init(),
            (cfg.router_width,), jnp.float32,
        ) if cfg.selection_bias else None
        init = nn.initializers.normal(0.02)
        shared_mlp, gated, _ = EXPERT_FORMS[cfg.expert_form]
        w_gate = (self.param("w_gate", init, (n, d, f), jnp.float32)
                  if gated else None)
        w_up = self.param("w_up", init, (n, d, f), jnp.float32)
        w_down = self.param("w_down", init, (n, f, d), jnp.float32)

        rows = x.reshape(b * s, d)
        read = rows if router_input is None else router_input.reshape(
            b * s, d)
        scores = SCORINGS[cfg.scoring](jnp.matmul(
            read.astype(jnp.float32), router, precision=HIGHEST
        ))                                              # (T, width) f32
        if routing is None:
            _, chosen = jax.lax.top_k(
                scores if bias is None
                else scores + jax.lax.stop_gradient(bias), k
            )
        else:
            chosen = routing.reshape(b * s, k).astype(jnp.int32)
        self.sow("intermediates", "chosen", chosen.reshape(b, s, k))
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = picked / (
            picked.sum(axis=-1, keepdims=True) + 1e-20
        ) * cfg.routed_scaling_factor

        ep = 1 if self.mesh is None else self.mesh.shape.get("ep", 1)
        ladder = rows_ladder(b * s * k, n // ep, cfg.router_width)
        log_traced_experts(cfg, ladder, ep)
        if ep > 1:
            part, sizes = self._over_ep(
                rows, chosen, weights, w_gate, w_up, w_down, ep
            )
        else:
            part, sizes = held_experts_part(
                rows, chosen, weights, w_gate, w_up, w_down, cfg.first_held,
                cfg.router_width, cfg.expert_form,
            )
        load = jnp.sum(
            chosen[..., None] == jnp.arange(cfg.router_width), axis=(0, 1),
            dtype=jnp.int32,
        )
        if bias is not None:
            part = part + _load_tap(bias, load).astype(dt)
        shared = shared_mlp(
            cfg.shared_intermediate_size or f, cfg, self.mesh, name="shared"
        )(x) if cfg.shared_expert else None
        counters = {
            "moe_rows": jnp.sum(sizes), "moe_expert_rows_max": jnp.max(sizes),
        }
        # What the ladder did with this step's routing. Whether a
        # member's held rows passed the last rung below the path over
        # every token-choice (never, where that path is the whole
        # ladder): counted, so that a routing the ladder was not written
        # for shows. And the rows of the rung each member's work ran
        # over, by the index ``held_experts_part`` switches on, summed
        # over the members.
        held = jnp.sum(sizes.reshape(ep, n // ep), axis=1)
        last = ladder[-2] if len(ladder) > 1 else ladder[-1]
        counters["moe_overflow_layers"] = jnp.any(held > last).astype(
            jnp.int32)
        counters["moe_bound_rows"] = jnp.sum(jnp.asarray(ladder, jnp.int32)[
            _rungs_passed(ladder, held)]) if len(ladder) > 1 else jnp.int32(
                ep * ladder[0])
        part = part.reshape(b, s, d)
        out = wsc(part if shared is None else shared + part,
                  "dp", None, None)
        return out, jax.lax.stop_gradient(counters)

    def _over_ep(self, rows, chosen, weights, w_gate, w_up, w_down, ep):
        """Each member of ``ep`` holds ``n_held / ep`` experts and is
        told which; every member sees all rows (no exchange is written
        here: the rows are replicated over ``ep``), and the parts add."""
        cfg = self.cfg
        if cfg.n_held % ep:
            raise ValueError(
                f"{cfg.n_held} held experts do not divide over ep={ep}"
            )
        per = cfg.n_held // ep

        def member(rows, chosen, weights, w_gate, w_up, w_down):
            first = cfg.first_held + jax.lax.axis_index("ep") * per
            part, sizes = held_experts_part(
                rows, chosen, weights, w_gate, w_up, w_down, first,
                cfg.router_width, cfg.expert_form,
            )
            return jax.lax.psum(part, "ep"), sizes

        stacked = P("ep", None, None)
        return jax.shard_map(
            member, mesh=self.mesh,
            in_specs=(P(), P(), P(), None if w_gate is None else stacked,
                      stacked, stacked),
            out_specs=(P(), P("ep")), check_vma=False,
        )(rows, chosen, weights, w_gate, w_up, w_down)


class MlaBlock(nn.Module):
    """x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)); FFN is the dense
    gated MLP or the expert layer. Returns (x, the layer's counters)."""
    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None
    use_experts: bool = True

    @nn.compact
    def __call__(self, x, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        h = RMSNorm(cfg.rms_eps, dt, name="attn_norm")(x)
        x = x + LatentAttention(cfg, self.mesh, name="attn")(h)
        h = RMSNorm(cfg.rms_eps, dt, name="ffn_norm")(x)
        if self.use_experts:
            h, counters = ExpertLayer(cfg, self.mesh, name="moe")(h, routing)
        else:
            h = GatedMlp(
                cfg.intermediate_size, cfg, self.mesh, name="mlp"
            )(h)
            counters = {}
        return x + h, counters


def _add_counters(total, counters):
    return {
        name: total.get(name, 0) + value for name, value in counters.items()
    } if counters else total


class MlaMoeLM(nn.Module):
    """``features`` = int32 token ids (B, S); see the module docstring
    for what training and evaluation return. ``routing``: one (B, S, k)
    array of expert ids for every expert layer in order, the MTP
    block's last, held in place of the layers' own choices."""

    cfg: MlaMoeConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, features, training=False, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        wsc = _Constrain(self.mesh)
        tokens = features.astype(jnp.int32)
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=dt, name="token_embed"
        )
        head = _LMHead(
            cfg.vocab_size, dt, fused=(cfg.fused_head and training),
            name="lm_head",
        )
        block_cls = (
            nn.remat(MlaBlock, policy=attention_remat_policy())
            if cfg.remat else MlaBlock
        )
        x = wsc(embed(tokens), "dp", None, None)
        counters = {}
        held = iter(routing) if routing is not None else None
        for i in range(cfg.num_layers):
            experts = i >= cfg.first_k_dense
            x, layer_counters = block_cls(
                cfg, self.mesh, use_experts=experts, name=f"block_{i}",
            )(x, next(held) if held and experts else None)
            counters = _add_counters(counters, layer_counters)
        logits = self._head(
            head, RMSNorm(cfg.rms_eps, dt, name="final_norm")(x), wsc
        )
        # ``init`` runs the evaluation form and has to make the MTP
        # module's parameters too.
        if not (training or self.is_initializing()):
            return logits
        out = {"logits": logits}
        if cfg.mtp_layers:
            # t_{i+1} is the next input token; the last position has
            # none, keeps its place (the kernels' shapes stay tiled)
            # and is masked out of the loss.
            joined = jnp.concatenate([
                RMSNorm(cfg.rms_eps, dt, name="mtp_hnorm")(x),
                RMSNorm(cfg.rms_eps, dt, name="mtp_enorm")(
                    embed(jnp.roll(tokens, -1, axis=1))
                ),
            ], axis=-1)
            h = _dense(cfg.hidden_size, dt, "mtp_eh_proj")(joined)
            h, layer_counters = block_cls(
                cfg, self.mesh, use_experts=True, name="mtp_block"
            )(h, next(held) if held else None)
            counters = _add_counters(counters, layer_counters)
            out["mtp_logits"] = self._head(
                head, RMSNorm(cfg.rms_eps, dt, name="mtp_final_norm")(h),
                wsc,
            )
        out["metrics"] = counters
        return out if training else logits

    @staticmethod
    def _head(head, x, wsc):
        out = head(x)
        if isinstance(out, tuple):
            hidden, kernel, bias = out
            return (wsc(hidden, "dp", None, None), wsc(kernel, None, "tp"),
                    wsc(bias, "tp"))
        return wsc(out.astype(jnp.float32), "dp", None, "tp")
