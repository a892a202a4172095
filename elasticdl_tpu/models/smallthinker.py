"""Decoder LM whose layers mix two kinds of attention and put the
expert router before it: the SmallThinker family (PowerInfer,
``SmallThinker-21BA3B-Instruct``).

A fifth sibling beside ``models/transformer.py``, ``models/mla_moe.py``,
``models/nemotron_h.py`` and ``models/sdar_moe.py``. It shares
``_LMHead``, ``RMSNorm``, ``rope_halves``, the attention kernels, and,
whole, the second family's drop-free
:class:`~elasticdl_tpu.models.mla_moe.ExpertLayer`, told what this
family's is: ReLU-gated experts (``expert_form="relu_gated"``), a
float32 softmax over the router's whole width with the top k
renormalised, no selection bias, no shared expert. Its own:

- **A layer pattern read from two layouts.** ``sliding_window_layout[l]``
  says whether layer l's attention is a causal band (query i sees keys
  i - window + 1 .. i: ``flash_attention(..., mask=SlidingWindow(S,
  window))``, one call by a static plan whose dead steps lie on both
  sides of the band) or full causal; ``rope_layout[l]`` whether q and k
  are rotated (by halves, ``rope_halves``) or carry **no positional
  encoding at all** (NoPE: a global layer knows order from the causal
  mask alone). The published pattern is one global NoPE layer and then
  three windowed rotary ones. The two kinds bear different names in the
  Flax tree (``global_attn``, ``window_attn``), so the operation table
  of a profile (``utils/hlo_ops.py``, column ``module``) tells a window
  call from a global one; the kernel call itself sits under the scope
  ``attn`` in both, so its device operations are ``attn.N`` as in every
  family.
- **The router reads the layer's input.** A layer's expert choice is
  made from the residual stream as the layer received it, before the
  attention's norm, while the experts' rows are the stream after
  attention, normed (``ExpertLayer(..., router_input=x)``): the choice
  does not wait for the attention, which is what lets a deployment
  fetch experts while attention runs.
- **One counter through ``metrics``**, ``attn_visible_pairs``: the
  (query, key) pairs a head's attention calls were handed, summed over
  the minibatch's rows and the layers, read from the mask objects given
  to the kernel calls: the witness that the band is in the timed path
  (a window layer run as full causal counts S (S + 1) / 2).

The family takes no mesh.
"""

import functools
from dataclasses import dataclass
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.models.mla_moe import (
    ExpertLayer,
    RMSNorm,
    _add_counters,
    _dense,
)
from elasticdl_tpu.models.sdar_moe import rope_halves
from elasticdl_tpu.models.transformer import _LMHead
from elasticdl_tpu.ops.flash_attention import (
    SlidingWindow,
    describe_kept as describe_attention_kept,
    describe_tiles as describe_attention_tiles,
    flash_attention,
    log_traced as log_traced_attention,
    remat_policy as attention_remat_policy,
    supports as flash_supports,
)
from elasticdl_tpu.ops.ring_attention import dense_attention


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_layers: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    # Layer l attends under a band of ``sliding_window`` keys where
    # ``sliding_window_layout[l]`` is 1, full causal where 0; its q and
    # k are rotated where ``rope_layout[l]`` is 1.
    sliding_window: int = 8
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    # The expert layer's fields, under ``ExpertLayer``'s names.
    moe_intermediate_size: int = 32
    shared_intermediate_size: int = 0
    router_width: int = 8
    first_held: int = 0
    n_held: int = 8
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    expert_form: str = "relu_gated"
    scoring: str = "softmax"
    selection_bias: bool = False
    shared_expert: bool = False
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            if len(getattr(self, name)) != self.num_layers:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"{self.num_layers} layers")


def causal_pairs(s_len: int) -> int:
    """Visible (query, key) pairs of one row and head under the plain
    causal mask."""
    return s_len * (s_len + 1) // 2


class MixedAttention(nn.Module):
    """x (B, S, d) -> (attention's output (B, S, d), the visible pairs a
    head was given over the B rows: a Python int). ``window`` 0: full
    causal; ``rope`` False: no positional encoding."""
    cfg: SmallThinkerConfig
    window: int
    rope: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt = cfg.compute_dtype
        h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        group = h // hkv
        b, s = x.shape[:2]
        q = _dense((h, hd), dt, "q")(x)
        k = _dense((hkv, hd), dt, "k")(x)
        v = _dense((hkv, hd), dt, "v")(x)
        if self.rope:
            positions = jnp.arange(s)
            q = rope_halves(q, positions, cfg.rope_theta)
            k = rope_halves(k, positions, cfg.rope_theta)
        scale = hd ** -0.5
        # A window that reaches every key is the causal mask.
        mask = SlidingWindow(s, self.window) if 0 < self.window < s else None
        said = (
            f"{h} query heads over {hkv} key/value heads, head size {hd}; "
            + (f"sliding window {mask.window}" if mask else "full causal")
            + (", rotary" if self.rope else ", no positions"))
        backend = jax.default_backend()
        with jax.named_scope("attn"):
            if backend == "tpu" and flash_supports(q.shape, mask=mask):
                log_traced_attention(
                    "pallas flash kernel",
                    f"tpu backend, shape tiles the kernel blocks; {said}; "
                    + describe_attention_tiles(s, group=group, mask=mask)
                    + ("; " + describe_attention_kept(q)
                       if cfg.remat else ""), q.shape,
                )
                o = flash_attention(q, k, v, scale=scale, mask=mask)
            else:
                log_traced_attention(
                    "dense reference",
                    (f"backend is {backend}" if backend != "tpu" else
                     "a shape the kernels have no plan for")
                    + f"; {said}, key/value heads repeated", q.shape,
                )
                o = dense_attention(
                    q, jnp.repeat(k, group, axis=2),
                    jnp.repeat(v, group, axis=2), scale=scale,
                    causal=mask is None, mask=mask)
        out = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=dt,
            name="out",
        )(o)
        return out, b * (mask.pairs if mask else causal_pairs(s))


class SmallThinkerBlock(nn.Module):
    """r = x; x += Attn(RMSNorm(x)); x += Experts(RMSNorm(x), router
    reads r). Returns (x, the layer's counters)."""
    cfg: SmallThinkerConfig
    layer: int

    @nn.compact
    def __call__(self, x, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        windowed = bool(cfg.sliding_window_layout[self.layer])
        read = x
        a, pairs = MixedAttention(
            cfg, cfg.sliding_window if windowed else 0,
            bool(cfg.rope_layout[self.layer]),
            name="window_attn" if windowed else "global_attn",
        )(RMSNorm(cfg.rms_eps, dt, name="attn_norm")(x))
        x = x + a
        h, counters = ExpertLayer(cfg, name="moe")(
            RMSNorm(cfg.rms_eps, dt, name="ffn_norm")(x), routing,
            router_input=read)
        # int32: 620,785,664 a row at S 16,384 over 2 global and 6
        # window layers of 4,096, so a minibatch of 4 such rows would
        # pass 2^31 - 1 in one step (the worker sums steps in int64).
        counters["attn_visible_pairs"] = jnp.int32(pairs)
        return x + h, counters


class SmallThinkerLM(nn.Module):
    """``features`` = int32 token ids (B, S); logits (B, S, V) float32
    in evaluation, ``{"logits": ..., "metrics": {...}}`` in training.
    ``routing``: one (B, S, k) array of expert ids for every layer in
    order, held in place of the layers' own choices."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, features, training=False, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        tokens = features.astype(jnp.int32)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=dt, name="token_embed"
        )(tokens)
        block_cls = (
            nn.remat(SmallThinkerBlock, policy=attention_remat_policy())
            if cfg.remat else SmallThinkerBlock
        )
        counters = {}
        held = iter(routing) if routing is not None else None
        for i in range(cfg.num_layers):
            x, layer_counters = block_cls(cfg, i, name=f"block_{i}")(
                x, next(held) if held else None)
            counters = _add_counters(counters, layer_counters)
        logits = _LMHead(cfg.vocab_size, dt, name="lm_head")(
            RMSNorm(cfg.rms_eps, dt, name="final_norm")(x)
        ).astype(jnp.float32)
        if not training:
            return logits
        return {"logits": logits, "metrics": counters}
