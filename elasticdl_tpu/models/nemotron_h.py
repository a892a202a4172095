"""Decoder-only LM of the Nemotron-H block family: a stack in which every
layer is ONE mixer behind a pre-norm residual, ``x + f(RMSNorm(x))``,
and a pattern string says which: ``M`` a Mamba-2 mixer, ``*`` attention
with fewer key/value heads than query heads, ``E`` an expert layer.
No position is added anywhere (the Mamba-2 layers carry the order).

A third sibling beside ``models/transformer.py`` and
``models/mla_moe.py``. It shares ``_LMHead``, the attention kernels, the losses, ``RMSNorm`` and, whole, the second
family's drop-free :class:`~elasticdl_tpu.models.mla_moe.ExpertLayer`
(told what it holds; here its experts are ``W_down(relu(W_up x)^2)`` and
the shared expert is wider than a routed one). Its own:

- **Mamba-2 mixer** (:class:`Mamba2Mixer`; Dao and Gu 2024): ``[z | xBC
  | dt] = W_in x``; a causal depthwise convolution and SiLU over xBC,
  split into x (H heads of P), B and C (G groups of N); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the selective scan
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D
  x_t`` in chunks (``ops/ssd_scan.py``: Pallas kernels, forward and
  backward); ``y = GroupRMSNorm(y * silu(z))`` (the gate first, then the
  norm over each of the G groups, a learned scale); ``W_out y``.
- **Grouped-query attention** (:class:`GroupedQueryAttention`): q over H
  heads, k and v over Hkv, query head h reading key/value head ``h //
  (H / Hkv)``; the flash kernels read the shared head in place.

In training the model returns ``{"logits", "metrics"}`` (``metrics``:
the expert layers' int32 counters, which leave the step beside the
loss: ``core/step.py``); in evaluation the logits alone. The family
takes no mesh: nothing of it is sharded yet, and the scan's kernels have
not run under one.
"""

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.models.mla_moe import (
    ExpertLayer,
    RMSNorm,
    _add_counters,
    _dense,
)
from elasticdl_tpu.models.transformer import _LMHead
from elasticdl_tpu.ops.flash_attention import (
    describe_kept as describe_attention_kept,
    describe_tiles as describe_attention_tiles,
    flash_attention,
    log_traced as log_traced_attention,
    remat_policy as attention_remat_policy,
    supports as flash_supports,
)
from elasticdl_tpu.ops.ring_attention import dense_attention
from elasticdl_tpu.ops.ssd_scan import log_traced as log_traced_ssd, ssd_scan

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    pattern: str = "ME*E"           # one letter a layer
    # Mamba-2: H heads of P, G groups of state N.
    mamba_num_heads: int = 4
    mamba_head_dim: int = 8
    n_groups: int = 2
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 8
    # Attention.
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    # The expert layer's fields, under ``ExpertLayer``'s names: the
    # router always scores ``router_width`` experts; this member holds
    # ``n_held`` of them, from ``first_held`` on.
    moe_intermediate_size: int = 32
    shared_intermediate_size: int = 64
    router_width: int = 8
    first_held: int = 0
    n_held: int = 8
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    expert_form: str = "relu2"
    scoring: str = "sigmoid"
    selection_bias: bool = True
    shared_expert: bool = True
    rms_eps: float = 1e-5
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def _dt_bias_init(low=1e-3, high=1e-1, floor=1e-4):
    """softplus^-1 of a time step drawn log-uniformly in [low, high]
    (Mamba-2's initialiser)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (jnp.log(high) - jnp.log(low)) + jnp.log(low))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(
        key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def causal_depthwise_conv(x, weight, bias):
    """x (B, S, C), weight (K, C), bias (C,): ``y_t = bias + sum_k
    weight[k] x_{t - (K-1) + k}``, positions before the first zero;
    float32."""
    taps = weight.shape[0]
    s_len = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(
        weight[k] * padded[:, k:k + s_len] for k in range(taps))


def group_rms_norm(x, scale, groups, eps):
    """RMSNorm over each of ``groups`` equal slices of the last axis,
    then one learned scale; float32 statistics."""
    x32 = x.astype(jnp.float32)
    grouped = x32.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    inv = jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped * inv).reshape(x.shape) * scale


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt_ = cfg.compute_dtype
        h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.ssm_state_size
        inner, channels = cfg.mamba_inner, cfg.conv_channels
        b, s, _ = x.shape
        conv_w = self.param(
            "conv_weight", nn.initializers.normal(cfg.conv_kernel ** -0.5),
            (cfg.conv_kernel, channels), jnp.float32)
        conv_b = self.param(
            "conv_bias", nn.initializers.zeros_init(), (channels,),
            jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(), (h,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        d_skip = self.param(
            "D", nn.initializers.ones_init(), (h,), jnp.float32)
        norm_scale = self.param(
            "norm_scale", nn.initializers.ones_init(), (inner,), jnp.float32)

        zxbcdt = _dense(inner + channels + h, dt_, "in_proj")(x)
        z = zxbcdt[..., :inner]
        xbc = nn.silu(causal_depthwise_conv(
            zxbcdt[..., inner:inner + channels], conv_w, conv_b
        )).astype(dt_)
        step = jax.nn.softplus(
            zxbcdt[..., inner + channels:].astype(jnp.float32) + dt_bias)
        backend = jax.default_backend()
        log_traced_ssd(
            (b, s, h, p), g, n, cfg.chunk_size,
            "pallas chunk kernel" if backend == "tpu"
            else f"pallas chunk kernel, interpreted (backend is {backend})",
        )
        y = ssd_scan(
            xbc[..., :inner].reshape(b, s, h, p), step, -jnp.exp(a_log),
            xbc[..., inner:inner + g * n].reshape(b, s, g, n),
            xbc[..., inner + g * n:].reshape(b, s, g, n),
            d_skip, chunk=cfg.chunk_size,
        ).reshape(b, s, inner)
        y = group_rms_norm(
            y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32)),
            norm_scale, g, cfg.rms_eps,
        ).astype(dt_)
        return _dense(cfg.hidden_size, dt_, "out_proj")(y)


class GroupedQueryAttention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt = cfg.compute_dtype
        h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        group = h // hkv
        s = x.shape[1]
        q = _dense((h, hd), dt, "q")(x)
        k = _dense((hkv, hd), dt, "k")(x)
        v = _dense((hkv, hd), dt, "v")(x)
        scale = hd ** -0.5
        backend = jax.default_backend()
        heads = (f"{h} query heads over {hkv} key/value heads, head size "
                 f"{hd}")
        if backend == "tpu" and flash_supports(q.shape):
            log_traced_attention(
                "pallas flash kernel",
                f"tpu backend, shape tiles the kernel blocks; {heads}; "
                + describe_attention_tiles(s, group=group)
                + ("; " + describe_attention_kept(q)
                   if cfg.remat else ""), q.shape,
            )
            o = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            log_traced_attention(
                "dense reference",
                (f"backend is {backend}" if backend != "tpu" else
                 "a shape that does not tile the kernel blocks")
                + f"; {heads}, key/value heads repeated", q.shape,
            )
            o = dense_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                causal=True, scale=scale)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=dt,
            name="out",
        )(o)


class NemotronLayer(nn.Module):
    """x + mixer(RMSNorm(x)), the mixer by ``kind``. Returns (x, the
    layer's counters: an expert layer's, else none)."""
    cfg: NemotronHConfig
    kind: str = MAMBA

    @nn.compact
    def __call__(self, x, routing=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="norm")(x)
        counters = {}
        if self.kind == MAMBA:
            h = Mamba2Mixer(cfg, name="mamba")(h)
        elif self.kind == ATTENTION:
            h = GroupedQueryAttention(cfg, name="attn")(h)
        elif self.kind == EXPERTS:
            h, counters = ExpertLayer(cfg, name="moe")(h, routing)
        else:
            raise ValueError(f"layer kind {self.kind!r}: not M, * or E")
        return x + h, counters


class NemotronHLM(nn.Module):
    """``features`` = int32 token ids (B, S). ``routing``: one (B, S, k)
    array of expert ids for every expert layer in order, held in place
    of the layers' own choices."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, features, training=False, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        tokens = features.astype(jnp.int32)
        layer_cls = (
            nn.remat(NemotronLayer, policy=attention_remat_policy())
            if cfg.remat else NemotronLayer
        )
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=dt, name="token_embed"
        )(tokens)
        counters = {}
        held = iter(routing) if routing is not None else None
        for i, kind in enumerate(cfg.pattern):
            x, layer_counters = layer_cls(cfg, kind=kind, name=f"layer_{i}")(
                x, next(held) if held and kind == EXPERTS else None)
            counters = _add_counters(counters, layer_counters)
        logits = _LMHead(cfg.vocab_size, dt, name="lm_head")(
            RMSNorm(cfg.rms_eps, dt, name="final_norm")(x)
        ).astype(jnp.float32)
        if not training:
            return logits
        return {"logits": logits, "metrics": counters}
