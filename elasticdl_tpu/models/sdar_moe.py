"""Block-diffusion LM over a Qwen3-MoE body: the SDAR family ("Synergistic
Diffusion-AutoRegression", arXiv:2510.06303), trained with block
diffusion's objective and attention mask (BD3-LM, arXiv:2503.09573).

A fourth sibling beside ``models/transformer.py``, ``models/mla_moe.py``
and ``models/nemotron_h.py``. It shares ``_LMHead``, ``RMSNorm``, the
attention kernels, the losses' module and, whole, the second family's
drop-free :class:`~elasticdl_tpu.models.mla_moe.ExpertLayer`, told what
this family's is: a float32 softmax over the router's whole width, the
top k of the probabilities renormalised, no selection bias, no shared
expert. Its own:

- **The noising, inside the model** (:func:`noise`). A training step
  sees a clean row x_0 of L tokens and makes its noised copy itself:
  every block n of ``block_length`` tokens draws a time t_n ~ U(0, 1),
  p_n = (1 - eps) t_n + eps (the linear schedule of masked diffusion),
  and every token of the block is replaced by MASK with probability
  p_n, independently. The draws come from a threefry key that is a
  function of the row and ``noise_seed`` alone (:func:`row_key`), so the
  noise is a pure function of the data, whenever the row comes again,
  with no random stream threaded through the step and no host work a
  step. t, p and the tokens' draws live on the grid of millionths
  (``NOISE_GRID``) and **the masking is a comparison of integers**, so
  it is the same bits on a CPU and a TPU, jitted or not (in float32 a
  fused multiply-add moves p by an ulp under ``jit`` and flips a token
  in ten million). Anyone who holds the row and the configuration (the
  plain reference, a comparison of gradients) knows which tokens were
  masked.
- **The doubled sequence**: z = [x_t ; x_0], 2L positions at positions
  [0..L-1, 0..L-1] (a noised token sits where its clean token sits),
  under :class:`~elasticdl_tpu.ops.flash_attention.BlockDiffusion`: a
  noised block sees itself and the clean blocks before it, the clean
  half is block-causal, and no clean query sees a noised key.
- **Qwen3's attention** (:class:`DiffusionAttention`): q over H heads, k
  and v over Hkv, no bias; RMSNorm over each head of q and of k with a
  learned scale the heads share; rotary by halves over the whole head
  (:func:`rope_halves`); query head h reads key/value head h // (H /
  Hkv) in place in the flash kernels.
- **The head over the noised half only**, and training outputs that
  carry the loss's targets and weights: ``{"logits": (B, L, V),
  "targets": x_0, "weights": m / p, "metrics": {...}}``. The loss is in
  place (position i predicts token i of the clean row: no shift), over
  masked tokens, each weighted by 1 / p of its block
  (``ops/losses.py::weighted_in_place_cross_entropy``). The clean half's
  last-layer output feeds nothing and is computed all the same (the
  layer is one call over 2L).

MASK is row ``vocab_size`` of the embedding, one past the vocabulary
held: the head has no output for it (MASK is never a target). In
evaluation the model noises likewise and returns the noised half's
logits alone. The family takes no mesh.
"""

import functools
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.models.mla_moe import (
    ExpertLayer,
    RMSNorm,
    _add_counters,
    _dense,
)
from elasticdl_tpu.models.transformer import _LMHead
from elasticdl_tpu.ops.flash_attention import (
    BlockDiffusion,
    describe_kept as describe_attention_kept,
    describe_tiles as describe_attention_tiles,
    flash_attention,
    log_traced as log_traced_attention,
    remat_policy as attention_remat_policy,
    supports as flash_supports,
)
from elasticdl_tpu.ops.ring_attention import dense_attention

logger = get_logger("diffusion")


@dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 256           # the rows held: targets, head outputs
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # Block diffusion: the block, the schedule's floor, the noise's seed.
    block_length: int = 4
    noise_eps: float = 1e-3
    noise_seed: int = 0
    # The expert layer's fields, under ``ExpertLayer``'s names.
    moe_intermediate_size: int = 32
    shared_intermediate_size: int = 0
    router_width: int = 8
    first_held: int = 0
    n_held: int = 8
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    expert_form: str = "silu_gated"
    scoring: str = "softmax"
    selection_bias: bool = False
    shared_expert: bool = False
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def mask_id(self) -> int:
        return self.vocab_size


def row_key(row, noise_seed: int):
    """The key a row's noise is drawn from: ``fold_in(key(noise_seed),
    sum_i row_i (2 i + 1) mod 2^32)``, threefry. row (L,) int."""
    odd = 2 * jnp.arange(row.shape[0], dtype=jnp.uint32) + 1
    mark = jnp.sum(row.astype(jnp.uint32) * odd, dtype=jnp.uint32)
    return jax.random.fold_in(
        jax.random.key(noise_seed, impl="threefry2x32"), mark)


# t, p and u are whole numbers of 1 / NOISE_GRID: 999 x t < 2^31.
NOISE_GRID = 1_000_000


def noise(tokens, cfg: SdarMoeConfig):
    """tokens (B, L) int -> (masked (B, L) bool, p (B, L) float32: the
    masking probability of every position's block). A pure function of
    each row and the configuration (so a row read again, in a later
    epoch, is noised as before: model_zoo/sdar_moe says why); the
    comparison is of integers."""
    length, block = tokens.shape[1], cfg.block_length
    floor = round(cfg.noise_eps * NOISE_GRID)
    per_mille = NOISE_GRID // 1000
    if length % block or floor % per_mille or not 0 < floor < NOISE_GRID:
        raise ValueError(
            f"a row of {length} tokens in blocks of {block}, noise_eps "
            f"{cfg.noise_eps}: the row is whole blocks and eps whole "
            "thousandths")

    def one(row):
        for_times, for_tokens = jax.random.split(
            row_key(row, cfg.noise_seed))
        times = jax.random.randint(
            for_times, (length // block,), 0, NOISE_GRID, jnp.int32)
        # (1 - eps) t + eps, rounded down to the grid.
        p = jnp.repeat(
            floor + (1000 - floor // per_mille) * times // 1000, block)
        drawn = jax.random.randint(
            for_tokens, (length,), 0, NOISE_GRID, jnp.int32)
        return drawn < p, p.astype(jnp.float32) / NOISE_GRID

    return jax.vmap(one)(tokens)


@functools.lru_cache(maxsize=None)
def log_traced_noising(shape: tuple, cfg: SdarMoeConfig):
    """One static line per traced shape (every trace asks again)."""
    logger.info(
        "diffusion: traced noising of x%s: blocks of %d, linear schedule, "
        "eps %g, mask row %d, loss in place over masked tokens",
        shape, cfg.block_length, cfg.noise_eps, cfg.mask_id,
    )


def rope_halves(x, positions, theta: float):
    """Rotary embedding by halves over the last axis of ``x`` (B, S, H,
    D): with x = (x1, x2) the head's two halves, angle_j = position *
    theta^(-2j/D), (x1 cos - x2 sin, x2 cos + x1 sin). ``positions``
    (S,). float32 inside, ``x``'s dtype out."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


class DiffusionAttention(nn.Module):
    """x (B, 2L, d), the noised half then the clean one."""
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt = cfg.compute_dtype
        h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        group = h // hkv
        s = x.shape[1]
        half = jnp.arange(s // 2)
        positions = jnp.concatenate([half, half])
        q = rope_halves(
            RMSNorm(cfg.rms_eps, dt, name="q_norm")(
                _dense((h, hd), dt, "q")(x)),
            positions, cfg.rope_theta)
        k = rope_halves(
            RMSNorm(cfg.rms_eps, dt, name="k_norm")(
                _dense((hkv, hd), dt, "k")(x)),
            positions, cfg.rope_theta)
        v = _dense((hkv, hd), dt, "v")(x)
        scale = hd ** -0.5
        mask = BlockDiffusion(s // 2, cfg.block_length)
        backend = jax.default_backend()
        said = (f"{h} query heads over {hkv} key/value heads, head size "
                f"{hd}; block-diffusion mask, blocks of {mask.block} over "
                f"halves of {mask.half}")
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
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                scale=scale, mask=mask)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=dt,
            name="out",
        )(o)


class SdarBlock(nn.Module):
    """x += Attn(RMSNorm(x)); x += Experts(RMSNorm(x)). Returns (x, the
    expert layer's counters)."""
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        x = x + DiffusionAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, dt, name="attn_norm")(x))
        h, counters = ExpertLayer(cfg, name="moe")(
            RMSNorm(cfg.rms_eps, dt, name="ffn_norm")(x), routing)
        return x + h, counters


class SdarMoeLM(nn.Module):
    """``features`` = int32 token ids (B, L), the clean row; see the
    module docstring for what training and evaluation return.
    ``routing``: one (B, 2L, k) array of expert ids for every layer in
    order, held in place of the layers' own choices."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, features, training=False, routing=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        tokens = features.astype(jnp.int32)
        length = tokens.shape[1]
        log_traced_noising(tokens.shape, cfg)
        masked, p = noise(tokens, cfg)
        doubled = jnp.concatenate(
            [jnp.where(masked, cfg.mask_id, tokens), tokens], axis=1)
        block_cls = (
            nn.remat(SdarBlock, policy=attention_remat_policy())
            if cfg.remat else SdarBlock
        )
        x = nn.Embed(
            cfg.vocab_size + 1, cfg.hidden_size, dtype=dt, name="token_embed"
        )(doubled)
        counters = {}
        held = iter(routing) if routing is not None else None
        for i in range(cfg.num_layers):
            x, layer_counters = block_cls(cfg, name=f"block_{i}")(
                x, next(held) if held else None)
            counters = _add_counters(counters, layer_counters)
        logits = _LMHead(cfg.vocab_size, dt, name="lm_head")(
            RMSNorm(cfg.rms_eps, dt, name="final_norm")(x[:, :length])
        ).astype(jnp.float32)
        if not training:
            return logits
        counters["diffusion_masked_tokens"] = jnp.sum(
            masked, dtype=jnp.int32)
        return {
            "logits": logits, "targets": tokens,
            "weights": jnp.where(masked, 1.0 / p, 0.0),
            "metrics": counters,
        }
