"""Decoder-only transformer LM — the multi-axis parallelism flagship.

Net-new capability relative to the reference (SURVEY.md §5: no long-context
or model parallelism exists in ElasticDL; its models are MLPs/CNNs/recsys),
built TPU-first to exercise every mesh axis the framework supports:

- ``dp``: batch dim sharded (the reference's only parallelism, worker
  data-parallel via PS push/pull, here XLA gradient psum over ICI),
- ``sp``: sequence dim sharded; attention runs as an exact ppermute ring
  (``ops/ring_attention.py``) so context length scales past one chip's HBM,
- ``tp``: attention heads and MLP hidden dim sharded Megatron-style —
  column-parallel in, row-parallel out, one psum per block, expressed as
  GSPMD sharding constraints instead of hand-written collectives,
- ``ep``: MoE expert dim sharded; dense one-hot dispatch whose expert
  einsum partitions over ``ep`` (each device computes only its experts,
  XLA inserts the combine psum).

Layout is declarative: ``transformer_sharding_rules()`` returns regex
path → PartitionSpec pairs consumed by ``parallel/rules.py``; the same
module runs unsharded on one chip (mesh=None) for the single-chip entry.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.ops.flash_attention import (
    describe_kept as describe_attention_kept,
    describe_tiles as describe_attention_tiles,
    flash_attention,
    log_traced as log_traced_attention,
    remat_policy as attention_remat_policy,
    supports as flash_supports,
)
from elasticdl_tpu.ops.ring_attention import dense_attention, ring_attention


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 512
    dropout_rate: float = 0.0
    moe_experts: int = 0        # 0 = dense MLP in every block
    moe_top_k: int = 1          # experts combined per token (renormed)
    moe_every: int = 2          # MoE replaces the MLP in every k-th block
    # "dense": exact one-hot einsum dispatch (FLOPs scale with E);
    # "scatter": capacity-based Switch/GShard dispatch (FLOPs ~constant
    # in E, tokens over capacity dropped, all-to-all under ep) — see
    # the MoE module docstring.
    moe_dispatch: str = "dense"
    moe_capacity_factor: float = 1.25
    # Rematerialize each block on backward (jax.checkpoint): trades
    # ~1/3 more FLOPs for O(n_layers) less activation HBM — the lever
    # for deep/long-context configs (HBM is the usual TPU bottleneck).
    # A block that ran the Pallas attention kernel keeps its o and
    # logsumexp (flash_attention.remat_policy) and never runs the
    # forward kernel twice.
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Fused head+loss mode: during TRAINING the model returns
    # (hidden, lm_head kernel, bias) instead of materializing the
    # (B, S, vocab) logits, and ops/losses.py
    # fused_next_token_cross_entropy computes per-chunk logits inside a
    # rematerialized scan. This is the MEMORY lever for configs whose
    # logits don't fit (very large vocab / long sequence / big batch:
    # full f32 logits are B*S*V*4 bytes — 1 GB at B8/S1024/V32k). It is
    # NOT a throughput win at the bench flagship size: measured ~4%
    # SLOWER there (paired duel, v5e) because the chunk scan serializes
    # the head matmul; the bench keeps the materialized path. Eval/
    # decode always return logits; the param tree is unchanged either
    # way.
    fused_head: bool = False

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def transformer_sharding_rules() -> Tuple[Tuple[str, P], ...]:
    """Regex path → PartitionSpec, in priority order; first match wins
    and ``regex_param_rule`` drops per-dim any axis the mesh lacks, so
    these run unchanged on dp-only, dp/sp/tp, or dp/ep meshes."""
    return (
        # Attention: column-parallel QKV, row-parallel out (heads on tp).
        (r"(query|key|value)/kernel", P(None, "tp", None)),
        (r"(query|key|value)/bias", P("tp", None)),
        (r"attn/out/kernel", P("tp", None, None)),
        # Dense MLP: Megatron column→row.
        (r"mlp/wi/kernel", P(None, "tp")),
        (r"mlp/wi/bias", P("tp")),
        (r"mlp/wo/kernel", P("tp", None)),
        # MoE experts: expert dim on ep, hidden dim on tp.
        (r"moe/wi", P("ep", None, "tp")),
        (r"moe/wo", P("ep", "tp", None)),
        # Embeddings / head: vocab over tp.
        (r"token_embed/embedding", P("tp", None)),
        (r"lm_head/kernel", P(None, "tp")),
        (r"lm_head/bias", P("tp")),
    )


class _Constrain:
    """Activation sharding-constraint helper bound to an optional mesh."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __call__(self, x, *axes):
        if self.mesh is None:
            return x
        shape = self.mesh.shape
        fixed = []
        for dim, a in enumerate(axes[: x.ndim]):
            ok = (
                a is not None
                and a in shape
                and x.shape[dim] % shape[a] == 0
            )
            fixed.append(a if ok else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*fixed))
        )


class SelfAttention(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.cfg
        wsc = _Constrain(self.mesh)
        proj = lambda name: nn.DenseGeneral(
            (cfg.n_heads, cfg.head_dim), dtype=cfg.compute_dtype, name=name
        )
        q = wsc(proj("query")(x), "dp", "sp", "tp", None)
        k = wsc(proj("key")(x), "dp", "sp", "tp", None)
        v = wsc(proj("value")(x), "dp", "sp", "tp", None)
        scale = cfg.head_dim ** -0.5
        if self.decode:
            return self._decode_step(q, k, v, scale)
        backend = jax.default_backend()
        if self.mesh is not None:
            o = ring_attention(q, k, v, self.mesh, causal=True, scale=scale)
        elif backend == "tpu" and flash_supports(q.shape):
            # Single-chip TPU hot path: fused Pallas kernel (O(S) HBM;
            # the causal walk stops at the diagonal, by sub-tiles inside
            # one grid tile, by grid tiles beyond it: the log line says
            # how many) instead of the O(S^2) dense scores.
            log_traced_attention(
                "pallas flash kernel",
                "tpu backend, shape tiles the kernel blocks; head sizes "
                f"q/k {q.shape[-1]}, v {v.shape[-1]}; "
                + describe_attention_tiles(q.shape[1])
                + ("; " + describe_attention_kept(v)
                   if cfg.remat else ""), q.shape,
            )
            o = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            log_traced_attention(
                "dense reference",
                f"backend is {backend}" if backend != "tpu"
                else "shape does not tile the kernel blocks", q.shape,
            )
            o = dense_attention(q, k, v, causal=True, scale=scale)
        o = nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.compute_dtype, name="out"
        )(o)
        return wsc(o, "dp", "sp", None)

    def _decode_step(self, q, k, v, scale):
        """KV-cache incremental decoding: one new token per call. The
        cache holds (B, max_len, H, D) K/V buffers (static shapes — the
        position index is the only dynamic piece, XLA-friendly), new
        entries land via dynamic_update_slice, and attention masks out
        positions beyond the cache fill."""
        cfg = self.cfg
        b, t, h, d = q.shape
        cache_k = self.variable(
            "cache", "k",
            lambda: jnp.zeros((b, cfg.max_len, h, d), cfg.compute_dtype),
        )
        cache_v = self.variable(
            "cache", "v",
            lambda: jnp.zeros((b, cfg.max_len, h, d), cfg.compute_dtype),
        )
        cache_index = self.variable(
            "cache", "index", lambda: jnp.zeros((), jnp.int32)
        )
        idx = cache_index.value
        cache_k.value = jax.lax.dynamic_update_slice(
            cache_k.value, k.astype(cache_k.value.dtype), (0, idx, 0, 0)
        )
        cache_v.value = jax.lax.dynamic_update_slice(
            cache_v.value, v.astype(cache_v.value.dtype), (0, idx, 0, 0)
        )
        cache_index.value = idx + t
        # Shared attention math with the query-position offset: causality
        # with qpos = idx+i also masks every still-empty cache slot
        # (those sit beyond the newest query's position).
        o = dense_attention(
            q, cache_k.value, cache_v.value, causal=True, scale=scale,
            q_offset=idx,
        )
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.compute_dtype,
            name="out",
        )(o)


class Mlp(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.cfg
        wsc = _Constrain(self.mesh)
        h = nn.Dense(cfg.d_ff, dtype=cfg.compute_dtype, name="wi")(x)
        h = wsc(nn.gelu(h), "dp", "sp", "tp")
        o = nn.Dense(cfg.d_model, dtype=cfg.compute_dtype, name="wo")(h)
        return wsc(o, "dp", "sp", None)


class MoE(nn.Module):
    """Routed mixture-of-experts: dense one-hot OR capacity dispatch.

    ``cfg.moe_dispatch``:

    - ``"dense"`` — the expert einsum carries the expert dim so GSPMD
      partitions it over ``ep``: every device computes its local
      experts for ALL tokens and the weighted combine psums over
      ``ep``. Exact (no token ever dropped) and collective-light, but
      expert FLOPs scale with E — the right choice for few experts or
      correctness baselines (the dryrun's ep4 == ep1 equivalence runs
      this path).
    - ``"scatter"`` — capacity-based dispatch (Switch/GShard shape):
      each token-choice gets a rank among the tokens routed to its
      expert (one-hot cumsum); tokens with rank < capacity
      C = ceil(k·T/E · capacity_factor) scatter into an (E, C, D)
      buffer, the expert FFN runs batched over (E, C) — FLOPs
      ~constant in E — and results gather back gate-weighted.
      Overflowing tokens are DROPPED (contribute zero), the standard
      capacity trade; with C >= T it is drop-free and numerically
      equals dense dispatch (tested). Under an ``ep`` mesh axis the
      (E, C, D) buffer shards over ``ep`` while tokens shard over
      ``dp``, so GSPMD lowers the scatter/gather to the all-to-all
      exchange this mode exists for.
    """

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.cfg
        e, dm, dff = cfg.moe_experts, cfg.d_model, cfg.d_ff
        wsc = _Constrain(self.mesh)
        gates = nn.Dense(e, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )
        gates = jax.nn.softmax(gates, axis=-1)            # (B,S,E)
        # Top-k routing. k=1 is the classic switch: the RAW gate value
        # weights the expert (renormalizing to 1 would kill the router's
        # gradient). k>1 renormalizes the kept gates to sum to 1
        # (gradients flow through the relative weights).
        k = min(cfg.moe_top_k, e)
        top_vals, top_idx = jax.lax.top_k(gates, k)
        if k > 1:
            top_vals = top_vals / jnp.maximum(
                top_vals.sum(axis=-1, keepdims=True), 1e-9
            )

        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (e, dm, dff), jnp.float32
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (e, dff, dm), jnp.float32
        )
        xc = x.astype(cfg.compute_dtype)
        if cfg.moe_dispatch not in ("dense", "scatter"):
            # A typo must not silently buy the E-times-more-expensive
            # dense einsum.
            raise ValueError(
                f"moe_dispatch must be 'dense' or 'scatter', got "
                f"{cfg.moe_dispatch!r}"
            )
        # KV-cache decode steps see t = B*1 tokens, so the scatter
        # capacity ceil(B*k/E*cf) is ~1 and any routing collision would
        # silently zero a token's expert output at inference. The dense
        # einsum at t=B is cheap and drop-free, so single-token decode
        # steps take it; the gate is the STATIC sequence length, so the
        # prefill pass (S = prompt length, ample capacity) keeps the
        # scatter path's E-independent FLOPs. Param tree is identical
        # either way.
        decode_step = self.decode and x.shape[1] == 1
        if cfg.moe_dispatch == "scatter" and not decode_step:
            return self._scatter_dispatch(
                xc, top_idx, top_vals, wi, wo, wsc
            )

        combine = (
            jax.nn.one_hot(top_idx, e, dtype=gates.dtype)
            * top_vals[..., None]
        ).sum(axis=-2)                                     # (B,S,E)
        combine = wsc(combine, "dp", "sp", "ep")
        h = jnp.einsum(
            "bsd,edf->besf", xc, wi.astype(cfg.compute_dtype)
        )
        h = wsc(nn.gelu(h), "dp", "ep", "sp", "tp")
        y = jnp.einsum(
            "besf,efd->besd", h, wo.astype(cfg.compute_dtype)
        )
        y = wsc(y, "dp", "ep", "sp", None)
        out = jnp.einsum("besd,bse->bsd", y, combine.astype(y.dtype))
        return wsc(out, "dp", "sp", None)

    def _scatter_dispatch(self, xc, top_idx, top_vals, wi, wo, wsc):
        cfg = self.cfg
        e = cfg.moe_experts
        b, s, dm = xc.shape
        k = top_idx.shape[-1]
        t = b * s
        cap = int(math.ceil(t * k / e * cfg.moe_capacity_factor))
        cap = max(min(cap, t), 1)

        tokens = xc.reshape(t, dm)
        idx = top_idx.reshape(t, k)                 # expert per choice
        vals = top_vals.reshape(t, k).astype(xc.dtype)
        # Rank of each (token, choice) within its expert, counted in
        # token-major order across all k choices: one-hot cumsum — the
        # standard XLA-friendly position_in_expert (no sort, static
        # shapes throughout).
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # (T,k,E)
        flat_oh = onehot.reshape(t * k, e)
        ranks = jnp.cumsum(flat_oh, axis=0) - 1           # (T*k,E)
        pos = (ranks * flat_oh).sum(-1).reshape(t, k)     # (T,k)
        keep = (pos < cap)                                # (T,k)
        safe_pos = jnp.where(keep, pos, 0)

        # Dispatch: (E, C, D) buffer; dropped choices scatter a zero
        # row at slot 0 of their expert via add-of-zero (scatter-add
        # keeps the op deterministic under duplicates).
        buf = jnp.zeros((e, cap, dm), xc.dtype)
        contrib = tokens[:, None, :] * keep[..., None].astype(xc.dtype)
        buf = buf.at[idx, safe_pos].add(contrib)
        buf = wsc(buf, "ep", None, None)

        h = jnp.einsum(
            "ecd,edf->ecf", buf, wi.astype(xc.dtype)
        )
        h = wsc(nn.gelu(h), "ep", None, "tp")
        y = jnp.einsum(
            "ecf,efd->ecd", h, wo.astype(xc.dtype)
        )
        y = wsc(y, "ep", None, None)

        # Combine: gather each choice's row back, gate-weight, zero the
        # dropped ones.
        rows = y[idx, safe_pos]                           # (T,k,D)
        rows = rows * (vals * keep.astype(xc.dtype))[..., None]
        out = rows.sum(axis=1).reshape(b, s, dm)
        return wsc(out, "dp", "sp", None)


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    use_moe: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln1")(x)
        h = SelfAttention(
            cfg, self.mesh, decode=self.decode, name="attn"
        )(h, training)
        if cfg.dropout_rate and training:
            h = nn.Dropout(cfg.dropout_rate, deterministic=False)(h)
        x = x + h
        h = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln2")(x)
        if self.use_moe:
            h = MoE(cfg, self.mesh, decode=self.decode, name="moe")(
                h, training
            )
        else:
            h = Mlp(cfg, self.mesh, name="mlp")(h, training)
        if cfg.dropout_rate and training:
            h = nn.Dropout(cfg.dropout_rate, deterministic=False)(h)
        return x + h


class _LMHead(nn.Module):
    """The output projection with an escape hatch: ``fused=True``
    returns (hidden, kernel, bias) for the chunked fused loss instead
    of computing logits. Param names/init match ``nn.Dense`` exactly
    (lm_head/kernel, lm_head/bias, f32 params, lecun-normal) so
    checkpoints and sharding rules are identical either way."""

    vocab_size: int
    dtype: jnp.dtype
    fused: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.vocab_size), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(),
            (self.vocab_size,), jnp.float32,
        )
        if self.fused:
            return x, kernel.astype(self.dtype), bias
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
        )
        return y + bias.astype(y.dtype)


class TransformerLM(nn.Module):
    """``features`` = int32 token ids (B, S).

    Output: f32 logits (B, S, V) — EXCEPT when ``cfg.fused_head`` and
    ``training=True`` (not decode), where it returns the fused-loss
    triple ``(hidden bf16 (B,S,D), lm_head kernel, bias)`` for
    ``ops.fused_next_token_cross_entropy``. Eval/decode always get
    logits."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, features, training=False):
        cfg = self.cfg
        wsc = _Constrain(self.mesh)
        tokens = features.astype(jnp.int32)
        b, s = tokens.shape
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype,
            name="token_embed",
        )(tokens)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (cfg.max_len, cfg.d_model),
            jnp.float32,
        )
        if self.decode:
            # Incremental positions continue from the cache fill.
            pos_index = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = pos_index.value
            pos_slice = jax.lax.dynamic_slice(
                pos, (start, 0), (s, cfg.d_model)
            )
            pos_index.value = start + s
        else:
            pos_slice = pos[:s]
        x = x + pos_slice.astype(cfg.compute_dtype)[None]
        x = wsc(x, "dp", "sp", None)
        # static_argnums counts self: (2,) marks ``training`` static so
        # dropout's Python bool branch still works under remat. Decode
        # (inference) never remats.
        block_cls = (
            nn.remat(Block, static_argnums=(2,),
                     policy=attention_remat_policy())
            if cfg.remat and not self.decode else Block
        )
        for i in range(cfg.n_layers):
            use_moe = (
                cfg.moe_experts > 0 and (i + 1) % cfg.moe_every == 0
            )
            x = block_cls(
                cfg, self.mesh, use_moe=use_moe, decode=self.decode,
                name=f"block_{i}",
            )(x, training)
        x = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln_f")(x)
        head = _LMHead(
            cfg.vocab_size, cfg.compute_dtype,
            fused=(cfg.fused_head and training and not self.decode),
            name="lm_head",
        )
        out = head(x)
        if isinstance(out, tuple):
            hidden, kernel, bias = out
            return (
                wsc(hidden, "dp", "sp", None),
                wsc(kernel, None, "tp"),
                wsc(bias, "tp"),
            )
        return wsc(out.astype(jnp.float32), "dp", "sp", "tp")


import functools as _functools


@_functools.lru_cache(maxsize=32)
def _generate_fn(cfg: TransformerConfig, max_new_tokens: int,
                 temperature: float):
    """Compiled generation driver, cached per (cfg, length, temperature)
    so repeated generate() calls don't retrace."""
    model = TransformerLM(cfg, mesh=None, decode=True)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1
        ).astype(jnp.int32)

    @jax.jit
    def run(params, prompt, rng):
        logits, aux = model.apply(
            {"params": params}, prompt, training=False,
            mutable=["cache"],
        )
        rng, key = jax.random.split(rng)
        tok0 = sample(logits[:, -1], key)

        def step(carry, _):
            cache, tok, rng = carry
            logits, aux = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                training=False, mutable=["cache"],
            )
            rng, key = jax.random.split(rng)
            next_tok = sample(logits[:, -1], key)
            return (aux["cache"], next_tok, rng), next_tok

        _, toks = jax.lax.scan(
            step, (aux["cache"], tok0, rng), None,
            length=max_new_tokens - 1,
        )
        return jnp.concatenate(
            [tok0[:, None], jnp.swapaxes(toks, 0, 1)], axis=1
        )

    return run


def generate(
    cfg: TransformerConfig,
    params,
    prompt,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng=None,
):
    """Autoregressive sampling with the KV cache: prompt prefills in one
    pass, then one token per ``lax.scan`` step — static shapes
    throughout (the cache is (B, max_len, H, D); the fill index is the
    only dynamic piece). temperature 0 = greedy.

    Returns (B, max_new_tokens) int32 tokens.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    total = prompt.shape[1] + max_new_tokens
    if total > cfg.max_len:
        # XLA clamps out-of-range dynamic slices silently — overflowing
        # the cache would return corrupted tokens, not an error.
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds max_len "
            f"{cfg.max_len}"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_fn(cfg, max_new_tokens, float(temperature))(
        params, prompt, rng
    )
