"""Ring attention: exact attention over a sequence-parallel mesh axis.

Long-context support is net-new relative to the reference (SURVEY.md §5
"Long-context / sequence parallelism: absent" — ElasticDL scales data and
sparse state only), designed TPU-first: the sequence dimension is sharded
over the ``sp`` mesh axis, each device holds one query block, and key/value
blocks rotate around the ring with ``jax.lax.ppermute`` over ICI while a
blockwise online softmax (flash-attention style running max / sum / output
accumulators) keeps the math exact. Compute of block t overlaps the
transfer of block t+1 — XLA schedules the ppermute DMA asynchronously —
so the ring rides ICI bandwidth instead of materializing the full
``S × S`` score matrix on any chip.

The public entry ``ring_attention`` wraps the per-device body in
``jax.shard_map``; ``dense_attention`` is the mathematically identical
single-device reference used by small models and by the tests.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _to_bh(x):
    """(B, S, H, D) -> (B*H, S, D) — the layout the Pallas kernels use."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    """(B*H, S, D) -> (B, S, H, D)."""
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def dense_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, q_offset=0, mask=None):
    """Plain softmax attention. Shapes: q = (B, Sq, H, D), k/v =
    (B, Sk, H, D) with Sk >= Sq allowed (KV-cache decoding: ``q_offset``
    is q[:,0]'s global position, so causality masks the right keys —
    including still-empty cache slots beyond the fill).

    Reference semantics for ``ring_attention`` (used when the mesh has no
    sequence axis, and by tests). f32 softmax accumulation regardless of
    input dtype — bf16 inputs stay bf16 through the matmuls (MXU) but the
    normalization happens in f32. ``mask``: a
    ``flash_attention.BlockDiffusion`` over the whole sequence, in place
    of ``causal``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        positions = jnp.arange(q.shape[1])
        s = jnp.where(
            mask.visible(positions[:, None], positions[None, :]), s,
            _NEG_INF)
    elif causal:
        q_len, k_len = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(q_len)[:, None]
        kpos = jnp.arange(k_len)[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _block_update(carry, q, k, v, qpos, kpos, causal, scale):
    """One online-softmax accumulation step against a single K/V block.

    carry: m (B,H,Sq) running max, l (B,H,Sq) running denominator,
    o (B,Sq,H,D) running numerator — all f32.
    """
    m, l, o = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp(-inf - -inf) would give 1 for fully-masked rows; zero the masked
    # entries explicitly instead of trusting the subtraction.
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v
    ).astype(jnp.float32)
    return m_new, l, o


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, scale,
                          return_lse: bool = False):
    """Per-device body under shard_map: q stays, k/v rotate the ring."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, q_len, h, d = q.shape
    k_len = k.shape[1]
    qpos = idx * q_len + jnp.arange(q_len)

    m0 = jnp.full((b, h, q_len), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, q_len), jnp.float32)
    o0 = jnp.zeros((b, q_len, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        m, l, o, k, v = carry
        # After t forward rotations, this device holds block (idx - t) % n.
        kpos = ((idx - t) % n) * k_len + jnp.arange(k_len)
        m, l, o = _block_update((m, l, o), q, k, v, qpos, kpos, causal, scale)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return (m, l, o, k, v), None

    (m, l, o, _, _), _ = jax.lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (none in causal LM) stay 0
    out = o / l.transpose(0, 2, 1)[..., None]
    if return_lse:
        lse = (m + jnp.log(l))[..., None]        # (b, h, s, 1)
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


def _make_ring_local_jnp(axis_name: str, causal: bool, scale):
    """jnp ring forward + the fused ring backward (shared with the
    Pallas path's math, jnp flavor): one reverse ring from the saved
    logsumexp instead of AD re-walking the forward scan."""

    @jax.custom_vjp
    def ring(q, k, v):
        return _ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale
        )

    def fwd(q, k, v):
        out, lse = _ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale,
            return_lse=True,
        )
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _ring_local_bwd(
            q, k, v, out, lse, g, axis_name, causal, scale
        )

    ring.defvjp(fwd, bwd)
    return ring


def _ring_local_pallas_fwd(q, k, v, axis_name: str, causal: bool,
                           scale, interpret: bool):
    """Pallas-fused ring forward: each arriving K/V chunk folds into the
    running flash accumulators via one fused kernel call
    (ops/flash_attention.flash_chunk_update) instead of XLA einsums —
    scores exist only as on-chip tiles while chunks rotate over ICI.

    Returns (out, lse) — the logsumexp residual feeds the fused ring
    backward."""
    from elasticdl_tpu.ops.flash_attention import flash_chunk_update

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    to_bh = _to_bh
    qb = to_bh(q)
    m0 = jnp.full((b * h, s_loc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b * h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b * h, s_loc, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_off = idx * s_loc

    def step(carry, t):
        m, l, acc, kc, vc = carry
        k_off = ((idx - t) % n) * s_loc
        m, l, acc = flash_chunk_update(
            qb, to_bh(kc), to_bh(vc), m, l, acc, q_off, k_off,
            causal=causal, scale=scale, interpret=interpret,
        )
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), None

    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n)
    )
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe
    out = out.reshape(b, h, s_loc, d).transpose(0, 2, 1, 3)
    lse = (m + jnp.log(l_safe)).reshape(b, h, s_loc, 1)
    return out.astype(q.dtype), lse


def _ring_local_bwd_pallas(q, k, v, o, lse, do, axis_name: str,
                           causal: bool, scale, interpret: bool):
    """Fused ring backward with the per-chunk Pallas kernel
    (flash_chunk_grads: ONE kernel a chunk pairing gives dq, dk and dv;
    dq's parts are summed over the chunks here): score tiles never
    leave VMEM. Same rotation schedule as the jnp version."""
    from elasticdl_tpu.ops.flash_attention import flash_chunk_grads

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    to_bh = _to_bh
    from_bh = lambda x: _from_bh(x, b, h)
    qb, dob = to_bh(q), to_bh(do)
    ob = to_bh(o)
    lse_b = lse.reshape(b * h, s_loc, 1)
    delta = (
        dob.astype(jnp.float32) * ob.astype(jnp.float32)
    ).sum(axis=-1, keepdims=True)
    q_off = idx * s_loc
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        dq, kc, vc, dkc, dvc = carry
        kc, vc = jax.lax.cond(
            t > 0,
            lambda kv: (
                jax.lax.ppermute(kv[0], axis_name, perm),
                jax.lax.ppermute(kv[1], axis_name, perm),
            ),
            lambda kv: kv,
            (kc, vc),
        )
        k_off = ((idx - t) % n) * s_loc
        dq_p, dk_c, dv_c = flash_chunk_grads(
            qb, to_bh(kc), to_bh(vc), dob, lse_b, delta, q_off, k_off,
            causal=causal, scale=scale, interpret=interpret,
        )
        dq = dq + dq_p
        dkc = jax.lax.ppermute(dkc + dk_c, axis_name, perm)
        dvc = jax.lax.ppermute(dvc + dv_c, axis_name, perm)
        return (dq, kc, vc, dkc, dvc), None

    zeros = jnp.zeros((b * h, s_loc, d), jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (zeros, k, v, zeros, zeros), jnp.arange(n)
    )
    return (
        from_bh(dq).astype(q.dtype),
        from_bh(dk).astype(k.dtype),
        from_bh(dv).astype(v.dtype),
    )


def _ring_local_bwd(q, k, v, o, lse, do, axis_name: str, causal: bool,
                    scale):
    """Fused ring backward from the saved logsumexp: ONE reverse ring
    instead of recompute-forward + AD (~3× less work). The local q
    block (with o, do, lse, Δ) stays put; each (K, V, dK, dV) chunk
    group rotates the full ring, accumulating every device's
    contribution, and arrives home after n steps:

        P = exp(QKᵀ·scale − lse);  Δ = rowsum(dO ∘ O)
        dS = P ∘ (dO·Vᵀ − Δ);  dQ += dS·K·scale
        dK += dSᵀ·Q·scale;     dV += Pᵀ·dO
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = (dof * of).sum(axis=-1)                     # (b, s, h)
    qpos = idx * s_loc + jnp.arange(s_loc)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        dq, kc, vc, dkc, dvc = carry
        # K/V rotate at step START (skipped at t=0), so the final
        # iteration doesn't pay two dead full-chunk ICI transfers; the
        # dK/dV accumulators rotate at the END of every step and land
        # home after n rotations.
        kc, vc = jax.lax.cond(
            t > 0,
            lambda kv: (
                jax.lax.ppermute(kv[0], axis_name, perm),
                jax.lax.ppermute(kv[1], axis_name, perm),
            ),
            lambda kv: kv,
            (kc, vc),
        )
        c = (idx - t) % n
        kpos = c * s_loc + jnp.arange(s_loc)
        kf = kc.astype(jnp.float32)
        vf = vc.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
        # lse: (b, h, s, 1) -> align as (b, h, q, 1)
        p = jnp.exp(s - lse)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            p = jnp.where(mask[None, None], p, 0.0)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
        ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
        dkc = dkc + jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        dvc = dvc + jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        return (dq, kc, vc, dkc, dvc), None

    zeros = jnp.zeros((b, s_loc, h, d), jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (zeros, k, v, zeros, zeros), jnp.arange(n)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _make_ring_local_pallas(axis_name: str, causal: bool, scale,
                            interpret: bool):
    """Pallas-fused forward + fused ring backward (from the saved
    logsumexp — no forward recompute)."""

    @jax.custom_vjp
    def ring(q, k, v):
        out, _ = _ring_local_pallas_fwd(
            q, k, v, axis_name, causal, scale, interpret
        )
        return out

    def fwd(q, k, v):
        out, lse = _ring_local_pallas_fwd(
            q, k, v, axis_name, causal, scale, interpret
        )
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _ring_local_bwd_pallas(
            q, k, v, out, lse, g, axis_name, causal, scale, interpret
        )

    ring.defvjp(fwd, bwd)
    return ring


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    sp_axis: str = "sp",
    dp_axis: Optional[str] = "dp",
    tp_axis: Optional[str] = "tp",
    causal: bool = True,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Exact attention with the sequence dim sharded over ``sp_axis``.

    q, k, v: (B, S, H, D) global shapes; B may be sharded over ``dp_axis``
    and H over ``tp_axis`` (both optional — axes absent from the mesh are
    treated as replicated). The ring communicates only over ``sp_axis``.

    ``use_pallas`` (default: auto — on for the TPU backend when the
    local block tiles by the kernel blocks) fuses each chunk update into
    one Pallas kernel call; backward is the fused reverse ring from the
    saved logsumexp (no forward recompute).

    A mesh with no usable ``sp`` axis (dp-only, dp×tp) needs no ring:
    each device then runs the single-chip flash kernel on its own
    (batch, head) shard under ``shard_map``, by the same gate. The
    dense reference remains for the CPU backend and for shapes the
    kernel does not tile; which one was traced is logged.
    """
    from elasticdl_tpu.ops import flash_attention as flash

    if scale is None:
        scale = q.shape[-1] ** -0.5
    axes = set(mesh.axis_names)
    b, s, h, d = q.shape

    def usable(axis, dim):
        # Axes the mesh lacks or that do not divide degrade to
        # replicated (same policy as rules.fit_spec).
        return (
            axis if axis and axis in axes and dim % mesh.shape[axis] == 0
            else None
        )

    def size(axis):
        return mesh.shape[axis] if axis else 1

    dp, tp = usable(dp_axis, b), usable(tp_axis, h)
    # The ring needs equal sequence blocks.
    ring = (
        sp_axis in axes
        and mesh.shape[sp_axis] > 1
        and s % mesh.shape[sp_axis] == 0
    )
    s_loc = s // mesh.shape[sp_axis] if ring else s
    local = (b // size(dp), s_loc, h // size(tp), d)
    if use_pallas is None:
        # Same tiling gate as single-chip flash: the local block must
        # tile by the clamped kernel blocks.
        backend = jax.default_backend()
        use_pallas = backend == "tpu" and flash.supports(local)
        why = (
            f"{backend} backend, local block {local} tiles"
            if use_pallas else
            f"backend is {backend}" if backend != "tpu" else
            f"local block {local} does not tile the kernel blocks"
        )
    else:
        why = f"use_pallas={use_pallas} passed by the caller"
    implementation = (
        ("pallas ring" if use_pallas else "jnp ring") if ring
        else "pallas flash kernel per shard" if use_pallas
        else f"dense reference (no usable {sp_axis} axis)"
    )
    mesh_str = "x".join(f"{a}{mesh.shape[a]}" for a in mesh.axis_names)
    if use_pallas:
        why += "; " + flash.describe_tiles(
            s_loc, causal=causal, traced_offsets=ring
        )
    flash.log_traced(f"{implementation} on {mesh_str}", why, q.shape)
    if not ring and not use_pallas:
        return dense_attention(q, k, v, causal=causal, scale=scale)
    if not ring:
        def body(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=interpret
            )
    elif use_pallas:
        body = _make_ring_local_pallas(
            sp_axis, causal, float(scale), interpret
        )
    else:
        body = _make_ring_local_jnp(sp_axis, causal, float(scale))
    spec = P(dp, sp_axis if ring else None, tp, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
