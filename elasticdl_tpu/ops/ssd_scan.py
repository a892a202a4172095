"""Mamba-2's selective state-space scan in chunks (state-space duality),
forward and backward as Pallas kernels.

For every head h (its group's B and C), with ``a_t = dt_t A`` (A < 0):

    h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T        (a P x N state)
    y_t = h_t C_t + D x_t

Step by step this is S sequential rank-one updates. In chunks of Q
positions (Dao and Gu 2024, "Transformers are SSMs", section 6), with
``s`` the running sum of ``a`` inside a chunk:

- inside a chunk ``Y = ((C B^T) o L)(dt X)`` with ``L_ij = exp(s_i -
  s_j)`` for i >= j, else 0: matrix products, quadratic in Q only;
- the chunk's own state ``S_c = sum_j exp(s_Q - s_j) dt_j x_j B_j^T``;
- across chunks ``h_c = exp(s_Q) h_{c-1} + S_c``;
- what the chunks before add: ``y_i += exp(s_i) h_{c-1} C_i``.

One kernel does all four: the grid is (batch, group, chunk) with the
chunk axis sequential, and the state of the group's heads stays in VMEM
scratch from one chunk to the next, so neither L nor a chunk's state is
ever written to HBM in the forward pass (L for 16,384 tokens would be
537 MB a layer in float32). The forward rule also writes the state
every chunk STARTED from, (B, S/Q, H, P, N) float32, which the backward
kernel reads.

The backward pass is a second kernel, written and not derived: the same
grid walked from the last chunk to the first, carrying the gradient of
the state in scratch, recomputing C B^T, L and dt X in VMEM. With
``dM = dY (dt X)^T`` per head:

    d(dt X)_j = sum_i M_ij dy_i + w_j (B_j . dh),   w_j = exp(s_Q - s_j)
    dC_i = sum_j (sum_heads dM o L)_ij B_j + exp(s_i) dy_i h_{c-1}
    dB_j = sum_i (sum_heads dM o L)_ij C_i + w_j (dt x)_j dh
    ds_i += rowsum_i(dM o M) + dy_i . y_off_i
    ds_j -= colsum_j(dM o M) + w_j (dt x)_j dh B_j
    ds_Q += sum_j (that last term) + exp(s_Q) <dh, h_{c-1}>
    dh_{c-1} = exp(s_Q) dh + sum_i exp(s_i) dy_i C_i^T

``ds`` turns into ``da`` by a running sum from the chunk's end (XLA, on
(B, S, H) float32), so nothing is ever summed over more than a chunk:
the whole-sequence identity ``d cum_i = dy_i . y_i - d(dt x)_i . (dt
x)_i`` cancels over thousands of positions and is not used.

Numerics: matrix products in x's type with float32 accumulation; the
decays (``s``, every ``exp``), the states and their gradients float32.

Layouts. x and y enter as (B, S, H*P): a group's heads are a lane-dense
(Q, hg*P) block. Per-head scalars of a position (s, dt) are needed as
columns (Q, 1) and, for L, s as a row (1, Q). The columns are picked in
the kernel out of (Q, H) blocks of s and dt as they lie, (B, S, H); the
rows come from one transpose of s, (B, G, hg, S). The backward kernel
writes ds and ddt a group at a time as (B, G, S, 2*hg), whose short last
axis is padded in HBM (16 to 128 lanes at the published sizes).

Off the TPU the same kernels run in the Pallas interpreter, so the CPU
tests run the code the chip runs.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("ssd")

DEFAULT_CHUNK = 128
# The kernels' custom calls bear this scope's name on the device
# trace's ``XLA Ops`` lane (``ssd.N``), whatever the caller's scopes.
SCOPE = "ssd"
BACKWARD = "kernel"
_F32 = jnp.float32


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, ((contract[0], contract[1]), ((), ())),
        preferred_element_type=_F32,
    )


def _matmul(a, b):          # (m, k) (k, n)
    return _dot(a, b, ((1,), (0,)))


def _matmul_nt(a, b):       # (m, k) (n, k) -> (m, n)
    return _dot(a, b, ((1,), (1,)))


def _matmul_tn(a, b):       # (k, m) (k, n) -> (m, n)
    return _dot(a, b, ((0,), (0,)))


def _column(block, lane, k):
    """Column ``k`` of a (Q, W) float32 block as (Q, 1): a select and a
    lane reduction, which every Mosaic takes, where a one-lane slice at
    an offset may not lower."""
    return jnp.sum(jnp.where(lane == k, block, 0.0), axis=1, keepdims=True)


def _head_terms(s_blk, dt_blk, lane, rows_ref, head, k, lower):
    """(s as a column, dt as a column, L) of head ``k`` of the group,
    which is head ``head`` of all."""
    s_col = _column(s_blk, lane, head)
    dt_col = _column(dt_blk, lane, head)
    s_row = rows_ref[0, 0, k:k + 1, :]
    # i >= j: s_i - s_j <= 0 (a < 0); the other half is masked, and
    # clamped first so that no exp overflows there.
    decay = jnp.where(
        lower, jnp.exp(jnp.minimum(s_col - s_row, 0.0)), 0.0
    )
    return s_col, dt_col, decay


def _lower_triangle(q):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, b_ref, c_ref, s_ref, dt_ref, rows_ref, d_ref, y_ref,
                hprev_ref, h_acc, *, hg, p):
    """One (batch, group, chunk) step; chunks in order, the group's
    states (hg, P, N) carried in ``h_acc``; ``hprev_ref`` takes the
    state this chunk started from, for the backward kernel."""
    q = x_ref.shape[1]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_acc[...] = jnp.zeros_like(h_acc)

    b, c = b_ref[0], c_ref[0]                           # (Q, N)
    cb = _matmul_nt(c, b)                               # (Q, Q) f32
    s_blk, dt_blk = s_ref[0], dt_ref[0]                 # (Q, H) f32
    lane = jax.lax.broadcasted_iota(jnp.int32, s_blk.shape, 1)
    first = pl.program_id(1) * hg
    lower = _lower_triangle(q)
    for k in range(hg):
        s_col, dt_col, decay = _head_terms(
            s_blk, dt_blk, lane, rows_ref, first + k, k, lower)
        heads = slice(k * p, (k + 1) * p)
        x32 = x_ref[0, :, heads].astype(_F32)           # (Q, P)
        xdt = x32 * dt_col
        h = h_acc[k]                                    # (P, N) f32
        y = _matmul((cb * decay).astype(dtype), xdt.astype(dtype))
        y += jnp.exp(s_col) * _matmul_nt(c, h.astype(dtype))
        y += d_ref[0, k:k + 1, :] * x32
        y_ref[0, :, heads] = y.astype(y_ref.dtype)
        hprev_ref[0, 0, k] = h
        s_last = s_col[q - 1:q, :]                      # (1, 1)
        to_end = (xdt * jnp.exp(s_last - s_col)).astype(dtype)
        h_acc[k] = jnp.exp(s_last) * h + _matmul_tn(to_end, b)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, s_ref, dt_ref, rows_ref, d_ref,
                hprev_ref, dx_ref, db_ref, dc_ref, dcols_ref, drows_ref,
                dd_ref, dh_acc, *, hg, p):
    """One (batch, group, chunk) step of the backward pass; the index
    maps hand the chunks over last first, the gradient of the group's
    states carried in ``dh_acc``. Module docstring for the equations."""
    q = x_ref.shape[1]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dh_acc[...] = jnp.zeros_like(dh_acc)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    b, c = b_ref[0], c_ref[0]
    b32 = b.astype(_F32)
    cb = _matmul_nt(c, b)
    s_blk, dt_blk = s_ref[0], dt_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, s_blk.shape, 1)
    first = pl.program_id(1) * hg
    lower = _lower_triangle(q)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    dcols = jnp.zeros((q, 2 * hg), _F32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, dcols.shape, 1)
    for k in range(hg):
        s_col, dt_col, decay = _head_terms(
            s_blk, dt_blk, lane, rows_ref, first + k, k, lower)
        heads = slice(k * p, (k + 1) * p)
        x32 = x_ref[0, :, heads].astype(_F32)
        dy = dy_ref[0, :, heads]
        dy32 = dy.astype(_F32)
        hprev = hprev_ref[0, 0, k]                      # (P, N) f32
        dh = dh_acc[k]
        hprev_lo, dh_lo = hprev.astype(dtype), dh.astype(dtype)
        s_last = s_col[q - 1:q, :]
        e_col, e_last = jnp.exp(s_col), jnp.exp(s_last)
        w_col = jnp.exp(s_last - s_col)
        xdt = x32 * dt_col
        xdt_lo = xdt.astype(dtype)
        to_end = (xdt * w_col).astype(dtype)
        m32 = cb * decay

        dxdt = (_matmul_tn(m32.astype(dtype), dy)
                + w_col * _matmul_nt(b, dh_lo))         # (Q, P)
        dx_ref[0, :, heads] = (
            dxdt * dt_col + d_ref[0, k:k + 1, :] * dy32
        ).astype(dx_ref.dtype)
        ddt = jnp.sum(dxdt * x32, axis=1, keepdims=True)

        dm = _matmul_nt(dy, xdt_lo)                     # (Qi, Qj)
        dcb += dm * decay
        through_decay = dm * m32
        ds = jnp.sum(through_decay, axis=1, keepdims=True)
        drows_ref[0, 0, k:k + 1, :] = -jnp.sum(
            through_decay, axis=0, keepdims=True
        )

        # What the chunks before added to y.
        dy_e = dy32 * e_col
        ds += jnp.sum(dy_e * _matmul_nt(c, hprev_lo), axis=1, keepdims=True)
        dy_e = dy_e.astype(dtype)
        dc += _matmul(dy_e, hprev_lo)
        # The chunk's own state, and the state's recurrence.
        into_state = _matmul(to_end, dh_lo)             # (Q, N)
        db += into_state
        through_w = jnp.sum(into_state * b32, axis=1, keepdims=True)
        at_end = jnp.sum(through_w, axis=0, keepdims=True) + e_last * (
            jnp.sum(jnp.sum(dh * hprev, axis=1, keepdims=True),
                    axis=0, keepdims=True))
        ds += jnp.where(last_row, at_end, 0.0) - through_w
        dh_acc[k] = e_last * dh + _matmul_tn(dy_e, c)

        dcols += jnp.where(out_lane == k, ds, 0.0) + jnp.where(
            out_lane == hg + k, ddt, 0.0)
        dd_ref[0, 0, k:k + 1, :] += jnp.sum(
            dy32 * x32, axis=0, keepdims=True
        )
    dcb = dcb.astype(dtype)
    db_ref[0] = (db + _matmul_tn(dcb, c)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _matmul(dcb, b)).astype(dc_ref.dtype)
    dcols_ref[0, 0] = dcols


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _specs(q, hg, p, n, groups, chunk_of):
    """BlockSpecs of a (batch, group, step) grid; ``chunk_of(step)`` is
    the chunk a step works on."""
    c = chunk_of
    return dict(
        x=pl.BlockSpec((1, q, hg * p), lambda b, g, t: (b, c(t), g)),
        bc=pl.BlockSpec((1, q, n), lambda b, g, t: (b, c(t), g)),
        heads=pl.BlockSpec((1, q, groups * hg), lambda b, g, t: (b, c(t), 0)),
        cols=pl.BlockSpec((1, 1, q, 2 * hg), lambda b, g, t: (b, g, c(t), 0)),
        rows=pl.BlockSpec((1, 1, hg, q), lambda b, g, t: (b, g, 0, c(t))),
        d=pl.BlockSpec((1, hg, p), lambda b, g, t: (g, 0, 0)),
        state=pl.BlockSpec((1, 1, hg, p, n),
                           lambda b, g, t: (b, c(t), g, 0, 0)),
        dd=pl.BlockSpec((1, 1, hg, p), lambda b, g, t: (b, g, 0, 0)),
    )


@functools.partial(jax.jit, static_argnums=(7, 8), inline=True)
def _forward_call(x, b, c, s, dt, rows, d_rows, q, interpret):
    """x (B, S, H*P), b/c (B, S, G*N), s and dt (B, S, H), rows
    (B, G, hg, S), d_rows (G, hg, P) -> (y like x, the state every
    chunk started from (B, S/Q, H, P, N) float32). One trace for all
    the layers that call it (``inline``: the program is what it would
    be without the call boundary)."""
    bt, s_len, hp = x.shape
    g, hg, p = d_rows.shape
    n = b.shape[2] // g
    nc = s_len // q
    spec = _specs(q, hg, p, n, g, lambda t: t)
    flops = 2 * bt * s_len * g * (q * n + hg * (q * p + 2 * p * n))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, hg=hg, p=p),
            grid=(bt, g, nc),
            in_specs=[spec["x"], spec["bc"], spec["bc"], spec["heads"],
                      spec["heads"], spec["rows"], spec["d"]],
            out_specs=[spec["x"], spec["state"]],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct((bt, nc, g * hg, p, n), _F32),
            ],
            scratch_shapes=[pltpu.VMEM((hg, p, n), _F32)],
            compiler_params=_compiler_params(),
            cost_estimate=pl.CostEstimate(
                flops=flops, transcendentals=bt * s_len * g * hg * q,
                bytes_accessed=(2 * x.size + b.size + c.size)
                * x.dtype.itemsize,
            ),
            interpret=interpret,
        )(x, b, c, s, dt, rows, d_rows)


@functools.partial(jax.jit, static_argnums=(9, 10), inline=True)
def _backward_call(x, dy, b, c, s, dt, rows, d_rows, hprev, q, interpret):
    """-> (dx like x, db, dc like b, dcols (B, G, S, 2 hg): ds | ddt a
    group, drows like rows (the part of ds that comes as a row), dD
    partial sums (B, G, hg, P))."""
    bt, s_len, hp = x.shape
    g, hg, p = d_rows.shape
    n = b.shape[2] // g
    nc = s_len // q
    spec = _specs(q, hg, p, n, g, lambda t: nc - 1 - t)
    flops = 2 * bt * s_len * g * (
        3 * q * n + hg * (3 * q * p + 5 * p * n))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, hg=hg, p=p),
            grid=(bt, g, nc),
            in_specs=[spec["x"], spec["x"], spec["bc"], spec["bc"],
                      spec["heads"], spec["heads"], spec["rows"], spec["d"],
                      spec["state"]],
            out_specs=[spec["x"], spec["bc"], spec["bc"], spec["cols"],
                       spec["rows"], spec["dd"]],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(b.shape, b.dtype),
                jax.ShapeDtypeStruct(c.shape, c.dtype),
                jax.ShapeDtypeStruct((bt, g, s_len, 2 * hg), _F32),
                jax.ShapeDtypeStruct(rows.shape, _F32),
                jax.ShapeDtypeStruct((bt, g, hg, p), _F32),
            ],
            scratch_shapes=[pltpu.VMEM((hg, p, n), _F32)],
            compiler_params=_compiler_params(),
            cost_estimate=pl.CostEstimate(
                flops=flops, transcendentals=bt * s_len * g * hg * q,
                bytes_accessed=(3 * x.size + 2 * (b.size + c.size))
                * x.dtype.itemsize + hprev.size * 4,
            ),
            interpret=interpret,
        )(x, dy, b, c, s, dt, rows, d_rows, hprev)


def _layouts(dt, a_head, d_head, groups, q, p):
    """What the kernels read besides x, B, C and dt: ``s``, the running
    sum of dt A inside each chunk, as it lies (B, S, H) and with the
    positions on the lanes, a group's heads together (B, G, hg, S); D as
    a (G, hg, P) block of rows."""
    bt, s_len, h = dt.shape
    hg = h // groups
    s = jnp.cumsum(
        (dt * a_head).reshape(bt, s_len // q, q, h), axis=2
    ).reshape(bt, s_len, h)
    rows = s.transpose(0, 2, 1).reshape(bt, groups, hg, s_len)
    d_rows = jnp.broadcast_to(
        d_head.reshape(groups, hg, 1), (groups, hg, p))
    return s, rows, d_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd(x, dt, a_head, b, c, d_head, q, groups, interpret):
    """x (B, S, H*P), b and c (B, S, G*N): the heads and groups stay
    flat on the lanes from the caller's projection to the kernels (a
    (B, S, H, P) array with P under a lane tile is laid out padded, and
    every reshape to or from it is a pass over HBM)."""
    return _ssd_fwd(x, dt, a_head, b, c, d_head, q, groups, interpret)[0]


def _ssd_fwd(x, dt, a_head, b, c, d_head, q, groups, interpret):
    p = x.shape[2] // dt.shape[2]
    s, rows, d_rows = _layouts(dt, a_head, d_head, groups, q, p)
    y, hprev = _forward_call(x, b, c, s, dt, rows, d_rows, q, interpret)
    return y, (x, dt, a_head, b, c, d_head, hprev)


def _ssd_bwd(q, groups, interpret, res, dy):
    x, dt, a_head, b, c, d_head, hprev = res
    bt, s_len, h = dt.shape
    s, rows, d_rows = _layouts(dt, a_head, d_head, groups, q,
                               x.shape[2] // h)
    dx, db, dc, dcols, drows, dd = _backward_call(
        x, dy.astype(x.dtype), b, c, s, dt, rows, d_rows, hprev, q,
        interpret)
    hg = h // groups
    heads_last = lambda z: z.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        bt, s_len, h)
    ds = heads_last(dcols[..., :hg]) + drows.transpose(0, 3, 1, 2).reshape(
        bt, s_len, h)
    # s is the running sum of a inside a chunk: da_k = sum_{i >= k} ds_i.
    da = jnp.flip(jnp.cumsum(jnp.flip(
        ds.reshape(bt, s_len // q, q, h), axis=2), axis=2), axis=2
    ).reshape(bt, s_len, h)
    ddt = heads_last(dcols[..., hg:]) + da * a_head
    return (dx, ddt.astype(dt.dtype),
            jnp.sum(da * dt, axis=(0, 1)).astype(a_head.dtype), db, dc,
            jnp.sum(dd, axis=(0, 3)).reshape(h).astype(d_head.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def supports(x_shape, groups, state, chunk=DEFAULT_CHUNK) -> bool:
    """Whether the compiled kernels take these shapes (the interpreter
    takes any): whole chunks, and blocks whose lanes are whole tiles."""
    _, s_len, h, p = x_shape
    return (h % groups == 0 and s_len % chunk == 0 and chunk % 128 == 0
            and (h // groups * p) % 128 == 0 and state % 128 == 0)


def ssd_scan(x, dt, A, B, C, D, chunk=DEFAULT_CHUNK, interpret=None):
    """y (B, S, H, P) of the recurrence in the module docstring.

    x (B, S, H, P); dt (B, S, H) float32, positive (after its
    softplus); A (H,) float32, negative; B, C (B, S, G, N), head h
    reading group ``h // (H / G)``; D (H,). Matrix products in x's type,
    decays and states float32. A length that is no whole number of
    chunks is padded with positions whose dt is 0 (they neither decay
    nor add). ``interpret`` None: the compiled kernels on a TPU, the
    Pallas interpreter elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bt, s_len, h, p = x.shape
    groups, n = B.shape[2:]
    if h % groups:
        raise ValueError(f"ssd_scan: {h} heads over {groups} groups")
    pad = -s_len % chunk
    if pad:
        widen = lambda z: jnp.pad(  # noqa: E731
            z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    if not interpret and not supports(x.shape, groups, n, chunk):
        raise ValueError(
            f"ssd_scan: the compiled kernels need whole lane tiles; got "
            f"x{x.shape}, {groups} groups, state {n}, chunk {chunk}")
    padded = x.shape[1]
    y = _ssd(
        x.reshape(bt, padded, h * p), dt.astype(_F32), A.astype(_F32),
        B.astype(x.dtype).reshape(bt, padded, groups * n),
        C.astype(x.dtype).reshape(bt, padded, groups * n), D.astype(_F32),
        chunk, groups, bool(interpret),
    ).reshape(bt, padded, h, p)
    return y[:, :s_len] if pad else y


@functools.lru_cache(maxsize=None)
def log_traced(x_shape: tuple, groups: int, state: int, chunk: int,
               implementation: str = "pallas chunk kernel"):
    """Once per shape in the process (every layer of every trace asks
    again), like the attention's line: which scan a trace took."""
    logger.info(
        "ssd: traced %s for x%s, %d groups, state %d, chunk %d: %d "
        "chunks, state carried in VMEM, backward %s",
        implementation, x_shape, groups, state, chunk,
        -(-x_shape[1] // chunk), BACKWARD,
    )


def ssd_reference(x, dt, A, B, C, D):
    """The recurrence as written, one position after another
    (``lax.scan``), float32: what the kernels are held to in the
    tests."""
    bt, s_len, h, p = x.shape
    groups = B.shape[2]
    rep = h // groups
    f32 = lambda z: z.astype(_F32)  # noqa: E731
    x, dt, B, C = f32(x), f32(dt), f32(B), f32(C)
    bh = jnp.repeat(B, rep, axis=2)                     # (B, S, H, N)
    ch = jnp.repeat(C, rep, axis=2)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                    # (B, H, .)
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        y_t = jnp.sum(state * c_t[..., None, :], axis=-1)
        return state, y_t + D[None, :, None] * x_t

    first = jnp.zeros((bt, h, p, B.shape[3]), _F32)
    over_time = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(
        step, first, (over_time(x), over_time(dt), over_time(bh),
                      over_time(ch)))
    return jnp.moveaxis(y, 0, 1)
