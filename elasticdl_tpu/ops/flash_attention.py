"""Fused flash attention for TPU (Pallas forward AND backward).

The transformer flagship's single-chip hot path. ``dense_attention``
(ops/ring_attention.py) materializes the (B, H, S, S) score matrix in
HBM — O(S²) memory and two extra HBM round-trips. This kernel tiles
queries over the grid and streams K/V through VMEM with the standard
online-softmax recurrence (running max m, denominator l, accumulator o),
so scores only ever exist as (block_q, block_k) tiles on-chip.

What the causal mask saves, and where: all of it rests on knowing the
diagonal's place at trace time (``_walk``: a causal call, Python-int
offsets). Inside ONE grid tile, which is the whole sequence at S <=
1,024 with the default blocks, the kernels walk the tile in
``SUB_TILE``-wide strips that stop at the diagonal: 10 of 16 sub-tiles
multiplied at S = 1,024, 3 of 4 at S = 512. Over a square GRID of tiles
(S over one block: 2,048, 4,096; equal offsets) grid step (qi, kt) is a
diagonal tile iff qi == kt, and the grid kernels branch on it: the
diagonal tiles walk the same strips (each row's accumulators stepped
once a tile, as the whole tile steps them), the tiles below the
diagonal are multiplied whole with NO mask built (no iota, compare or
select: every score is visible), and the steps above it are dead: their
bodies are predicated out and their index maps name the diagonal's
block, which is resident or wanted next, so nothing is fetched for
them. At S = 4,096: 6 tiles whole and unmasked, 4 walked 10 of 16, 6
dead. Where the diagonal is traced (the ring's prefetched offsets;
unequal blocks or offsets) every tile is computed whole under a traced
compare, as before. All forms share one definition of the tile
mathematics (``_tile_scores``, ``_tile_probs``, ``_bwd_tile_math``) and
give the same bits: the rows dropped are exact zeros of the mask.

Two more mask kinds take ``causal``'s place (``mask=``), each ONE call
over the S x S grid by a STATIC PLAN that says, for every row of query
tiles, which k steps are whole tiles (no mask built), which are
boundary tiles walked in sub-tile strips under a rule, and which are
dead (predicated out, their index maps clamped to a live step's tile):
:class:`BlockDiffusion` (a row twice over, ``_BlockDiffusionPlan``) and
:class:`SlidingWindow` (a causal band, ``_BandPlan``: the diagonal
tile, the whole tiles inside the band, one more boundary tile at the
band's far edge, dead steps on both sides). One pair of kernels runs
any plan (``_fwd_plan_kernel``, ``_bwd_plan_kernel``): a new mask kind
is a plan class and, where its boundary needs one, a ``_Rule`` kind,
not a kernel body.

Backward is a custom VJP: the forward saves only o and the logsumexp
L = m + log(l) (the flash-attention residual trick); the backward is
ONE tiled Pallas kernel a call, the ring path's too
(``flash_chunk_grads``): the probability tile is recomputed from the
residuals in VMEM once a live tile, and S, P, dP and dS feed dq, dk and
dv together, 5 products a tile (until PR 35 a dq kernel and a dk/dv
kernel each recomputed them: 7 products, and q, k, v, do, lse and delta
read twice). dk and dv sum over the q tiles of a k block in scratch; dq
sums over the k blocks in its output block, a (batch*head)'s whole row
resident in VMEM. An earlier pure-XLA blockwise-scan backward measured
~3.2x the forward's device time on v5e (~22% of the whole transformer
train step) and was replaced by kernels.

Numerics: QK^T and PV matmuls run in the input dtype on the MXU with
float32 accumulation (``preferred_element_type``); softmax state is
float32 throughout.
"""

import functools
import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("attention")

_NEG_INF = -1e30
# Measured on v5e (bf16, D=64): per-grid-step overhead dominates small
# tiles on this backend — round-2 found 512-blocks 10-27x faster than
# 128-blocks; the round-3 device-time block sweep at S=1024
# (B8/H8/D64, fwd+bwd, causal) went further: 1024x1024 blocks run
# 1.083 ms vs 1.244 ms at 512x512 (+13%) — fewer grid steps beat the
# causal block-skipping the smaller GRID tiles enable (the skipping is
# had inside the 1024 block instead, by ``_walk``'s strips). 1024 is
# the default; blocks clamp to S for shorter sequences (S=512 uses
# 512x512). VMEM per step at 1024 blocks, whole tile: each f32
# (block_q, block_k) tile is 4 MB and the backward keeps several live
# (s, p, dp, ds); a strip's are a quarter of that. No vmem_limit_bytes
# is set: Mosaic (jax 0.9.0, libtpu 0.0.34, v5e) takes all three
# kernels under its default scoped limit at the flagship geometry
# (bf16, B*H=128, S=1024, D=128; tests/test_tpu_kernels.py). The
# forward kernel's scoped allocation there is 65 MB of the v5e's
# 128 MiB (the compiler's own figure, from its refusal when the limit
# was lowered on purpose): a larger default block would not fit.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Edge of the sub-tiles a grid tile on the diagonal is walked in
# (``_walk``). v5e sweep, B*H=128, S=1024, bf16, forward + both backward
# kernels, device ms a layer: whole tile 1.836; 512: 1.329; 256: 1.237;
# 128: 1.336; within two microseconds of that at D=128, so ``d`` does
# not enter the choice (tools/bench_flash_blocks.py prints the rows).
# Over a grid of several tiles (PR 28; same tool, ``grid`` rows; v5e,
# B*H=128, bf16, ms a layer fwd / dq / dk+dv / sum), what each part of
# the walk buys over the grid of whole tiles under a traced compare:
#   S 4,096, q/k 192, v 128   whole tiles  8.778 11.862 14.482 35.122
#     strips in the diagonal tiles         8.098 10.544 12.987 31.629
#     + no mask built below them           7.791 10.488 12.930 31.209
#     + dead steps fetch nothing           7.234  9.991 10.739 27.964
#   S 4,096, D 64             whole tiles  6.491  7.493 10.279 24.262
#     strips / + no mask / + no fetch     22.720 / 22.323 / 19.846 (sums)
#   S 2,048, D 64             whole tiles  1.888  2.200  2.900  6.988
#     strips / + no mask / + no fetch      6.122 /  6.050 /  5.386 (sums)
# All three parts pay and are kept; every row gives the whole tiles'
# bits. The dead steps cost what they did because a dk/dv step fetched
# q, do and two (block, 1) f32 columns (lse, delta), whose rows are 4
# bytes each: 2.9 us a dead step, 768 of them a call.
# Since PR 35 the backward is ONE kernel (S, P, dP, dS once a tile: 5
# products where the dq and dk/dv kernels ran 7; same tool and chip, ms
# a layer fwd / bwd / sum, beside the rows above, each shape's last
# and its whole tiles', as fwd / dq + dk/dv / sum):
#   S 4,096, q/k 192, v 128   7.234 / 20.730 / 27.964 -> 7.237 / 14.029 / 21.267
#   S 4,096, D 64             (sum 19.846)            -> 6.015 /  8.967 / 14.982
#     whole tiles             6.491 / 17.772 / 24.262 -> 6.492 / 11.907 / 18.399
#   S 2,048, D 64             (sum  5.386)            -> 1.779 /  2.456 /  4.235
#     whole tiles             1.888 /  5.100 /  6.988 -> 1.998 /  3.393 /  5.392
#   S 1,024, D 64             0.332 /  0.904 /  1.237 -> 0.332 /  0.612 /  0.944
# and at B*H = 64, S 8,192, D 128: 32 query heads over 2, causal, 9.817
# / 24.340 / 34.157 -> 9.821 / 16.417 / 26.238; over 4 under the
# block-diffusion mask 6.979 / 15.631 / 22.610 -> 6.977 / 10.643 / 17.620.
SUB_TILE = 256
# A head wider than ``WIDE_HEAD`` (latent attention's q and k are 192)
# took the former dk/dv kernel's operands, outputs and accumulators 0.5
# MiB past Mosaic's default 16 MiB of scoped VMEM at the default blocks
# (the compiler's refusal, v5e): such calls raise the limit. v5e sweep, B*H =
# 128, S = 4,096, q/k 192, v 128, bf16, forward + backward, ms a layer:
# 1024 x 1024 under this limit 42.8; under the default limit 512 x 1024:
# 45.4, 1024 x 512: 49.8, 512 x 512: 50.8
# (tools/bench_mla_moe_parts.py). Calls with heads up to 128 pass no
# limit, but for a backward whose blocks need more (below).
WIDE_HEAD = 128
WIDE_HEAD_VMEM_BYTES = 64 * 2 ** 20


# Mosaic's default limit of scoped VMEM (v5e: 16 of 128 MiB). The
# backward over a grid of tiles keeps a b's whole dq row, (Sq, d)
# float32, in VMEM as an output block (twice: the pipeline's second
# buffer), so what it needs follows the sequence: the compiler's own
# figures (the smallest limit it accepts, v5e, bf16, 1024 x 1024 blocks,
# MiB): S 2,048 D 64 15.8; S 4,096 D 64 18.2; S 8,192 D 128 21.4 (under
# the block-diffusion mask the same); S 4,096 q/k 192 v 128 25.7; S
# 16,384 D 128 29.9. ``_grads_vmem_bytes`` counts the blocks as VMEM
# lays them out and three float32 score tiles for the kernel's
# temporaries (the compiler takes 6.4-8.2 MiB for them), and a call
# whose count passes the default raises the limit as a wide head does.
DEFAULT_VMEM_BYTES = 16 * 2 ** 20
# The longest dq row the backward keeps resident; a longer call's
# queries are cut into runs that fit (``flash_chunk_grads``).
RESIDENT_DQ_BYTES = 8 * 2 ** 20


def _compiler_params(semantics, *head_sizes, vmem_bytes=0):
    raised = (max(head_sizes) > WIDE_HEAD
              or vmem_bytes > DEFAULT_VMEM_BYTES)
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=WIDE_HEAD_VMEM_BYTES if raised else None,
    )


def _grads_vmem_bytes(sq, block_q, block_k, d, dv, itemsize):
    """What the backward over a grid of tiles holds in VMEM: the
    streamed blocks (q, do, k, v and the lse and delta columns, a lane
    tile wide each) and the outputs (dq's whole row; dk, dv) twice, the
    dk/dv accumulators, three float32 score tiles."""
    def lanes(width):
        return -(-width // 128) * 128

    heads = lanes(d) + lanes(dv)
    streamed = (block_q + block_k) * heads * itemsize + 2 * block_q * 128 * 4
    written = (sq * lanes(d) + block_k * heads) * 4
    return (2 * (streamed + written) + block_k * heads * 4
            + 3 * block_q * block_k * 4)


def _resident_rows(d, block_q):
    """How many of a call's queries, in whole blocks, the backward
    kernel takes at once: those whose float32 dq rows fit
    ``RESIDENT_DQ_BYTES``."""
    return max(1, RESIDENT_DQ_BYTES // (4 * d * block_q)) * block_q


class BlockDiffusion(NamedTuple):
    """The mask of block-diffusion training (BD3-LM, arXiv:2503.09573):
    the sequence is a row twice over, ``[x_t ; x_0]``, ``half`` noised
    positions and then the same ``half`` clean, in blocks of ``block``.
    With n(i) = (i mod half) // block, query i sees key j iff

    - both noised and n(i) == n(j): a noised block sees itself, both
      ways;
    - i noised, j clean and n(j) < n(i): and the clean blocks before it;
    - both clean and n(j) <= n(i): block-causal, both ways in a block;

    and a clean query sees no noised key. ``flash_attention(...,
    mask=BlockDiffusion(half, block))`` runs it through the kernels
    (``_block_diffusion_plan``), ``visible`` is the same rule on
    positions, for ``dense_attention`` and the tests."""
    half: int
    block: int

    def visible(self, qpos, kpos):
        q_block = qpos % self.half // self.block
        k_block = kpos % self.half // self.block
        q_clean, k_clean = qpos >= self.half, kpos >= self.half
        return jnp.where(
            q_clean, k_clean & (k_block <= q_block),
            jnp.where(k_clean, k_block < q_block, k_block == q_block))

    @property
    def pairs(self) -> int:
        """Visible (query, key) pairs of one row and head: half (half +
        block) / 2 clean on clean, half (half - block) / 2 noised on
        clean, half x block noised on noised."""
        return self.half * (self.half + self.block)

    @property
    def span(self) -> int:
        """The length the kernels' blocks tile: a half."""
        return self.half


class SlidingWindow(NamedTuple):
    """A causal band over a sequence of ``length`` positions: query i
    sees keys i - window + 1 .. i, itself among them (a window of
    ``length`` or more is plain causal attention). ``flash_attention(...,
    mask=SlidingWindow(length, window))`` runs it through the kernels
    that run a :class:`BlockDiffusion` call, by a static plan
    (``_BandPlan``); ``visible`` is the same rule on positions, for
    ``dense_attention`` and the tests."""
    length: int
    window: int

    def visible(self, qpos, kpos):
        return (kpos <= qpos) & (qpos - kpos < self.window)

    @property
    def pairs(self) -> int:
        """Visible (query, key) pairs of one row and head: the first
        ``window`` queries see 1, 2, .. window keys, every later one
        ``window``."""
        w = min(self.window, self.length)
        return w * (w + 1) // 2 + (self.length - w) * w

    @property
    def span(self) -> int:
        """The length the kernels' blocks tile: the sequence."""
        return self.length


# The mask kinds ``flash_attention(..., mask=)`` takes in place of
# ``causal``. Each has ``visible(qpos, kpos)``, ``pairs`` and ``span``.
Mask = Union[BlockDiffusion, SlidingWindow]


class _Rule(NamedTuple):
    """What a boundary tile of a masked call masks, on positions counted
    from the tile's corner. Of a block-diffusion call (a block never
    straddles a tile: ``block`` divides the sub-tile): ``block_causal``
    n(k) <= n(q), ``block_strict`` n(k) < n(q), ``block_diagonal`` n(k)
    == n(q); ``block`` is a power of two, so the block of a position is
    its high bits. Of a sliding window's far edge, a whole number of
    tiles behind the diagonal: ``after``, the key after the query
    (``block`` 1, unread)."""
    kind: str
    block: int

    def visible(self, qpos, kpos):
        if self.kind == "after":
            return kpos > qpos
        low = self.block - 1
        if self.kind == "block_causal":
            return (qpos | low) >= kpos
        if self.kind == "block_strict":
            return (qpos & ~low) > kpos
        return (qpos ^ kpos) <= low

    def _blocks(self, q0, q_len, k0, k_len):
        b = self.block
        return q0 // b, (q0 + q_len - 1) // b, k0 // b, (k0 + k_len - 1) // b

    def any_visible(self, q0, q_len, k0, k_len) -> bool:
        q_lo, q_hi, k_lo, k_hi = self._blocks(q0, q_len, k0, k_len)
        if self.kind == "after":
            return k_hi > q_lo
        if self.kind == "block_causal":
            return k_lo <= q_hi
        if self.kind == "block_strict":
            return k_lo < q_hi
        return k_lo <= q_hi and q_lo <= k_hi

    def any_masked(self, q0, q_len, k0, k_len) -> bool:
        q_lo, q_hi, k_lo, k_hi = self._blocks(q0, q_len, k0, k_len)
        if self.kind == "after":
            return k_lo <= q_hi
        if self.kind == "block_causal":
            return k_hi > q_lo
        if self.kind == "block_strict":
            return k_hi >= q_lo
        return not q_lo == q_hi == k_lo == k_hi


class _Strips(NamedTuple):
    """How a boundary tile is walked: sub-tile row ``i`` multiplies the
    sub-tiles ``starts[i] .. rows[i]`` (none where they are equal) under
    ``rule``: a :class:`_Rule`, or True for the causal diagonal through
    the tile's corner."""
    rule: Union[_Rule, bool]
    starts: Tuple[int, ...]
    rows: Tuple[int, ...]

    @property
    def computed(self) -> int:
        return sum(self.rows) - sum(self.starts)


def _strips(rule: _Rule, block: int, sub: int) -> _Strips:
    starts, rows = [], []
    for i in range(block // sub):
        live = [j for j in range(block // sub)
                if rule.any_visible(i * sub, sub, j * sub, sub)]
        starts.append(live[0] if live else 0)
        rows.append(live[-1] + 1 if live else 0)
    return _Strips(rule, tuple(starts), tuple(rows))


def _diffusion_steps(plan, row, step):
    """Which of a block-diffusion call's tiles grid step ``step`` of
    tile row (forward) or key column (backward) ``row`` is, as traced
    booleans: (the row lies in the noised half, its place in its half,
    whether the step is the noised tile of the same place, whether it
    is the clean one)."""
    n = plan.n
    noised = row < n
    place = jnp.where(noised, row, row - n)
    return noised, place, step == place, step == n + place


# A static plan is what the plan kernels (``_fwd_plan_kernel``,
# ``_bwd_plan_kernel``) run a masked call by, one class a mask kind,
# each with:
#   forward_steps(row, step) / backward_steps(column, step): the kinds
#     of live tile of a q tile row's k steps (a k column's q steps), as
#     (live, walk, flag): ``live()`` the traced boolean "this step is
#     one" (a thunk: its operations are emitted where its step is, so a
#     plan's program is its steps' in order), ``walk`` the boundary
#     tile's :class:`_Strips` or None for a whole tile with no mask
#     built, ``flag`` the strips kernels' ``final`` (forward: the row's
#     last live tile) or ``add`` (backward: the accumulators hold
#     earlier tiles' parts). A step that is none of them is dead;
#   kv_tile(i, j) / q_tile(i, j): the index maps, clamped so that a dead
#     step names a live one's tile and fetches nothing;
#   tiles: (whole, boundary, skipped); ``walked``: the boundary tiles'
#     clause of the attention line; ``rows``: the strips ``TilePlan``
#     shows.


class _BlockDiffusionPlan(NamedTuple):
    """The static plan of a :class:`BlockDiffusion` call over a square
    grid of 2n x 2n tiles, n to a half. Query tile row r of a half sees:
    where noised, its own noised tile (``own``: the blocks on the
    diagonal and nothing else), the clean tiles before r whole, and
    clean tile r under ``before``; where clean, the clean tiles before
    r whole and clean tile r under ``clean``. Every other tile is dead.
    n (n - 1) tiles whole and unmasked, 3 n boundary tiles, the rest
    of the 4 n^2 skipped."""
    n: int
    sub: int
    own: _Strips
    before: _Strips
    clean: _Strips

    @property
    def rows(self):
        return self.clean.rows

    @property
    def tiles(self) -> Tuple[int, int, int]:
        n = self.n
        return n * (n - 1), 3 * n, 4 * n * n - n * (n - 1) - 3 * n

    def walked(self, total, sub_tiles) -> str:
        n = self.n
        return (
            f"{n} noised on their own blocks {self.own.computed}, {n} "
            f"noised on the clean blocks before {self.before.computed}, "
            f"{n} clean {self.clean.computed} of {total} {sub_tiles}")

    def forward_steps(self, qi, kb):
        """A noised q tile steps its state over its own noised tile,
        the clean tiles before its place whole, and the clean tile of
        its place under ``before``, where it is finalised; a clean q
        tile over the clean tiles before it whole and its own under
        ``clean``."""
        n = self.n
        noised, place, on_noised, on_clean = _diffusion_steps(self, qi, kb)
        return (
            (lambda: noised & on_noised, self.own, False),
            (lambda: (kb >= n) & (kb - n < place), None, False),
            (lambda: noised & on_clean, self.before, True),
            (lambda: jnp.logical_not(noised) & on_clean, self.clean, True),
        )

    def backward_steps(self, ki, qt):
        """A noised key tile is seen by the noised q tile of its place
        alone (``own`` sets the accumulators). Clean key tile c is seen
        by noised q tile c under ``before`` (the first: it sets the
        accumulators), the noised tiles after it whole, clean q tile c
        under ``clean`` (added) and the clean tiles after it whole."""
        n = self.n
        noised, place, on_noised, on_clean = _diffusion_steps(self, ki, qt)
        clean = jnp.logical_not(noised)
        return (
            (lambda: noised & on_noised, self.own, False),
            (lambda: clean & on_noised, self.before, False),
            (lambda: clean & (((qt > place) & (qt < n)) | (qt > ki)),
             None, True),
            (lambda: clean & on_clean, self.clean, True),
        )

    def kv_tile(self, i, j):
        """The K/V tile step j of query row i names (forward): a
        live step its own, a dead one the next live one's, or the last
        live one's past it: nothing is fetched for a dead step."""
        n = self.n
        noised = jnp.where(
            j <= i, i, jnp.where(j < n, n, jnp.minimum(j, n + i)))
        return jnp.where(i < n, noised, jnp.clip(j, n, i))

    def q_tile(self, i, j):
        """The q/do/lse/delta tile step j of key column i names (backward):
        a noised column is seen by its own tile alone; clean column c by
        the noised tiles from c on and the clean tiles from c on."""
        n = self.n
        clean = jnp.where(
            j < n, jnp.maximum(j, i - n), jnp.maximum(j, i))
        return jnp.where(i < n, i, clean)


def _block_diffusion_plan(mask: BlockDiffusion, s_len, block_q, block_k,
                          sub=None) -> Optional[_BlockDiffusionPlan]:
    """The plan of a block-diffusion call, or None where the kernels do
    not tile it: the sequence is the two halves, the tiles are square
    and divide a half, a tile is at least two sub-tiles each way, and
    the block length is a power of two that divides the sub-tile (then
    no block straddles a sub-tile, and every boundary is known at trace
    time)."""
    sub = sub or SUB_TILE
    half, b = mask.half, mask.block
    if not (
        s_len == 2 * half and block_q == block_k
        and half % block_q == 0 and block_q % sub == 0
        and block_q >= 2 * sub and b & (b - 1) == 0 and sub % b == 0
        and half % b == 0
    ):
        return None
    return _BlockDiffusionPlan(
        half // block_q, sub,
        *(_strips(_Rule(kind, b), block_q, sub)
          for kind in ("block_diagonal", "block_strict", "block_causal")))


class _BandPlan(NamedTuple):
    """The static plan of a :class:`SlidingWindow` call over a square
    grid of n x n tiles, the window ``w`` whole tiles wide. Query tile
    row i sees key tile i - w (where there is one) under ``edge``, the
    rule "key after query" from the tile's corner; the w - 1 tiles
    between (or the i before i) whole with no mask built; and tile i
    under ``diagonal``, the causal walk, where it is finalised. The
    tiles before the edge and after the diagonal are dead: dead steps
    on BOTH sides, where a causal grid has them on one."""
    n: int
    sub: int
    w: int
    diagonal: _Strips
    edge: _Strips

    @property
    def rows(self):
        return self.diagonal.rows

    @property
    def tiles(self) -> Tuple[int, int, int]:
        n, w = self.n, self.w
        whole = sum(min(i, w - 1) for i in range(n))
        boundary = n + max(0, n - w)
        return whole, boundary, n * n - whole - boundary

    def walked(self, total, sub_tiles) -> str:
        return (
            f"{self.n} diagonal, {max(0, self.n - self.w)} at the "
            f"window's edge, {self.diagonal.computed} of {total} "
            f"{sub_tiles}")

    def kv_tile(self, i, j):
        """The K/V tile step j of query row i names (forward)."""
        return jnp.clip(j, jnp.maximum(i - self.w, 0), i)

    def q_tile(self, i, j):
        """The q/do/lse/delta tile step j of key column i names
        (backward): the diagonal's, the w - 1 after it, the edge's."""
        return jnp.clip(j, i, jnp.minimum(i + self.w, self.n - 1))

    def forward_steps(self, qi, kb):
        w = self.w
        return (
            (lambda: kb == qi - w, self.edge, False),
            (lambda: (kb > qi - w) & (kb < qi), None, False),
            (lambda: kb == qi, self.diagonal, True),
        )

    def backward_steps(self, ki, qt):
        """The diagonal tile is the first that sees a key tile: it sets
        the accumulators, the others add."""
        w = self.w
        return (
            (lambda: qt == ki, self.diagonal, False),
            (lambda: (qt > ki) & (qt < ki + w), None, True),
            (lambda: qt == ki + w, self.edge, True),
        )


def _band_plan(mask: SlidingWindow, s_len, block_q, block_k,
               sub=None) -> Optional[_BandPlan]:
    """The plan of a sliding-window call, or None where the kernels do
    not tile it: the sequence is the mask's, the tiles are square, of at
    least two sub-tiles each way, and the window is a whole number of
    them (then every boundary is known at trace time: the far edge runs
    corner to corner through tile i - w as the diagonal does through
    tile i)."""
    sub = sub or SUB_TILE
    if not (
        s_len == mask.length and block_q == block_k
        and s_len % block_q == 0 and block_q % sub == 0
        and block_q >= 2 * sub and mask.window >= block_q
        and mask.window % block_q == 0
    ):
        return None
    n_sub = block_q // sub
    return _BandPlan(
        s_len // block_q, sub, mask.window // block_q,
        _Strips(True, (0,) * n_sub, tuple(range(1, n_sub + 1))),
        _strips(_Rule("after", 1), block_q, sub))


# Which backward a trace took is static, so its counter is this clause
# of the line ``log_traced`` prints (every ``TilePlan.describe`` ends
# with it): S, dP, dQ, dK, dV once a live tile, in one ``pallas_call``.
BACKWARD_FORM = "; backward: one kernel, 5 products a tile"


class TilePlan(NamedTuple):
    """How a kernel call spends its grid, and how one grid tile of it is
    multiplied: ``rows[i]`` is the number of sub-tiles, from the left,
    that sub-tile row ``i`` multiplies. The whole tile kept is one
    sub-tile: ``rows == (1,)`` and ``sub_q, sub_k`` the block itself.
    Over a ``grid`` of several tiles ``rows`` is the plan of the tiles
    on the diagonal (``tiles`` counts them and the others). Under a
    ``mask``, ``plan`` is the call's static plan and ``rows`` its
    causal boundary tiles' (a block-diffusion call's clean ones, a
    sliding window's diagonal ones)."""
    block_q: int
    block_k: int
    sub_q: int
    sub_k: int
    rows: Tuple[int, ...]
    grid: Tuple[int, int] = (1, 1)
    # Query heads that read one key/value head (``_kv_row``).
    group: int = 1
    plan: Union[_BlockDiffusionPlan, _BandPlan, None] = None

    @property
    def computed(self) -> int:
        return sum(self.rows)

    @property
    def total(self) -> int:
        return (self.block_q // self.sub_q) * (self.block_k // self.sub_k)

    @property
    def tiles(self) -> Tuple[int, int, int]:
        """(multiplied whole, walked by ``rows``, skipped) grid tiles.
        A walked grid is square with the diagonal through the corners
        of its diagonal tiles: those are walked, the tiles below them
        are whole and build no mask, the tiles above are skipped. A
        grid that is not walked visits every tile whole (what its
        traced compare predicates out is not known here)."""
        n_q, n_k = self.grid
        if self.plan is not None:
            return self.plan.tiles
        if self.rows == (1,):
            return n_q * n_k, 0, 0
        off_diagonal = n_q * (n_k - 1) // 2
        return off_diagonal, n_q, off_diagonal

    def describe(self) -> str:
        blocks = f"blocks {self.block_q}x{self.block_k}"
        sub_tiles = f"sub-tiles {self.sub_q}x{self.sub_k}"
        walked = f"{self.computed} of {self.total}"
        shared = (
            f"; one key/value head read in place by {self.group} query "
            "heads, dk/dv summed over them" if self.group > 1 else ""
        ) + BACKWARD_FORM
        if self.plan is not None:
            whole, boundary, skipped = self.tiles
            return (
                f"grid {self.grid[0]}x{self.grid[1]} of {blocks}: {whole} "
                f"tile{'s' if whole != 1 else ''} whole and unmasked, "
                f"{boundary} boundary tiles walked "
                f"({self.plan.walked(self.total, sub_tiles)}), "
                f"{skipped} skipped{shared}"
            )
        if self.grid == (1, 1) or self.rows == (1,):
            return f"{blocks}, {sub_tiles}, {walked} computed{shared}"
        whole, diagonal, skipped = self.tiles
        return (
            f"grid {self.grid[0]}x{self.grid[1]} of {blocks}: {whole} "
            f"tile{'s' if whole != 1 else ''} whole and unmasked, "
            f"{diagonal} diagonal tiles walked {walked} {sub_tiles}, "
            f"{skipped} skipped{shared}"
        )


def _walk(causal, sq, sk, block_q, block_k, q_offset, k_offset, sub=None):
    """The strip walk of a kernel call's diagonal, or None where every
    tile is kept whole. It needs the diagonal's place inside a tile at
    trace time: a causal call, offsets that are Python ints (the ring's
    are traced), a block of at least two sub-tiles, and either one grid
    tile each way (the offsets place the diagonal in it) or a square
    grid of square tiles with equal offsets: then grid tile (qi, kt)
    holds the diagonal iff qi == kt, from its corner, so the plan for
    offsets (0, 0) is every diagonal tile's, the tiles below hold no
    masked score and the tiles above no visible one.

    Returns, for each ``sub``-high row of sub-tiles, how many sub-tiles
    from the left the mask leaves something of: sub-tile (i, j) has an
    unmasked score iff its bottom-left one is, q_offset + (i+1)*sub - 1
    >= k_offset + j*sub. Rows are prefixes and columns suffixes, so a
    strip is one rectangle."""
    sub = sub or SUB_TILE
    if not (
        causal
        and isinstance(q_offset, int) and isinstance(k_offset, int)
        and block_q % sub == 0 and block_k % sub == 0
        and block_q >= 2 * sub and block_k >= 2 * sub
    ):
        return None
    if not _one_tile(sq, sk, block_q, block_k):
        if not (sq == sk and block_q == block_k and q_offset == k_offset):
            return None
        q_offset = k_offset = 0
    n_k = block_k // sub
    return tuple(
        min(n_k, max(0, (q_offset + (i + 1) * sub - 1 - k_offset) // sub + 1))
        for i in range(block_q // sub)
    )


def _one_tile(sq, sk, block_q, block_k):
    """Whether a call's grid is one tile: then a plan of ``_walk`` is
    run by the strips kernels, else by the grid kernels' diagonal
    steps."""
    return sq == block_q and sk == block_k


def tile_plan(sq, sk, causal=True, block_q=0, block_k=0, q_offset=0,
              k_offset=0, sub=None, group=1, mask=None) -> TilePlan:
    """What a kernel call with these arguments multiplies (the kernels
    ask ``_walk`` the same question, or ``_mask_plan`` under a
    ``mask``): for the line ``log_traced`` prints and for the tests.
    Traced offsets are anything that is not an int."""
    sub = sub or SUB_TILE
    if mask is not None:
        block_q, block_k = _blocks(mask.span, mask.span, block_q, block_k)
        plan = _mask_plan(mask, sq, block_q, block_k, sub)
        return TilePlan(block_q, block_k, sub, sub, plan.rows,
                        (sq // block_q, sk // block_k), group, plan)
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    grid = (sq // block_q, sk // block_k)
    rows = _walk(causal, sq, sk, block_q, block_k, q_offset, k_offset, sub)
    if rows is None:
        return TilePlan(block_q, block_k, block_q, block_k, (1,), grid,
                        group)
    return TilePlan(block_q, block_k, sub, sub, rows, grid, group)


def describe_tiles(s_len, causal=True, traced_offsets=False,
                   group=1, mask=None) -> str:
    """``tile_plan(...).describe()`` of the kernels a layer over a
    sequence of ``s_len`` runs with the default blocks: the standalone
    kernels, or the ring's chunk kernels (``traced_offsets``); ``group``
    query heads to a key/value head; ``mask`` as ``flash_attention``'s."""
    offset = None if traced_offsets else 0
    return tile_plan(
        s_len, s_len, causal, q_offset=offset, k_offset=offset,
        group=group, mask=mask,
    ).describe()


def _diagonal_crosses(q_start, k_start, k_len):
    """Whether a rectangle whose first query sits at ``q_start`` and
    whose keys span ``k_len`` from ``k_start`` holds a masked score: its
    top-right one is."""
    return q_start < k_start + k_len - 1


def _cost(bh, sq, sk, d, dv, causal, byte_tensors, mask=None, matmuls=2):
    """pl.CostEstimate for one attention kernel, MODEL-FLOPs convention:
    count the algorithmically required ``matmuls`` of the kernel, half
    of which contract or produce the q/k head size ``d`` and half the v
    head size ``dv`` (fwd, 2: QK over d, PV over dv; the backward, 4:
    dQ and dK over d, dP and dV over dv), and NOT the
    in-kernel score recompute (that is rematerialization — the same
    convention under which benchlib.program_flops excludes
    jax.checkpoint recompute). Causal discounts by 1/2 (the exact useful fraction is
    (S+1)/2S; 1/2 is the conservative side, and ring chunks fully below
    the diagonal are also undercounted, never overcounted). XLA's cost
    analysis folds these into the program totals, so Pallas-kernel
    FLOPs stop reading as zero in the bench's MFU numerator
    (tools/measure_config.py, BASELINE.md round-4 note).

    ``byte_tensors``: (count, seq_len, width, dtype_size) of
    (BH, seq_len, width)-shaped operands/outputs for bytes_accessed;
    k and v with fewer heads than q count ``_kv_share`` of one. Under a
    ``mask`` the count is its visible pairs', and ``causal`` unread."""
    if mask is not None:
        # The visible pairs, whatever tiles the kernel visits.
        frac = mask.pairs / (sq * sk)
    else:
        frac = 0.5 if causal else 1.0
    flops = int(matmuls * bh * sq * sk * (d + dv) * frac)
    # One exp per score element per kernel (fwd online-softmax; the
    # backward recomputes P once).
    transcendentals = int(bh * sq * sk * frac)
    nbytes = int(sum(
        count * bh * s * width * size
        for count, s, width, size in byte_tensors
    ))
    return pl.CostEstimate(
        flops=flops, transcendentals=transcendentals,
        bytes_accessed=nbytes,
    )


def _kv_row(q, k):
    """The (batch*head) row of k and v that query row ``b`` of the grid
    reads: q (B*H, S, D) against k, v (B*Hkv, S, .), query head h
    reading key/value head ``h // (H / Hkv)``, which is row ``b //
    group`` because H = Hkv * group. The key/value heads are never
    repeated in HBM: the index maps name the shared row, and
    consecutive grid rows of one group find its tile resident. With as
    many key/value heads as query heads the maps are what they were."""
    group = q.shape[0] // k.shape[0]
    if group == 1:
        return lambda b: b
    return lambda b: jax.lax.div(b, jnp.int32(group))


def _kv_share(q, k):
    """k's and v's rows as a share of q's, for ``_cost``'s bytes."""
    group = q.shape[0] // k.shape[0]
    return 1 if group == 1 else 1.0 / group


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_acc, l_acc, o_acc,
                *, block_k: int, causal: bool, scale: float):
    """One (batch*head, q-block, k-block) grid step.

    The k dimension is innermost and sequential on TPU, so the VMEM
    scratch accumulators (running max / denominator / output) persist
    across k steps while Pallas streams (block_k, d) K/V tiles from HBM
    with automatic double buffering — VMEM residency is O(block), not
    O(S)."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_acc[:] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)
        o_acc[:] = jnp.zeros_like(o_acc)

    _scratch_tile_update(
        q_ref, k_ref, v_ref, m_acc, l_acc, o_acc, q_start, k_start,
        block_k=block_k, causal=causal, scale=scale,
    )

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l_safe = jnp.maximum(l_acc[:], 1e-30)
        o_ref[0] = (o_acc[:] / l_safe).astype(o_ref.dtype)
        l_ref[0] = m_acc[:] + jnp.log(l_safe)  # logsumexp residual


def _causal_mask(shape, q_start, k_start):
    """Where the query at global position q_start + row may see the key
    at k_start + column."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return qpos >= kpos


def _visible(shape, q_start, k_start, causal):
    """The mask of a tile of ``shape``: the causal one (``causal``
    True), or a boundary tile's of a block-diffusion call (a
    :class:`_Rule`, positions from the tile's corner)."""
    if causal is True:
        return _causal_mask(shape, q_start, k_start)
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return causal.visible(qpos, kpos)


def _holds_masked(rule, q_start, q_len, k_start, k_len):
    """What a strip's tile mathematics takes as ``causal`` over the
    rectangle of ``q_len`` queries from ``q_start`` and ``k_len`` keys
    from ``k_start``: False where no score of it is masked (no mask is
    built), else the rule."""
    if rule is True:
        return _diagonal_crosses(q_start, k_start, k_len)
    return rule.any_masked(q_start, q_len, k_start, k_len) and rule


def _tile_scores(q, k_blk, q_start, k_start, causal, scale):
    """Scaled scores of one tile, f32 (rows of q, rows of k_blk), and
    the mask (None when not ``causal``; ``_visible`` says what it may
    be): masked scores are set to ``_NEG_INF``. ``q_start/k_start``:
    global positions of the tile's first query and key, ints or traced
    scalars."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if not causal:
        return s, None
    mask = _visible(s.shape, q_start, k_start, causal)
    return jnp.where(mask, s, _NEG_INF), mask


def _tile_probs(s, mask, m):
    """exp(s - m) with masked entries exactly zero."""
    p = jnp.exp(s - m)
    return p if mask is None else jnp.where(mask, p, 0.0)


def _scratch_tile_update(q_ref, k_ref, v_ref, m_acc, l_acc, o_acc,
                         q_start, k_start, *, block_k, causal, scale):
    """The online-softmax recurrence for one K/V tile against the VMEM
    scratch accumulators — shared by the standalone forward and the
    ring-chunk kernel so the numerically delicate update exists once."""
    block_q = q_ref.shape[1]

    def _compute():
        v_blk = v_ref[0]
        s, mask = _tile_scores(
            q_ref[0], k_ref[0], q_start, k_start, causal, scale
        )                              # (block_q, block_k)
        m_prev = m_acc[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = _tile_probs(s, mask, m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_acc[:] = m_new
        l_acc[:] = l_acc[:] * alpha + p.sum(axis=1, keepdims=True)
        o_acc[:] = o_acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Tiles strictly above the diagonal contribute nothing — the
        # body is predicated out and their FLOPs skipped (the grid still
        # visits the step, so the scratch state machine stays uniform).
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()


def _fwd_strips_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, rows, sub,
                       scale, carried=None, starts=None, rule=True,
                       final=True):
    """The forward over one grid tile whose diagonal starts at its
    corner (``_walk`` with offsets 0, 0): q strip ``i`` against the keys
    up to its own end, and no further. A strip sees all its keys of the
    tile at once, so a row's softmax state is stepped once a tile:
    stepping scratch accumulators once a SUB-tile costs more than the
    masked half of the tile saves (2.320 ms a layer against 1.836, v5e).

    Without ``carried`` the tile is the whole sequence, one (batch*head)
    a grid step, and a strip's softmax the plain one on values: the
    recurrence of ``_scratch_tile_update`` from an empty state (m =
    -1e30, l = o = 0) in one step, to the same bits. ``carried`` =
    (m_acc, l_acc, o_acc), the scratch state the tiles to the left have
    left (``_fwd_grid_kernel``): the strip takes one step of that
    recurrence from it. Either way the diagonal tile is the last that
    contributes to its queries, so the strip is finalised here.

    A boundary tile of a masked call (``_fwd_plan_kernel``) is walked
    likewise, under its ``rule`` and from sub-tile
    ``starts[i]`` on; ``final`` False (a noised tile on its own blocks:
    clean tiles follow) leaves the stepped state in ``carried``."""
    for i, n_k in enumerate(rows):
        first = starts[i] if starts else 0
        strip = pl.ds(i * sub, sub)
        keys = pl.ds(first * sub, (n_k - first) * sub)
        if n_k == first:
            # Only under a rule: the strip sees nothing of this tile.
            if final:
                m_acc, l_acc, o_acc = carried
                l_safe = jnp.maximum(l_acc[strip, :], 1e-30)
                o_ref[0, strip, :] = (
                    o_acc[strip, :] / l_safe).astype(o_ref.dtype)
                l_ref[0, strip, :] = m_acc[strip, :] + jnp.log(l_safe)
            continue
        v_blk = v_ref[0, keys, :]
        s, mask = _tile_scores(
            q_ref[0, strip, :], k_ref[0, keys, :], i * sub, first * sub,
            rule, scale
        )                              # (sub, (n_k - first) * sub)
        m = s.max(axis=1, keepdims=True)
        if carried is not None:
            m_acc, l_acc, o_acc = carried
            m_prev = m_acc[strip, :]
            m = jnp.maximum(m_prev, m)
        p = _tile_probs(s, mask, m)
        l = p.sum(axis=1, keepdims=True)
        if carried is not None:
            alpha = jnp.exp(m_prev - m)
            l = l_acc[strip, :] * alpha + l
        if final:
            l_safe = jnp.maximum(l, 1e-30)
        o = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if carried is not None:
            o = o_acc[strip, :] * alpha + o
        if not final:
            m_acc[strip, :], l_acc[strip, :], o_acc[strip, :] = m, l, o
            continue
        o_ref[0, strip, :] = (o / l_safe).astype(o_ref.dtype)
        l_ref[0, strip, :] = m + jnp.log(l_safe)


def _fwd_grid_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_acc, l_acc,
                     o_acc, *, rows, sub, scale):
    """One (batch*head, q-block, k-block) grid step of a call whose
    diagonal tiles are walked (``_walk`` over several tiles): the k
    block lies wholly below the diagonal (every score visible: the tile
    update with no mask built), on it (the strips of ``rows``, finalised
    there), or above it (nothing: the step is predicated out, and the
    index maps of ``_forward_call`` name no new block for it)."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_acc[:] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)
        o_acc[:] = jnp.zeros_like(o_acc)

    @pl.when(kb < qi)
    def _below():
        _scratch_tile_update(
            q_ref, k_ref, v_ref, m_acc, l_acc, o_acc, 0, 0,
            block_k=k_ref.shape[1], causal=False, scale=scale,
        )

    @pl.when(kb == qi)
    def _diagonal():
        _fwd_strips_kernel(
            q_ref, k_ref, v_ref, o_ref, l_ref, rows=rows, sub=sub,
            scale=scale, carried=(m_acc, l_acc, o_acc),
        )


def _fwd_plan_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_acc, l_acc,
                     o_acc, *, plan, scale):
    """One (batch*head, q-block, k-block) grid step of a call under a
    mask with a static plan (``_BlockDiffusionPlan``, ``_BandPlan``):
    the q tile steps its softmax state over the step's tile if
    ``plan.forward_steps`` says it is live, a boundary tile walked in
    strips under its rule (the row's last is finalised there), a whole
    one multiplied with no mask built. Every other step is dead:
    predicated out, and ``plan.kv_tile`` names no new block for it."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    steps = plan.forward_steps(qi, kb)
    carried = (m_acc, l_acc, o_acc)

    def strips(walk, final):
        _fwd_strips_kernel(
            q_ref, k_ref, v_ref, o_ref, l_ref, rows=walk.rows,
            sub=plan.sub, scale=scale, carried=carried,
            starts=walk.starts, rule=walk.rule, final=final,
        )

    def whole():
        _scratch_tile_update(
            q_ref, k_ref, v_ref, m_acc, l_acc, o_acc, 0, 0,
            block_k=k_ref.shape[1], causal=False, scale=scale,
        )

    @pl.when(kb == 0)
    def _init():
        m_acc[:] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)
        o_acc[:] = jnp.zeros_like(o_acc)

    for live, walk, final in steps:
        pl.when(live())(
            whole if walk is None
            else functools.partial(strips, walk, final))


def _plan_of(mask, s_len, block_q, block_k, sub=None):
    """``mask``'s static plan for these blocks, or None where the
    kernels have none."""
    planner = (_band_plan if isinstance(mask, SlidingWindow)
               else _block_diffusion_plan)
    return planner(mask, s_len, block_q, block_k, sub)


def _mask_plan(mask, s_len, block_q, block_k, sub=None):
    """The static plan the kernels of a call under ``mask`` run by (the
    whole sequence against itself); None without a mask."""
    if mask is None:
        return None
    plan = _plan_of(mask, s_len, block_q, block_k, sub)
    if plan is None:
        raise ValueError(
            f"flash_attention: {mask} over {s_len} positions in blocks "
            f"({block_q}, {block_k}) has no kernel plan; gate callers "
            "with supports()")
    return plan


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool,
                   mask: Optional[Mask] = None):
    """q,k: (BH, S, D), v: (BH, S, Dv) -> (o (BH,S,Dv), L (BH,S,1))."""
    s_len = q.shape[1]
    if s_len % block_q or s_len % block_k:
        raise ValueError(
            f"flash_attention: seq len {s_len} must tile by blocks "
            f"({block_q}, {block_k}); gate callers with supports()"
        )
    rows = None if mask is not None else _walk(
        causal, s_len, s_len, block_q, block_k, 0, 0)
    return _forward_call(
        q, k, v, causal, scale, block_q, block_k, rows, interpret, mask,
        _mask_plan(mask, s_len, block_q, block_k))


def _shared_trace(*static_argnums):
    """``jax.jit`` inlined into the caller's program, for what it does
    while TRACING: a model's layers call the kernels with one set of
    shapes and static arguments, so they share one trace of the kernel
    body where each used to trace it again. A kernel body is dozens of
    ``jnp`` calls, and the worker traces the 24 forward kernels once
    for shapes in its ``state_init`` (``eval_shape`` of the model's
    init) and all 72 in ``first_program``: on the chip's host, with
    JAX's compile log on and the reader's thread busy, that was 2.5 s
    of a 5.5 s ``state_init`` with the whole-tile kernel and 8.4 of
    11.3 s with the strips (PERF.md, PR 26). Everything a trace depends
    on has to be an argument: the plan (``rows``) is computed by the
    caller, never read from ``SUB_TILE`` inside. ``inline=True`` keeps
    the lowered program what it was (no call boundary, the custom
    calls named from the caller's scope)."""
    return functools.partial(
        jax.jit, static_argnums=static_argnums, inline=True
    )


@_shared_trace(3, 4, 5, 6, 7, 8, 9, 10)
def _forward_call(q, k, v, causal, scale, block_q, block_k, rows,
                  interpret, mask=None, plan=None):
    """The forward ``pallas_call``. ``rows`` is the plan of ``_walk``:
    given and the sequence one tile, the strips kernel; given over a
    grid of tiles, the grid kernel that walks the diagonal tiles; None,
    the grid of whole tiles. Under a ``mask``, ``plan`` is its static
    plan and the grid kernel the one that runs plans (``causal`` and
    ``rows`` are then unread)."""
    bh, s_len, d = q.shape
    dv = v.shape[2]
    if rows is not None and _one_tile(s_len, s_len, block_q, block_k):
        out_shape, cost = _forward_outputs(q, k, v, causal)
        kv_row = _kv_row(q, k)
        whole = pl.BlockSpec((1, s_len, d), lambda b: (b, 0, 0))
        whole_v = pl.BlockSpec((1, s_len, dv), lambda b: (b, 0, 0))
        return pl.pallas_call(
            functools.partial(
                _fwd_strips_kernel, rows=rows, sub=block_q // len(rows),
                scale=scale,
            ),
            grid=(bh,),
            in_specs=[
                whole,
                pl.BlockSpec((1, s_len, d), lambda b: (kv_row(b), 0, 0)),
                pl.BlockSpec((1, s_len, dv), lambda b: (kv_row(b), 0, 0)),
            ],
            out_specs=[
                whole_v, pl.BlockSpec((1, s_len, 1), lambda b: (b, 0, 0)),
            ],
            out_shape=out_shape,
            compiler_params=_compiler_params(("parallel",), d, dv),
            cost_estimate=cost,
            interpret=interpret,
        )(q, k, v)
    if plan is not None:
        kernel = functools.partial(
            _fwd_plan_kernel, plan=plan, scale=scale)
        kv_tile = plan.kv_tile
    elif rows is not None:
        kernel = functools.partial(
            _fwd_grid_kernel, rows=rows, sub=block_q // len(rows),
            scale=scale,
        )
        kv_tile = jnp.minimum
    else:
        kernel = functools.partial(
            _fwd_kernel, block_k=block_k, causal=causal, scale=scale
        )
        kv_tile = _streamed
    return _grid_forward(kernel, kv_tile, q, k, v, block_q, block_k, causal,
                         interpret, mask)


def _forward_outputs(q, k, v, causal, mask=None):
    """(``out_shape``, ``cost_estimate``) of a forward ``pallas_call``:
    o and the logsumexp, carried as (BH, S, 1)."""
    bh, s_len, d = q.shape
    dv = v.shape[2]
    out_shape = [
        jax.ShapeDtypeStruct((bh, s_len, dv), q.dtype),
        jax.ShapeDtypeStruct((bh, s_len, 1), jnp.float32),
    ]
    kv = _kv_share(q, k)
    cost = _cost(
        bh, s_len, s_len, d, dv, causal=causal,
        byte_tensors=[(1 + kv, s_len, d, q.dtype.itemsize),
                      (1 + kv, s_len, dv, q.dtype.itemsize)],
        mask=mask,
    )
    return out_shape, cost


def _streamed(resident, streamed):
    """The tile a grid step names of the operands that stream past the
    resident block: the step's own. Where the diagonal tiles are walked
    the steps past the diagonal are dead, and ``jnp.minimum`` (K/V past
    a q block) or ``jnp.maximum`` (q, do, lse, delta before a k block)
    take this function's place: a dead step then names the diagonal's
    tile, which is resident or wanted next, and no DMA is issued for
    it."""
    del resident
    return streamed


def _grid_forward(kernel, kv_tile, q, k, v, block_q, block_k, causal,
                  interpret, mask=None):
    """The forward ``pallas_call`` over the grid of tiles: ``kernel`` is
    a grid step's body, ``kv_tile(i, j)`` the K/V tile that step j of q
    block i names."""
    bh, s_len, d = q.shape
    dv = v.shape[2]
    out_shape, cost = _forward_outputs(q, k, v, causal, mask)
    kv_row = _kv_row(q, k)
    return pl.pallas_call(
        kernel,
        grid=(bh, s_len // block_q, s_len // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv_row(b), kv_tile(i, j), 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda b, i, j: (kv_row(b), kv_tile(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            # lse carried as (BH, S, 1): a trailing unit dim keeps the
            # block's last-two dims TPU-tileable (block_q % 8, 1 == dim).
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, dv), jnp.float32),  # running output
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), d, dv
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(q, k, v)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, mask):
    o, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, mask)
    return o


# What the forward kernel alone can give: a block that recomputes
# (``jax.checkpoint``, ``nn.remat``) under ``remat_policy()`` keeps
# these two and so never runs the kernel a second time; q, k and v are
# projections its recomputation makes again anyway. Without a checkpoint
# around it a name lowers to nothing.
_KEPT_O = "flash_attention_o"
_KEPT_LSE = "flash_attention_lse"


def remat_policy():
    """The policy for a recomputed block that may hold this module's
    kernel: keep the forward kernel's o and logsumexp, recompute
    everything else. Where no kernel is traced (the dense reference, the
    ring) no value bears the names and nothing is kept."""
    return jax.checkpoint_policies.save_only_these_names(
        _KEPT_O, _KEPT_LSE
    )


def describe_kept(v) -> str:
    """The clause the attention line of a model with remat on ends with,
    for v (B, S, H, Dv): what ``remat_policy()`` keeps of one block, o
    in v's shape and type and a float32 logsumexp."""
    b, s_len, h, dv = v.shape
    kept = b * h * s_len * (dv * v.dtype.itemsize + 4)
    return (
        f"under remat the block keeps o and logsumexp ({kept / 1e6:.1f} "
        "MB), the forward kernel is not run again"
    )


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, mask):
    o, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            interpret, mask)
    # The names have to sit here, inside the forward rule: one on
    # ``flash_attention``'s result alone would keep o and still run the
    # kernel again for the logsumexp.
    o = checkpoint_name(o, _KEPT_O)
    lse = checkpoint_name(lse[..., 0], _KEPT_LSE)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, mask, res, g):
    """Backward via the tiled Pallas kernel (flash_chunk_grads with the
    whole sequence as one chunk). Profiled on v5e: the previous XLA
    blockwise-scan backward was ~22% of transformer step device time at
    ~3.2x the Pallas forward's cost per call; the kernel (shared with
    the ring path, gradient-verified there) keeps score tiles in VMEM
    and runs its five products a tile on the MXU."""
    q, k, v, o, lse = res
    dof = g.astype(jnp.float32)
    delta = (dof * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True
    )                                                   # (BH, S, 1)
    dq, dk, dv = flash_chunk_grads(
        q, k, v, g, lse[..., None], delta, 0, 0, causal=causal,
        scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, mask=mask,
    )
    group = q.shape[0] // k.shape[0]
    if group > 1:
        # The kernel gives every query head's part of dk, dv (float32);
        # a key/value head's gradient is the sum over its group.
        dk, dv = (
            x.reshape((k.shape[0], group) + x.shape[1:]).sum(axis=1)
            for x in (dk, dv)
        )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _chunk_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                  acc_ref, m_out, l_out, acc_out, m_scr, l_scr, acc_scr,
                  *, block_k: int, causal: bool, scale: float):
    """Carry-in/carry-out online-softmax update of q blocks against one
    K/V chunk — the fused inner step of ring attention (the ring rotates
    chunks between devices; position offsets arrive as prefetched
    scalars). Same streaming structure as _fwd_kernel: the k dimension
    is an innermost sequential grid axis and K/V tiles flow through VMEM
    (O(block) residency), with scratch seeded from the carry at the
    first tile and flushed to the carry outputs at the last."""
    qi = pl.program_id(1)
    kt = pl.program_id(2)
    num_kt = pl.num_programs(2)
    block_q = q_ref.shape[1]
    q_start = qoff_ref[0] + qi * block_q
    k_start = koff_ref[0] + kt * block_k

    @pl.when(kt == 0)
    def _init():
        m_scr[:] = m_ref[0]
        l_scr[:] = l_ref[0]
        acc_scr[:] = acc_ref[0]

    _scratch_tile_update(
        q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, q_start, k_start,
        block_k=block_k, causal=causal, scale=scale,
    )

    @pl.when(kt == num_kt - 1)
    def _flush():
        m_out[0] = m_scr[:]
        l_out[0] = l_scr[:]
        acc_out[0] = acc_scr[:]


def flash_chunk_update(
    q, k_chunk, v_chunk, m, l, acc, q_offset, k_offset,
    causal: bool = True, scale: Optional[float] = None,
    block_q: int = 0, block_k: int = 0,
    interpret: bool = False,
):
    """Fold one K/V chunk into running flash accumulators.

    q: (BH, Sq, D); k_chunk: (BH, Sk, D); v_chunk: (BH, Sk, Dv); m, l:
    (BH, Sq, 1) f32; acc: (BH, Sq, Dv) f32; q_offset/k_offset: scalar global positions of
    q[.,0] and k_chunk[.,0] (traced values fine — scalar-prefetched).
    Returns updated (m, l, acc); callers finalize with acc/max(l,eps)
    after the last chunk.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bh, sq, d = q.shape
    sk, dv = v_chunk.shape[1:]
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_chunk_update: shapes (Sq={sq}, Sk={sk}) must tile "
            f"by blocks ({block_q}, {block_k})"
        )
    kernel = functools.partial(
        _chunk_kernel, block_k=block_k, causal=causal,
        scale=float(scale),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, *_: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, *_: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    qoff = jnp.asarray(q_offset, jnp.int32).reshape((1,))
    koff = jnp.asarray(k_offset, jnp.int32).reshape((1,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), d, dv
        ),
        cost_estimate=_cost(
            bh, sq, sk, d, dv, causal=causal,
            byte_tensors=[(1, sq, d, q.dtype.itemsize),
                          (1, sk, d, q.dtype.itemsize),
                          (1, sk, dv, q.dtype.itemsize), (2, sq, dv, 4)],
        ),
        interpret=interpret,
    )(qoff, koff, q, k_chunk, v_chunk, m, l, acc)


def _bwd_tile_math(q, k_blk, v_blk, do, lse, delta, q_start, k_start,
                   causal, scale):
    """Shared backward tile: P = exp(S−lse); dS = P∘(dO·Vᵀ−Δ).
    Returns (ds, p) as f32 (rows of q, rows of k_blk)."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.exp(s - lse)
    if causal:
        p = jnp.where(_visible(p.shape, q_start, k_start, causal), p, 0.0)
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    return ds, p


def _tile_rows(tile, block, first=0, rows=None):
    """``rows`` rows (the tile's all, by default) from row ``first`` of
    grid tile ``tile`` (a grid index, traced) in a (1, S, width) block
    that holds the whole sequence in tiles of ``block`` rows."""
    start = pl.multiple_of(tile * block + first, math.gcd(block, first))
    return pl.ds(start, rows or block)


def _bwd_products(ds, p, q, k_blk, do, scale):
    """(dq, dk, dv) parts of one rectangle from its ``_bwd_tile_math``:
    dS K, dS^T Q and P^T dO, in the input type with float32 sums."""
    ds = ds.astype(q.dtype)
    dq = jax.lax.dot_general(
        ds, k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dq, dk, dv


def _bwd_tile_update(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_acc, dv_acc, q_tile, q_start, k_start,
                     causal, scale):
    """One whole q/do/lse/delta tile against the resident K/V tile: S,
    P, dP and dS once, their parts of dk and dv added to the scratch
    accumulators and their part of dq to rows ``q_tile`` of the
    resident dq row."""
    ds, p = _bwd_tile_math(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
        delta_ref[0], q_start, k_start, causal, scale,
    )
    dq, dk, dv = _bwd_products(ds, p, q_ref[0], k_ref[0], do_ref[0], scale)
    dq_ref[0, _tile_rows(q_tile, q_ref.shape[1]), :] += dq
    dk_acc[:] += dk
    dv_acc[:] += dv


def _zero_dq_tile(dq_ref, k_tile, q_tile, block_q):
    """A b's dq row stays in VMEM across its (k tile, q tile) steps,
    every live step adding its part; the first k tile's steps, live or
    dead, zero the q tile they name first."""
    @pl.when(k_tile == 0)
    def _zero():
        dq_ref[0, _tile_rows(q_tile, block_q), :] = jnp.zeros(
            (block_q, dq_ref.shape[2]), dq_ref.dtype)


def _bwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal: bool, scale: float):
    """Grid (bh, k-block, q-tile), both sequential: dK/dV accumulate in
    scratch while Q/dO/lse/delta tiles stream, flushed at the last
    tile; dq accumulates in its output block, the whole (1, Sq, d) row
    of the b, written back when b moves on."""
    ki = pl.program_id(1)
    qt = pl.program_id(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    q_start = qoff_ref[0] + qt * block_q
    k_start = koff_ref[0] + ki * block_k

    @pl.when(qt == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    _zero_dq_tile(dq_ref, ki, qt, block_q)

    def _compute():
        _bwd_tile_update(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
            dk_acc, dv_acc, qt, q_start, k_start, causal, scale,
        )

    if causal:
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(qt == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0] = dk_acc[:]
        dv_ref[0] = dv_acc[:]


def _strip_of(ref, strip):
    """The index of a strip of rows in a kernel's (1, rows, width)
    block, or in a (rows, width) scratch accumulator."""
    return (0, strip, slice(None)) if len(ref.shape) == 3 else (
        strip, slice(None))


def _bwd_strips_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, *, rows, sub, q_offset,
                       k_offset, scale, starts=None, rule=True, add=False,
                       q_tile=None):
    """The backward over one grid tile walked by ``_walk``: k strip
    ``j`` against the queries from the first row whose plan reaches it
    to the tile's end, S, P, dP and dS once a strip; its dk and dv are
    the strip's own, its dq part is added to those queries' rows (a q
    strip's dq is summed k strip by k strip). A k strip no query sees
    gets zeros. Without ``q_tile`` the tile is the whole call
    (``_strips_grads``): dq's rows are the tile's, zeroed here first.
    With it, the tile is grid tile ``q_tile`` of the resident dq row and
    ``dk_ref, dv_ref`` the scratch accumulators of ``_bwd_grid_kernel``
    (the diagonal tile is the first that contributes to its keys, so it
    sets them). ``starts``, ``rule``: as ``_fwd_strips_kernel``'s, for
    a boundary tile of a block-diffusion call (the rows that reach a
    strip are consecutive under every rule); ``add``: the strips' parts
    are added to the accumulators (a clean tile's keys were seen by
    noised tiles before it)."""
    if q_tile is None:
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], dq_ref.dtype)
    for j in range(k_ref.shape[1] // sub):
        strip = pl.ds(j * sub, sub)
        dk_strip, dv_strip = _strip_of(dk_ref, strip), _strip_of(dv_ref, strip)
        reach = [i for i, n_k in enumerate(rows)
                 if (starts[i] if starts else 0) <= j < n_k]
        if not reach:
            if not add:
                dk_ref[dk_strip] = jnp.zeros((sub, dk_ref.shape[-1]),
                                             dk_ref.dtype)
                dv_ref[dv_strip] = jnp.zeros((sub, dv_ref.shape[-1]),
                                             dv_ref.dtype)
            continue
        first, q_len = reach[0], (reach[-1] + 1 - reach[0]) * sub
        queries = pl.ds(first * sub, q_len)
        q, do = q_ref[0, queries, :], do_ref[0, queries, :]
        k_blk = k_ref[0, strip, :]
        q_start, k_start = q_offset + first * sub, k_offset + j * sub
        ds, p = _bwd_tile_math(
            q, k_blk, v_ref[0, strip, :], do, lse_ref[0, queries, :],
            delta_ref[0, queries, :], q_start, k_start,
            _holds_masked(rule, q_start, q_len, k_start, sub), scale,
        )
        dq, dk, dv = _bwd_products(ds, p, q, k_blk, do, scale)
        dq_rows = queries if q_tile is None else _tile_rows(
            q_tile, q_ref.shape[1], first * sub, q_len)
        dq_ref[0, dq_rows, :] += dq
        if add:
            dk_ref[dk_strip] += dk
            dv_ref[dv_strip] += dv
        else:
            dk_ref[dk_strip] = dk
            dv_ref[dv_strip] = dv


def _bwd_grid_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                     dv_acc, *, rows, sub, scale):
    """``_bwd_kernel`` where the diagonal tiles are walked (``_walk``
    over several tiles; the offsets are equal and do not enter): the q
    tiles before the diagonal are dead steps, the diagonal tile's strips
    set the dk/dv accumulators, a q tile below the diagonal adds its
    part with no mask built; every live step adds its part of dq."""
    del qoff_ref, koff_ref
    ki = pl.program_id(1)
    qt = pl.program_id(2)
    _zero_dq_tile(dq_ref, ki, qt, q_ref.shape[1])

    @pl.when(qt == ki)
    def _diagonal():
        _bwd_strips_kernel(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
            dk_acc, dv_acc, rows=rows, sub=sub, q_offset=0, k_offset=0,
            scale=scale, q_tile=qt,
        )

    @pl.when(qt > ki)
    def _below():
        _bwd_tile_update(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
            dk_acc, dv_acc, qt, 0, 0, False, scale,
        )

    @pl.when(qt == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0] = dk_acc[:]
        dv_ref[0] = dv_acc[:]


def _bwd_plan_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                     dv_acc, *, plan, scale):
    """``_bwd_kernel`` under a mask with a static plan: the key tile's
    accumulators take the part of every q tile ``plan.backward_steps``
    says is live, a boundary tile's by its strips (the first live one
    sets them, a later one adds), a whole one's with no mask built. At
    a live step the q tile is the step's own (``plan.q_tile``), and its
    part of dq goes to that tile's rows."""
    del qoff_ref, koff_ref
    ki = pl.program_id(1)
    qt = pl.program_id(2)
    steps = plan.backward_steps(ki, qt)
    _zero_dq_tile(dq_ref, ki, qt, q_ref.shape[1])

    def strips(walk, add):
        _bwd_strips_kernel(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
            dk_acc, dv_acc, rows=walk.rows, sub=plan.sub, q_offset=0,
            k_offset=0, scale=scale, starts=walk.starts, rule=walk.rule,
            add=add, q_tile=qt,
        )

    def whole():
        _bwd_tile_update(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
            dk_acc, dv_acc, qt, 0, 0, False, scale,
        )

    for live, walk, add in steps:
        pl.when(live())(
            whole if walk is None else functools.partial(strips, walk, add))

    @pl.when(qt == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0] = dk_acc[:]
        dv_ref[0] = dv_acc[:]


def _grads_outputs(q, v_chunk, causal, mask=None):
    """(``out_shape``, ``cost_estimate``) of the backward
    ``pallas_call``: dq, dk, dv in float32, dk and dv with q's rows; the
    four required matmuls (dP and dV over dv, dQ and dK over d); q, k, v
    and do read once."""
    bh, sq, d = q.shape
    sk, dv = v_chunk.shape[1:]
    size = q.dtype.itemsize
    kv = _kv_share(q, v_chunk)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, sk, dv), jnp.float32),
    ]
    cost = _cost(
        bh, sq, sk, d, dv, causal=causal, mask=mask, matmuls=4,
        byte_tensors=[(1, sq, d, size), (1, sq, dv, size),
                      (kv, sk, d, size), (kv, sk, dv, size),
                      (1, sq, d, 4), (1, sk, d, 4), (1, sk, dv, 4)],
    )
    return out_shape, cost


@_shared_trace(6, 7, 8, 9, 10)
def _strips_grads(q, k_chunk, v_chunk, do, lse, delta, rows, q_offset,
                  k_offset, scale, interpret):
    """``flash_chunk_grads`` where ``_walk`` has a plan of one tile: one
    (batch*head) a grid step and the whole tile as one block."""
    bh, sq, d = q.shape
    sk, dv = v_chunk.shape[1:]
    out_shape, cost = _grads_outputs(q, v_chunk, True)
    kv_row = _kv_row(q, k_chunk)
    q_rows = pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0))
    do_rows = pl.BlockSpec((1, sq, dv), lambda b: (b, 0, 0))
    q_col = pl.BlockSpec((1, sq, 1), lambda b: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _bwd_strips_kernel, rows=rows, sub=sq // len(rows),
            q_offset=q_offset, k_offset=k_offset, scale=scale),
        grid=(bh,),
        in_specs=[
            q_rows,
            pl.BlockSpec((1, sk, d), lambda b: (kv_row(b), 0, 0)),
            pl.BlockSpec((1, sk, dv), lambda b: (kv_row(b), 0, 0)),
            do_rows, q_col, q_col,
        ],
        out_specs=[
            q_rows,
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, dv), lambda b: (b, 0, 0)),
        ],
        out_shape=out_shape,
        compiler_params=_compiler_params(("parallel",), d, dv),
        cost_estimate=cost,
        interpret=interpret,
    )(q, k_chunk, v_chunk, do, lse, delta)


@_shared_trace(8, 9, 10, 11, 12, 13, 14, 15)
def _tiles_grads(q, k_chunk, v_chunk, do, lse, delta, q_offset, k_offset,
                 causal, scale, block_q, block_k, rows, interpret,
                 mask=None, plan=None):
    """``flash_chunk_grads`` over a grid of tiles. ``rows`` None: whole
    tiles, the offsets traced (scalar-prefetched), a tile strictly above
    the diagonal predicated out. ``rows`` the plan of ``_walk`` over
    several tiles: the diagonal tiles walked, dead steps naming the
    diagonal's tiles. Under a ``mask``, ``plan`` is its static plan:
    the kernel that runs plans and the plan's index map."""
    if plan is not None:
        kernel = functools.partial(
            _bwd_plan_kernel, plan=plan, scale=scale)
        q_tile = plan.q_tile
    elif rows is None:
        kernel = functools.partial(_bwd_kernel, causal=causal, scale=scale)
        q_tile = _streamed
    else:
        kernel = functools.partial(
            _bwd_grid_kernel, rows=rows, sub=block_q // len(rows),
            scale=scale)
        q_tile = jnp.maximum
    return _grid_grads(
        kernel, q_tile, q, k_chunk, v_chunk, do, lse, delta, q_offset,
        k_offset, block_q, block_k, causal, interpret, mask,
    )


def _grid_grads(kernel, q_tile, q, k_chunk, v_chunk, do, lse, delta,
                q_offset, k_offset, block_q, block_k, causal, interpret,
                mask=None):
    """The backward ``pallas_call`` over the grid of tiles (bh, k block,
    q tile): ``q_tile(i, j)`` is the q/do/lse/delta tile that step j of
    k block i names. dk and dv leave a block at its last step; dq's
    block is the whole row of a b, resident across that b's steps, so
    the k-block axis is sequential too."""
    bh, sq, d = q.shape
    sk, dv = v_chunk.shape[1:]
    out_shape, cost = _grads_outputs(q, v_chunk, causal, mask)
    kv_row = _kv_row(q, k_chunk)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape((1,))
    koff = jnp.asarray(k_offset, jnp.int32).reshape((1,))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, sk // block_k, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, i, j, *_: (b, q_tile(i, j), 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, *_: (kv_row(b), i, 0)),
                pl.BlockSpec((1, block_k, dv),
                             lambda b, i, j, *_: (kv_row(b), i, 0)),
                pl.BlockSpec((1, block_q, dv),
                             lambda b, i, j, *_: (b, q_tile(i, j), 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, i, j, *_: (b, q_tile(i, j), 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, i, j, *_: (b, q_tile(i, j), 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, sq, d), lambda b, i, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_k, dv),
                             lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"), d, dv,
            vmem_bytes=_grads_vmem_bytes(
                sq, block_q, block_k, d, dv, q.dtype.itemsize),
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(qoff, koff, q, k_chunk, v_chunk, do, lse, delta)


def flash_chunk_grads(
    q, k_chunk, v_chunk, do, lse, delta, q_offset, k_offset,
    causal: bool = True, scale: Optional[float] = None,
    block_q: int = 0, block_k: int = 0,
    interpret: bool = False,
    mask: Optional[Mask] = None,
):
    """Backward of one attention chunk pairing, fully tiled.

    q: (BH, Sq, D); k_chunk: (BH, Sk, D); v_chunk: (BH, Sk, Dv); do:
    (BH, Sq, Dv); lse/delta: (BH, Sq, 1) f32. Returns (dq_partial, dk_chunk, dv_chunk) — f32,
    the ring accumulates dq over chunks and rotates dk/dv home. k and v
    may have fewer rows than q, B*Hkv (``_kv_row``): dk and dv still
    come back with q's BH rows, every query head's part, for the caller
    to sum over a group. ONE
    kernel: S, P, dP and dS are computed once a live tile and feed dq,
    dk and dv together (5 products a tile); dk/dv accumulate over the
    q tiles of a k block in scratch, dq over the k blocks in its output
    block, a b's whole (Sq, D) float32 row resident in VMEM; score
    tiles never leave VMEM. Where that row would pass
    ``RESIDENT_DQ_BYTES`` (no model here runs such a length) the
    queries are cut into runs of whole blocks that fit, one kernel a
    run over whole tiles, dk and dv summed over the runs.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    sq, sk = q.shape[1], k_chunk.shape[1]
    block_q, block_k = _blocks(sq, sk, block_q, block_k)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_chunk_grads: shapes (Sq={sq}, Sk={sk}) must tile by "
            f"blocks ({block_q}, {block_k})"
        )
    run = _resident_rows(q.shape[2], block_q)
    if sq > run:
        if mask is not None:
            raise ValueError(
                f"flash_chunk_grads: {mask} over {sq} positions has no "
                "kernel plan (dq's row is not resident); gate callers "
                "with supports()")
        parts = [
            flash_chunk_grads(
                q[:, at:at + run], k_chunk, v_chunk, do[:, at:at + run],
                lse[:, at:at + run], delta[:, at:at + run], q_offset + at,
                k_offset, causal, scale, block_q, block_k, interpret)
            for at in range(0, sq, run)
        ]
        dq, dk, dv = zip(*parts)
        return jnp.concatenate(dq, axis=1), sum(dk), sum(dv)
    # Under a mask: the whole sequence against itself (``_flash_bwd``).
    rows = None if mask is not None else _walk(
        causal, sq, sk, block_q, block_k, q_offset, k_offset)
    if rows is not None and _one_tile(sq, sk, block_q, block_k):
        return _strips_grads(
            q, k_chunk, v_chunk, do, lse, delta, rows, q_offset, k_offset,
            float(scale), interpret,
        )
    return _tiles_grads(
        q, k_chunk, v_chunk, do, lse, delta, q_offset, k_offset, causal,
        float(scale), block_q, block_k, rows, interpret, mask,
        _mask_plan(mask, sq, block_q, block_k),
    )


@functools.lru_cache(maxsize=None)
def log_traced(implementation: str, why: str, q_shape: tuple):
    """Say which attention implementation a trace took and why, once
    per distinct choice in the process (every layer of every trace asks
    again). The dense reference standing in for the kernel is a
    slowdown nothing else reports; for the kernels, callers end ``why``
    with ``describe_tiles``: how much of a grid tile the causal walk
    multiplies is static, so this line is its counter."""
    logger.info(
        "attention: traced %s for q%s: %s", implementation, q_shape, why
    )


def _auto_block(s_len: int, requested: int) -> int:
    """Largest LANE-ALIGNED (x128) block <= min(requested, s_len) that
    tiles s_len; 0 when none exists. Keeps default-path block choices
    on shapes Mosaic is known to compile (the score tile's lane dim is
    block_k) and lets S = 1536/2560/3584... keep the kernel via 768/
    512-wide blocks instead of silently regressing to dense."""
    cap = min(requested, s_len)
    for cand in range(cap - cap % 128, 0, -128):
        if s_len % cand == 0:
            return cand
    return 0


def _blocks(sq: int, sk: int, block_q: int, block_k: int):
    """The blocks a call runs with: an explicit one clamped to the
    sequence, 0 = the largest lane-aligned default-or-smaller block
    that tiles it (``_auto_block``)."""
    block_q = min(block_q, sq) if block_q else (
        _auto_block(sq, DEFAULT_BLOCK_Q) or min(DEFAULT_BLOCK_Q, sq)
    )
    block_k = min(block_k, sk) if block_k else (
        _auto_block(sk, DEFAULT_BLOCK_K) or min(DEFAULT_BLOCK_K, sk)
    )
    return block_q, block_k


def supports(q_shape, block_q: int = 0, block_k: int = 0,
             mask: Optional[Mask] = None) -> bool:
    """Static shape gate — callers fall back to dense otherwise. With
    default blocks (0), S must admit a lane-aligned tiling block
    (``_auto_block``); explicit blocks keep the raw divisibility rule
    (tests drive small interpret-mode tiles). Under a ``mask`` (a
    :class:`BlockDiffusion` or a :class:`SlidingWindow`) the blocks tile
    its ``span`` (a half; the sequence), and the kernels need a static
    plan for it (``_block_diffusion_plan``; ``_band_plan``: the window a
    whole number of blocks) and the row's dq resident in the backward."""
    s_len = q_shape[1]
    if s_len % 8:
        return False
    if mask is not None:
        if not (block_q or block_k) and not (
                _auto_block(mask.span, DEFAULT_BLOCK_Q)
                and _auto_block(mask.span, DEFAULT_BLOCK_K)):
            return False
        block_q, block_k = _blocks(mask.span, mask.span, block_q, block_k)
        # The backward keeps the whole row's dq resident, and a masked
        # call cannot be cut into runs (``flash_chunk_grads``).
        return s_len <= _resident_rows(q_shape[3], block_q) and (
            _plan_of(mask, s_len, block_q, block_k) is not None)
    if not block_q and not block_k:
        return (
            _auto_block(s_len, DEFAULT_BLOCK_Q) > 0
            and _auto_block(s_len, DEFAULT_BLOCK_K) > 0
        )
    bq = min(block_q or DEFAULT_BLOCK_Q, s_len)
    bk = min(block_k or DEFAULT_BLOCK_K, s_len)
    return s_len % bq == 0 and s_len % bk == 0


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 0,
    block_k: int = 0,
    interpret: bool = False,
    mask: Optional[Mask] = None,
):
    """Fused attention. q: (B, S, H, D); k: (B, S, Hkv, D); v: (B, S,
    Hkv, Dv), whose head size may differ from q's and k's (latent
    attention: 192 against 128); returns (B, S, H, Dv). Hkv divides H:
    query head h reads key/value head ``h // (H / Hkv)``, in place
    (``_kv_row``).

    ``mask``, in place of ``causal`` (which is then not read): a
    :class:`BlockDiffusion` over S = 2 x half (the blocks tile a half)
    or a :class:`SlidingWindow` over S = length (a causal band; the
    window a whole number of blocks). Either way the same custom VJP,
    the same kernels' tile mathematics, one call over the S x S grid by
    a static plan (``_BlockDiffusionPlan``, ``_BandPlan``) whose dead
    tiles cost a predicated-out step each and fetch nothing.

    ``block_q/block_k`` 0 = auto: the largest lane-aligned default-or-
    smaller block that tiles S (``_auto_block`` — gate callers check
    ``supports`` first). ``interpret=True`` runs the kernel in the
    Pallas interpreter (CPU tests); on TPU the Mosaic-compiled kernel
    runs.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, s_len, h, d = q.shape
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"flash_attention: {h} query heads over {k.shape[2]} key and "
            f"{v.shape[2]} value heads")
    if mask is not None:
        causal = False
        block_q, block_k = _blocks(mask.span, mask.span, block_q, block_k)
    else:
        block_q, block_k = _blocks(s_len, s_len, block_q, block_k)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(
            b * x.shape[2], s_len, x.shape[3])

    o = _flash(
        to_bh(q), to_bh(k), to_bh(v), causal, float(scale), block_q,
        block_k, interpret, mask,
    )
    return o.reshape(b, h, s_len, v.shape[3]).transpose(0, 2, 1, 3)
