"""Grouped matrix product: rows sorted into groups, one matrix a group.

``grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,))``: rows
``sum(sizes[:i]) .. sum(sizes[:i+1])`` of ``lhs`` times ``rhs[i]``. The
sizes are ragged and traced; ``m`` is the static bound, the caller's to
choose at or over ``sum(group_sizes)``. Rows past
``sum(group_sizes)`` belong to no group: what comes back there is
unspecified, callers cut it off.

``jax.lax.ragged_dot``: XLA's TPU compiler lowers it to Mosaic kernels
of its own (``ragged-dot-none.N`` on the trace's ``XLA Ops`` lane,
``ragged-dot-metadata.N`` for the group offsets) that walk the live row
tiles only; the CPU runs the same call. Against a Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``) on a v5e, forward +
backward of an expert layer's products over a bound of 131,072 rows,
16 groups, 2048 -> 2 x 768 -> 2048, bf16: 8,192 live rows evenly 6.52
against 5.74 ms, all on one group 6.82 against 5.85, every row live
29.2 against 26.5 (tools/bench_mla_moe_parts.py; PERF.md, PR 27). The
0.8 ms a layer did not pay for a second path on the CPU and an
experimental import, so this is the one kept.

How long a call takes depends on the columns of ``rhs`` far more than
on the multiply-adds: XLA's kernels tile whole blocks of 512 columns
well and anything else badly (1,856 columns cost 1.6 times what 2,048
do). :func:`product_width` has the readings and the rule the expert
layer pads its weights by; the static bound costs too (at 6,144 live
rows a layer's 5.95 + 13.66 ms under a bound of 98,304 are 4.25 + 10.31
under 24,576: PERF.md section 7, row 28), so the expert layer gives its
products ``m`` = a rung of ``models/mla_moe.py::rows_ladder`` rows, the
held experts' share of the token-choices times 2 or 4 as the step's
groups need, and all the token-choices only in a step whose groups pass
both (PR 37, PR 40).
"""

import jax
import jax.numpy as jnp

GROUPED_PRODUCT = "ragged_dot"

# XLA's ragged-dot kernels tile a product's columns well where they are
# whole blocks of this many (:func:`product_width`).
_COLUMN_BLOCK = 512


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs``'s type in and out; float32 accumulation on the MXU."""
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype
    )


def product_width(f: int) -> int:
    """The width an expert layer's two grouped products run at, for
    experts of inner width ``f``: ``f`` rounded up to whole blocks of
    512 columns where that adds at most an eighth, else ``f``. The
    weights are zero-padded to it (:func:`zero_padded`), which is the
    same work exactly: the added hidden columns are act(0) = 0 in every
    live row and meet zero rows of the second matrix.

    Written from ``tools/bench_ssd_scan.py experts`` on a v5e (PERF.md,
    PR 33, call 1), a layer's forward + (forward with backward) in ms.
    Bound 98,304 rows, 6,144 live, 8 groups, 2,688 -> f -> 2,688, relu^2,
    bfloat16 weights of the width itself: 1,856 7.59 + 18.80; 1,920
    (15 lane tiles) 7.64 + 19.46; 2,048 4.94 + 11.26; 2,304 (9 x 256)
    6.45 + 15.96; 2,560 5.79 + 13.53: whole blocks of 512 cost 3.9-4.1
    ms a block, anything else more than the next whole block. With the
    float32 parameters cast and padded as the program does it, 1,856 at
    1,856 8.44 + 19.86, at 2,048 5.95 + 13.66 (the pads and their cuts
    are 1.5 of that). Where it loses: bound 131,072, 8,192 live, 16
    groups, 2,048 -> 2 x 768 -> 2,048, silu-gated, 768 at 768 3.67 +
    8.74, at 1,024 4.17 + 9.96 (a third more columns; 1,536 = 3 x 512
    is whole blocks as it is). The eighth lies between 2,304 -> 2,560
    (a ninth more, still ahead) and that."""
    wide = -(-f // _COLUMN_BLOCK) * _COLUMN_BLOCK
    return wide if 8 * (wide - f) <= f else f


def zero_padded(w, axis: int, width: int):
    """``w`` with zeros after it along ``axis`` up to ``width``; ``w``
    itself where it is that wide already (no equation is traced)."""
    if w.shape[axis] == width:
        return w
    pads = [(0, 0)] * w.ndim
    pads[axis] = (0, width - w.shape[axis])
    return jnp.pad(w, pads)
