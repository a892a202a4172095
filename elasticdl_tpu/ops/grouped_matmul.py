"""Grouped matrix product: rows sorted into groups, one matrix a group.

``grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,))``: rows
``sum(sizes[:i]) .. sum(sizes[:i+1])`` of ``lhs`` times ``rhs[i]``. The
sizes are ragged and traced; ``m`` is the static bound. Rows past
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
"""

import jax

GROUPED_PRODUCT = "ragged_dot"


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs``'s type in and out; float32 accumulation on the MXU."""
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype
    )
