"""The two sweeps behind ``models/mla_moe.py``'s choices, at the
benchmark cell's shapes. Chip only; one JSON line a row.

    python tools/bench_mla_moe_parts.py [attention] [grid] [grouped]

- **attention**: causal forward + backward of ``ops.flash_attention``
  at q/k head 192, v head 128 (B 4, H 32, S 4,096, bf16): the default
  (1024 x 1024 blocks, Mosaic's scoped VMEM limit raised as the kernels
  do for wide heads), then smaller blocks under the default limit, and
  1024 x 1024 under it (refused). ms a layer, wall clock around
  ``block_until_ready`` over ``RUNS`` calls.
- **grid**: what each part of the walk over that call's 4 x 4 grid of
  tiles buys, per kernel, from the profiler's device times
  (``tools/bench_flash_blocks.py``'s ``grid`` rows, at these head
  sizes).
- **grouped**: the expert layer's grouped products, forward + backward
  of gate+up, silu, down, over the static bound of 131,072 rows with
  8,192 of them live (the cell's expected share) evenly over 16 experts,
  and all on one expert: ``jax.lax.ragged_dot`` against a Pallas grouped
  matmul (``jax.experimental.pallas.ops.tpu.megablox``). PR 27 kept the
  former (PERF.md has the readings); the latter is imported here only.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RUNS = 10


def timed(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    started = time.perf_counter()
    for _ in range(RUNS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - started) / RUNS


def attention_rows():
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as flash

    b, s, h, d, dv = 4, 4096, 32, 192, 128
    key = jax.random.PRNGKey(0)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d),
                              jnp.bfloat16) for i in range(2))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dv),
                          jnp.bfloat16)
    raised = flash.WIDE_HEAD_VMEM_BYTES
    choices = [(0, 0, raised), (512, 1024, None), (1024, 512, None),
               (512, 512, None), (1024, 1024, None)]
    for block_q, block_k, vmem in choices:
        flash.WIDE_HEAD_VMEM_BYTES = vmem
        jax.clear_caches()

        def loss(q, k, v):
            return flash.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k
            ).astype(jnp.float32).sum()

        row = {"row": "attention", "q_k_head": d, "v_head": dv,
               "blocks": list(flash._blocks(s, s, block_q, block_k)),
               "vmem_limit_bytes": vmem}
        try:
            row["fwd_bwd_ms_a_layer"] = timed(
                jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, k, v)
        except Exception as exc:
            row["refused"] = str(exc)[-300:]
        print(json.dumps(row), flush=True)
    flash.WIDE_HEAD_VMEM_BYTES = raised
    jax.clear_caches()


def grid_rows():
    import bench_flash_blocks

    from elasticdl_tpu.ops import flash_attention as flash

    bench_flash_blocks.grid_rows(flash, "B4/H32/S4096", 4 * 32, 4096, 192, 128)


def grouped_rows():
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import grouped_matmul as gm

    bound, live, n, d, f = 131072, 8192, 16, 2048, 768
    key = jax.random.PRNGKey(1)
    rows = jax.random.normal(key, (bound, d), jnp.bfloat16)
    w_in = 0.02 * jax.random.normal(
        jax.random.fold_in(key, 1), (n, d, 2 * f), jnp.bfloat16)
    w_out = 0.02 * jax.random.normal(
        jax.random.fold_in(key, 2), (n, f, d), jnp.bfloat16)
    loads = {
        "even": jnp.full((n,), live // n, jnp.int32),
        "one_expert": jnp.zeros((n,), jnp.int32).at[3].set(live),
        "all_rows_live": jnp.full((n,), bound // n, jnp.int32),
    }

    def megablox(lhs, rhs, sizes):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tiling = (512, min(1024, lhs.shape[1]), min(768, rhs.shape[2]))
        return gmm(lhs, rhs, sizes, lhs.dtype, tiling)

    for name, product in (("ragged_dot", gm.grouped_matmul),
                          ("megablox", megablox)):
        def loss(rows, w_in, w_out, sizes):
            gate_up = product(rows, w_in, sizes)
            hidden = jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]
            out = product(hidden, w_out, sizes)
            live_rows = jnp.arange(bound) < jnp.sum(sizes)
            return jnp.where(live_rows[:, None], out, 0).astype(
                jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        for load, sizes in loads.items():
            row = {"row": "grouped", "product": name, "load": load,
                   "rows_bound": bound, "rows_live": int(sizes.sum())}
            try:
                row["fwd_bwd_ms_a_layer"] = timed(
                    step, rows, w_in, w_out, sizes)
            except Exception as exc:
                row["refused"] = str(exc)[:300]
            print(json.dumps(row), flush=True)


def main(argv):
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"chip only: this is {device.platform}")
    print(json.dumps({"row": "device", "kind": device.device_kind}),
          flush=True)
    which = argv or ["attention", "grid", "grouped"]
    if "attention" in which:
        attention_rows()
    if "grid" in which:
        grid_rows()
    if "grouped" in which:
        grouped_rows()


if __name__ == "__main__":
    main(sys.argv[1:])
