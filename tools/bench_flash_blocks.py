"""Flash-attention tile sweep at an exact shape, and what the kernels
cost the host at start-up. Chip only.

Usage:  python tools/bench_flash_blocks.py [B] [H] [S] [D] [--layers N]
                                            [--only sweep grid startup]

The default shape is DERIVED from ``bench_suite`` (the d512 flagship's
batch + the zoo's ``WIDTHS`` head geometry), so the sweep cannot drift
off the bench shape; the benchmark's cell is ``8 16 1024 64``,
``transformer_l`` is ``16 8 1024 128``.

One JSON line per row:

- **sweep rows** — causal forward + backward (``jit`` of ``grad`` over
  ``ops.flash_attention``) per tiling: the default block with the strip
  walk off (``sub_tile`` = the block: the whole tile, what ran before
  PR 26) and at each sub-tile edge, then smaller grid tiles. Each row
  has the block, the sub-tile, how many sub-tiles of a grid tile are
  computed (``tile_plan``), and device ms per kernel from the profiler's
  ``XLA Ops`` lane. The kernels are the program's ``tpu_custom_call``
  instructions, named from the compiled HLO and labelled forward,
  backward in the order their names number them (the order the trace
  made them; the backward is one kernel since PR 35).
  ``kernel_model_flops_frac_of_peak`` is the required work
  (``ops/flash_attention._cost``'s convention: 2 matmuls forward, 4
  backward, causal half) over the two kernels' time, over the bf16
  peak.
- **grid rows** (``"row": "grid"``), where the call has several grid
  tiles whose diagonal tiles are walked (S over one block: 2,048,
  4,096): what each part of that walk buys, the two kernels built
  from ``ops.flash_attention``'s own kernel functions and called on
  (B*H, S, D) operands: ``today`` the grid of whole tiles under the
  traced compare (what ran before PR 28); ``1`` strips in the diagonal
  tiles; ``1+2`` and no mask built in the tiles below them; ``1+2+3``
  and the dead steps' index maps naming the diagonal's tile (what
  ``flash_attention`` runs; a last row, ``shipped``, is that call
  itself). ``same_bits_as_today`` says whether o, lse, dq, dk, dv are.
  ``tools/bench_mla_moe_parts.py attention`` prints the same rows at
  latent attention's head sizes.
- **start-up rows** (``"row": "startup"``) — ``--layers`` (24) causal
  layers forward and backward in one ``jit``: seconds to trace, to
  lower, and to load or compile (``cache_entries_written`` 0 = loaded
  from the persistent cache), whole tile against the default walk.
  The layers share one trace of each kernel body
  (``flash_attention._shared_trace``), so ``trace_s`` should hardly
  grow with ``--layers``; a kernel change that breaks the sharing, or
  a body with many more ``jnp`` calls, shows here before it shows in
  the worker's ``state_init`` and ``first_program`` start-up phases,
  which ``setup_s`` holds to a bound (and where each traced ``jnp``
  call costs several times what it costs in this quiet process:
  PERF.md, PR 26).
"""

import argparse
import contextlib
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = ("sweep", "grid", "startup")
SUB_TILES = (512, 256, 128)
GRID_TILES = ((512, 512), (256, 256))
RUNS = 8


@contextlib.contextmanager
def sub_tile(flash, edge):
    """Call the kernels with another sub-tile edge (``_walk`` reads the
    module's constant when a call is made, i.e. while it is traced)."""
    old, flash.SUB_TILE = flash.SUB_TILE, edge
    try:
        yield
    finally:
        flash.SUB_TILE = old


def kernel_names(compiled):
    """The compiled program's Pallas kernels, in the order their HLO
    names number them."""
    names = set(re.findall(
        r"%?([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call",
        compiled.as_text(),
    ))
    def number(name):
        suffix = name.rsplit(".", 1)[-1]
        return int(suffix) if suffix.isdigit() else 0

    return sorted(names, key=number)


def device_ms(compiled, args, names):
    """(ms per run of each named op, ms per run of the whole program)."""
    import jax

    from benchmark.lib import trace as trace_lib

    jax.block_until_ready(compiled(*args))
    with tempfile.TemporaryDirectory(prefix="flash_sweep_") as td:
        jax.profiler.start_trace(td)
        try:
            for _ in range(RUNS):
                out = compiled(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        path = trace_lib.find_trace(td)
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        trace = trace_lib.Trace.load(path)
    ops = {}
    for _, dur, name in trace.lane("XLA Ops"):
        if name in names:
            ops[name] = ops.get(name, 0.0) + dur
    if set(ops) != set(names):
        raise RuntimeError(
            f"kernels {sorted(names)} not all on the device's XLA Ops "
            f"lane (found {sorted(ops)}); cannot report kernel time"
        )
    modules = [m[1] for m in trace.module_events()]
    return (
        [1e3 * ops[n] / RUNS for n in names],
        1e3 * float(np.median(modules)) if modules else None,
    )


GRID_PARTS = ("today", "1", "1+2", "1+2+3", "shipped")


def grid_kernels(flash, part, rows, scale, block):
    """fn(q, k, v, do) -> (o, lse, dq, dk, dv) on (B*H, S, D) operands:
    the forward and backward kernels of a causal call over a square
    grid of ``block`` tiles, as ``part`` of GRID_PARTS builds them from
    the module's kernel functions. Part 1 alone keeps today's tile
    below the diagonal: masked by a compare of iotas that every score of
    it passes."""
    import functools

    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    sub = block // len(rows)

    def fwd_1(q_ref, k_ref, v_ref, o_ref, l_ref, m_acc, l_acc, o_acc):
        qi, kb = pl.program_id(1), pl.program_id(2)

        @pl.when(kb == 0)
        def _init():
            m_acc[:] = jnp.full_like(m_acc, flash._NEG_INF)
            l_acc[:] = jnp.zeros_like(l_acc)
            o_acc[:] = jnp.zeros_like(o_acc)

        @pl.when(kb < qi)
        def _below():
            flash._scratch_tile_update(
                q_ref, k_ref, v_ref, m_acc, l_acc, o_acc, qi * block,
                kb * block, block_k=block, causal=True, scale=scale)

        @pl.when(kb == qi)
        def _diagonal():
            flash._fwd_strips_kernel(
                q_ref, k_ref, v_ref, o_ref, l_ref, rows=rows, sub=sub,
                scale=scale, carried=(m_acc, l_acc, o_acc))

    def bwd_1(qoff, koff, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              dq_ref, dk_ref, dv_ref, dk_acc, dv_acc):
        ki, qt = pl.program_id(1), pl.program_id(2)
        refs = (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_acc, dv_acc)
        flash._zero_dq_tile(dq_ref, ki, qt, block)

        @pl.when(qt == ki)
        def _diagonal():
            flash._bwd_strips_kernel(
                *refs, rows=rows, sub=sub, q_offset=0, k_offset=0,
                scale=scale, q_tile=qt)

        @pl.when(qt > ki)
        def _below():
            flash._bwd_tile_update(*refs, qt, qt * block, ki * block, True,
                                   scale)

        @pl.when(qt == pl.num_programs(2) - 1)
        def _flush():
            dk_ref[0] = dk_acc[:]
            dv_ref[0] = dv_acc[:]

    walked = dict(rows=rows, sub=sub, scale=scale)
    fwd_kernel, bwd_kernel = {
        "1": (fwd_1, bwd_1),
    }.get(part, tuple(
        functools.partial(kernel, **walked) for kernel in (
            flash._fwd_grid_kernel, flash._bwd_grid_kernel)))
    clamped = part == "1+2+3"
    kv_tile = jnp.minimum if clamped else flash._streamed
    q_tile = jnp.maximum if clamped else flash._streamed

    module = part in ("today", "shipped")
    plan = rows if part == "shipped" else None

    def kernels(q, k, v, do):
        if module:
            o, lse = flash._forward_call(q, k, v, True, scale, block, block,
                                         plan, False)
        else:
            o, lse = flash._grid_forward(fwd_kernel, kv_tile, q, k, v, block,
                                         block, True, False)
        delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
            axis=-1, keepdims=True)
        if module:
            grads = flash._tiles_grads(q, k, v, do, lse, delta, 0, 0, True,
                                       scale, block, block, plan, False)
        else:
            grads = flash._grid_grads(
                bwd_kernel, q_tile, q, k, v, do, lse, delta, 0, 0, block,
                block, True, False)
        return (o, lse, *grads)

    return kernels


def grid_rows(flash, shape_name, bh, s, d, dv):
    """One ``"row": "grid"`` line for each of GRID_PARTS at this shape,
    or nothing where the call's diagonal tiles are not walked."""
    import jax
    import jax.numpy as jnp

    plan = flash.tile_plan(s, s)
    if plan.grid == (1, 1) or plan.rows == (1,):
        return
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16) for _ in range(2))
    v, do = (jnp.asarray(rng.randn(bh, s, dv), jnp.bfloat16)
             for _ in range(2))
    today = None
    for part in GRID_PARTS:
        compiled = jax.jit(grid_kernels(
            flash, part, plan.rows, d ** -0.5, plan.block_q,
        )).lower(q, k, v, do).compile()
        names = kernel_names(compiled)
        if len(names) != 2:
            raise RuntimeError(f"expected two kernels, found {names}")
        outputs = compiled(q, k, v, do)
        today = today or outputs
        gaps = {
            name: (bool(jnp.array_equal(got, want)),
                   float(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)).max()))
            for name, got, want
            in zip(("o", "lse", "dq", "dk", "dv"), outputs, today)
        }
        per_kernel, program = device_ms(compiled, (q, k, v, do), names)
        print(json.dumps({
            "row": "grid", "shape": shape_name, "head_sizes": [d, dv],
            "part": part, "plan": plan.describe(),
            "device_ms": {
                "fwd": round(per_kernel[0], 4),
                "bwd": round(per_kernel[1], 4),
                "kernels": round(sum(per_kernel), 4),
                "program": program and round(program, 4),
            },
            "same_bits_as_today": {n: same for n, (same, _) in gaps.items()},
            "max_abs_gap_to_today": {n: gap for n, (_, gap) in gaps.items()},
        }), flush=True)


def main(argv=None):
    import jax
    import jax.numpy as jnp

    from benchmark.lib import peaks
    from elasticdl_tpu.common.jax_env import enable_compile_cache
    from elasticdl_tpu.ops import flash_attention as flash

    parser = argparse.ArgumentParser()
    parser.add_argument("shape", nargs="*", type=int)
    parser.add_argument("--layers", type=int, default=24)
    parser.add_argument("--only", nargs="+", default=list(ROWS), choices=ROWS,
                        help="which kinds of row to print")
    args = parser.parse_args(argv)
    cache_dir = enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"device times come from a chip; this is {device.platform}")
    import bench_suite

    sizes = bench_suite.lm_zoo().WIDTHS["transformer"]
    default_shape = [
        bench_suite.CONFIGS["transformer"][1],       # bench batch
        sizes["n_heads"],
        bench_suite.lm_zoo().SEQ_LEN,
        sizes["d_model"] // sizes["n_heads"],        # head dim (128)
    ]
    b, h, s, d = (args.shape + default_shape[len(args.shape):])[:4]
    shape_name = f"B{b}/H{h}/S{s}/D{d}"

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
               for _ in range(3))
    # 2*BHSSD a matmul, 6 required matmuls (fwd QK, PV; bwd dP, dQ, dK,
    # dV), causal half: the two kernels' ``_cost`` together.
    model_flops = 12 * b * h * s * s * d * 0.5
    peak = peaks.peak(device.device_kind, "bf16_flops_per_s")

    def grads(block_q, block_k, layers=1):
        def loss(q, k, v):
            x = q
            for _ in range(layers):
                x = flash.flash_attention(
                    x, k, v, causal=True, block_q=block_q, block_k=block_k
                )
            return jnp.sum(x.astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    block = flash.tile_plan(s, s).block_q
    tilings = [(0, 0, block)] + [
        (0, 0, edge) for edge in SUB_TILES
        if block == s and block % edge == 0 and block >= 2 * edge
    ] + [(bq, bk, block) for bq, bk in GRID_TILES if s > bq and s % bq == 0]
    for block_q, block_k, edge in tilings if "sweep" in args.only else ():
        with sub_tile(flash, edge):
            plan = flash.tile_plan(s, s, True, block_q, block_k)
            compiled = grads(block_q, block_k).lower(q, k, v).compile()
        names = kernel_names(compiled)
        if len(names) != 2:
            raise RuntimeError(f"expected two kernels, found {names}")
        per_kernel, program = device_ms(compiled, (q, k, v), names)
        total = sum(per_kernel)
        print(json.dumps({
            "row": "sweep", "shape": shape_name,
            "block": [plan.block_q, plan.block_k],
            "sub_tile": [plan.sub_q, plan.sub_k],
            "computed": plan.computed, "total": plan.total,
            "device_ms": {
                "fwd": round(per_kernel[0], 4),
                "bwd": round(per_kernel[1], 4), "kernels": round(total, 4),
                "program": program and round(program, 4),
            },
            "kernel_model_flops_frac_of_peak": round(
                model_flops / (total / 1e3) / peak, 4),
        }), flush=True)

    if "grid" in args.only:
        grid_rows(flash, shape_name, b * h, s, d, d)

    def entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    seen = set()
    for edge in (block, flash.SUB_TILE) if "startup" in args.only else ():
        with sub_tile(flash, edge):
            plan = flash.tile_plan(s, s)
            if plan in seen:    # several grid tiles: one tiling only
                continue
            seen.add(plan)
            before = entries()
            t0 = time.perf_counter()
            traced = grads(0, 0, args.layers).trace(q, k, v)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            lowered.compile()
            t3 = time.perf_counter()
        print(json.dumps({
            "row": "startup", "shape": shape_name, "layers": args.layers,
            "sub_tile": [plan.sub_q, plan.sub_k],
            "computed": plan.computed, "total": plan.total,
            "trace_s": round(t1 - t0, 3), "lower_s": round(t2 - t1, 3),
            "load_or_compile_s": round(t3 - t2, 3),
            "cache_entries_written": entries() - before,
        }), flush=True)


if __name__ == "__main__":
    main()
