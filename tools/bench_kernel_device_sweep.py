"""DEVICE-TIME kernel-vs-XLA sweep for the embedding ops.

Round-3 replacement for the retired wall-clock sweep
(tools/bench_embedding_sweep.py): every number here is per-program
device execution time read off the profiler trace
(benchlib.module_device_times), so host dispatch
cannot contaminate the comparison — the flaw that made the round-2
sweep report physically impossible rates (0.017 ms for 65k x 1 KB row
reads = 3.8 TB/s) and a phantom 1.44-3.12x kernel win.

Measures, at production-like sizes over a 1M-row table:
  - lookup_combine: force_pallas vs force_xla,
  - sparse_apply (Adagrad): use_pallas always vs never, with the table
    state DONATED and threaded between calls (without donation both
    paths degrade to full-table copies and the comparison is
    meaningless — the round-2 harness also missed this),
  - the FUSED scatter-apply family (use_pallas="fused", SGD/Momentum —
    ops/pallas_embedding.fused_*_scatter_apply): the on-chip numbers
    the ROADMAP's pending dispatch-flip decision needs
    (``use_pallas_apply`` stays False until this sweep shows a win on
    real hardware). Same donated-and-threaded protocol.

Writes EMBEDDING_SWEEP.json. Run on the TPU, nothing else on the host.
``--lookup-only`` / ``--fused-only`` re-measure one section and merge
over the previous file (single-section runs fit a session timeout).
"""

import json
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import enable_compile_cache, module_device_times  # noqa: E402

OUT_FILE = os.path.join(HERE, "EMBEDDING_SWEEP.json")
VOCAB = 1_000_000


def device_ms(run, args, reps=10, donate_state=False):
    """Median per-program device ms over ``reps`` traced calls."""
    import jax

    out = None
    state = args
    for _ in range(3):
        out = run(*state)
        if donate_state:
            state = (*out, *args[len(out):])
    jax.block_until_ready(out)
    td = tempfile.mkdtemp(prefix="sweep_")
    jax.profiler.start_trace(td)
    for _ in range(reps):
        out = run(*state)
        if donate_state:
            state = (*out, *args[len(out):])
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    times = module_device_times(td, name_filter="jit_")
    return float(np.median(times)) if times else float("nan")


def _merge_previous(results, keep_sections):
    """Carry ``keep_sections`` over from the previous OUT_FILE so a
    single-section re-measure doesn't clobber the rest."""
    try:
        with open(OUT_FILE) as f:
            prev = json.load(f)
        for section in keep_sections:
            results[section] = prev.get(section, [])
        return True
    except (OSError, ValueError) as exc:
        print(f"WARNING: previous {OUT_FILE} unreadable ({exc}); "
              f"section(s) {keep_sections} will be EMPTY — re-run the "
              "full sweep to restore them", file=sys.stderr)
        return False


def sweep(lookup_only=False, fused_only=False):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.embedding.optimizer import (
        Adagrad,
        Momentum,
        SGD,
        init_slot_tables,
        sparse_apply,
    )
    from elasticdl_tpu.ops import pallas_embedding as pe

    rng = np.random.RandomState(0)
    results = {"platform": jax.devices()[0].platform,
               "device_kind": getattr(jax.devices()[0], "device_kind", ""),
               "method": "per-program device time off the profiler "
                         "trace (benchlib.module_device_times); update "
                         "path donated+threaded",
               "lookup": [], "sparse_update": [],
               "fused_sparse_update": []}

    def fused_section():
        """use_pallas='fused' (block-pipelined scatter-apply kernels)
        vs the XLA path, SGD + Momentum, donated and threaded."""
        dim = 256
        for opt_name, opt in (("sgd", SGD(lr=0.05)),
                              ("momentum", Momentum(lr=0.05))):
            for n in [256, 4096, 16384]:
                ids = np.unique(
                    rng.randint(0, VOCAB, n)
                ).astype(np.int32)
                padded = jnp.asarray(
                    np.concatenate([ids, [VOCAB]], 0), jnp.int32
                )
                grads = jnp.asarray(
                    rng.randn(len(ids) + 1, dim).astype(np.float32)
                )

                def mk(mode):
                    def f(t, s, i, g):
                        t2, s2 = sparse_apply(
                            opt, t, s, i, g, step=1, use_pallas=mode,
                        )
                        return t2, s2
                    return jax.jit(f, donate_argnums=(0, 1))

                def fresh():
                    return (
                        jnp.asarray(
                            rng.randn(VOCAB, dim).astype(np.float32)
                        ),
                        init_slot_tables(opt, VOCAB, dim),
                    )

                table, slots = fresh()
                k = device_ms(mk("fused"), (table, slots, padded, grads),
                              donate_state=True)
                table, slots = fresh()
                x = device_ms(mk("never"), (table, slots, padded, grads),
                              donate_state=True)
                row = {"opt": opt_name, "dim": dim,
                       "rows": int(len(ids)), "vocab": VOCAB,
                       "fused_ms": round(k, 4), "xla_ms": round(x, 4),
                       "fused_speedup": round(x / k, 4) if k else None}
                results["fused_sparse_update"].append(row)
                print(json.dumps(row), flush=True)
                del table

    if fused_only:
        _merge_previous(results, ("lookup", "sparse_update"))
        fused_section()
        with open(OUT_FILE, "w") as f:
            json.dump(results, f, indent=1)
        return 0

    for dim, L, B in [(256, 32, 64), (256, 32, 512), (256, 64, 1024),
                      (512, 64, 1024)]:
        table = jnp.asarray(rng.randn(VOCAB, dim).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, VOCAB, (B, L)), jnp.int32)
        w = jnp.ones((B, L), jnp.float32)

        def mk(fp):
            def f(t, i, ww):
                return pe.lookup_combine(
                    t, i, ww, "sum", force_pallas=fp, force_xla=not fp
                )
            return jax.jit(f)

        def aligned(t, i, ww):
            return pe.lookup_combine_aligned(t, i, ww, "sum")

        k = device_ms(mk(True), (table, ids, w))
        x = device_ms(mk(False), (table, ids, w))
        a = device_ms(jax.jit(aligned), (table, ids, w))
        row = {"dim": dim, "L": L, "batch": B, "vocab": VOCAB,
               "pallas_ms": round(k, 4), "xla_ms": round(x, 4),
               "aligned_ms": round(a, 4),
               "pallas_speedup": round(x / k, 4) if k else None,
               "aligned_speedup": round(x / a, 4) if a else None}
        results["lookup"].append(row)
        print(json.dumps(row), flush=True)
        del table

    if lookup_only:
        # Merge over the previous full run so the update sections
        # survive a lookup-only re-measure (single-section runs fit the
        # session command timeout).
        _merge_previous(
            results, ("sparse_update", "fused_sparse_update")
        )
        with open(OUT_FILE, "w") as f:
            json.dump(results, f, indent=1)
        return 0

    dim = 256
    opt = Adagrad(lr=0.05)
    for n in [256, 4096, 16384]:
        table = jnp.asarray(rng.randn(VOCAB, dim).astype(np.float32))
        slots = init_slot_tables(opt, VOCAB, dim)["accumulator"]
        ids = np.unique(rng.randint(0, VOCAB, n)).astype(np.int32)
        padded = jnp.asarray(np.concatenate([ids, [VOCAB]], 0), jnp.int32)
        grads = jnp.asarray(
            rng.randn(len(ids) + 1, dim).astype(np.float32)
        )

        def mk(mode):
            def f(t, s, i, g):
                t2, s2 = sparse_apply(
                    opt, t, {"accumulator": s}, i, g, step=1,
                    use_pallas=mode,
                )
                return t2, s2["accumulator"]
            return jax.jit(f, donate_argnums=(0, 1))

        k = device_ms(mk("always"), (table, slots, padded, grads),
                      donate_state=True)
        table = jnp.asarray(rng.randn(VOCAB, dim).astype(np.float32))
        slots = init_slot_tables(opt, VOCAB, dim)["accumulator"]
        x = device_ms(mk("never"), (table, slots, padded, grads),
                      donate_state=True)
        row = {"dim": dim, "rows": int(len(ids)), "vocab": VOCAB,
               "pallas_ms": round(k, 4), "xla_ms": round(x, 4),
               "pallas_speedup": round(x / k, 4) if k else None}
        results["sparse_update"].append(row)
        print(json.dumps(row), flush=True)
        del table

    fused_section()

    with open(OUT_FILE, "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(sweep(lookup_only="--lookup-only" in sys.argv,
                   fused_only="--fused-only" in sys.argv))
