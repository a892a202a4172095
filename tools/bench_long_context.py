"""Single-chip long-context training sweep — the long-sequence story
made quantitative on real hardware.

Long context is first-class in this framework (ring attention for the
multi-chip axis — dryrun-proven sp2 == dense; Pallas flash fwd+bwd for
the single-chip path). This sweep trains the d512/L8 flagship at
S = 1024 -> 8192 with the global token count held at 8192/step (batch
shrinks as S grows), rematerialization ON for S >= 4096 (the HBM lever
— full activations at S=8192 would not fit next to params+opt state),
and records device tokens/s + per-device HBM in use. Writes
LONGCTX.json.

The reference has no long-context capability at all (its largest
sequence dim is DeepFM's input_length=10 — SURVEY.md §5), so these are
capability numbers, not parity numbers.

Run on the TPU: python tools/bench_long_context.py
"""

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import enable_compile_cache, measure_multi_step  # noqa: E402

OUT_FILE = os.path.join(HERE, "LONGCTX.json")

# (seq_len, batch, remat): B*S = 8192 tokens/step throughout.
SWEEP = [
    (1024, 8, False),
    (2048, 4, False),
    (4096, 2, True),
    (8192, 1, True),
]
# The d1024/L12 model at the longest shapes (python tools/
# bench_long_context.py --large): params+opt ~2.1 GB f32, so remat
# everywhere past S=2048.
SWEEP_LARGE = [
    (2048, 4, True),
    (8192, 1, True),
]
STEPS_PER_TASK = 8
MEASURE_TASKS = 2


def main():
    enable_compile_cache()
    import jax

    from elasticdl_tpu.core.model_spec import get_model_spec
    from elasticdl_tpu.core.step import stack_batches
    from elasticdl_tpu.models.transformer import TransformerConfig
    from elasticdl_tpu.testing.data import model_zoo_dir

    import bench_suite

    large = "--large" in sys.argv
    sweep = SWEEP_LARGE if large else SWEEP
    # The flagship geometry comes from ONE place (the round-5 D=128
    # head flip silently stranded a local copy of these dicts on D=64;
    # sharing the zoo's WIDTHS keeps the sweep characterizing the
    # model the suite actually gates).
    size = dict(bench_suite.lm_zoo().WIDTHS[
        "transformer_l" if large else "transformer"
    ])
    dev = jax.devices()[0]
    results = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "model": "d1024/L12" if large else "d512/L8",
        "tokens_per_step": sweep[0][0] * sweep[0][1],
        "rows": [],
    }
    for seq, batch, remat in sweep:
        cfg = TransformerConfig(
            vocab_size=32768, max_len=seq, remat=remat, **size,
        )
        spec = get_model_spec(
            model_zoo_dir(), "transformer.transformer_lm.custom_model"
        )
        spec.model = spec.module.custom_model(config=cfg)
        rng = np.random.RandomState(0)

        def make_batch():
            start = rng.randint(0, cfg.vocab_size, (batch, 1))
            s = (start + np.arange(seq + 1)[None, :]) % cfg.vocab_size
            return {
                "features": s[:, :-1].astype(np.int32),
                "labels": s[:, 1:].astype(np.int32),
                "mask": np.ones((batch,), np.float32),
            }

        task = jax.device_put(stack_batches(
            [make_batch() for _ in range(STEPS_PER_TASK)]
        ))
        m = measure_multi_step(
            spec, task, batch, STEPS_PER_TASK, MEASURE_TASKS,
            compute_mfu=True,
        )
        stats = dev.memory_stats() or {}
        row = {
            "seq_len": seq,
            "batch": batch,
            "remat": remat,
            "device_ms_per_step": round(
                (m["device_ms_per_task"] or 0.0) / STEPS_PER_TASK, 3
            ),
            "tokens_per_sec_device": round(
                (m["eps_device"] or 0.0) * seq, 1
            ),
            "mfu": round(m.get("mfu") or 0.0, 4),
            # None on a backend that reports no memory_stats (the
            # CPU) — 0.0 would read as a measurement.
            "hbm_in_use_gb": (
                round(stats["bytes_in_use"] / 2**30, 3)
                if stats.get("bytes_in_use") else None
            ),
        }
        results["rows"].append(row)
        print(json.dumps(row), flush=True)

    # Keyed by model so --large merges beside the default sweep
    # (migrating the round-4 flat layout if present).
    try:
        with open(OUT_FILE) as f:
            existing = json.load(f)
        if "rows" in existing:
            existing = {existing.get("model", "d512/L8"): existing}
    except (OSError, ValueError):
        existing = {}
    existing[results["model"]] = results
    with open(OUT_FILE, "w") as f:
        json.dump(existing, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
