"""Multi-config benchmark suite over the BASELINE.md target configs.

BASELINE.md defines five self-measured configs (the reference publishes no
numbers): mnist CNN, cifar10 CNN, resnet50 (224x224), DeepFM sparse ids, and
census wide&deep mixed dense+sparse. ``bench.py`` stays the driver's
single-line metric (mnist); this suite is the breadth harness: it measures
examples/sec/chip for every config through the same task-granular execution
path (core/step.build_multi_step — N fused optimizer steps per XLA program,
harness shared via benchlib.py) and records per-config regression floors.

Usage:
    python bench_suite.py               # all configs
    python bench_suite.py mnist deepfm  # a subset

Prints one JSON line per config and merges results into BENCH_SUITE.json;
the first TPU run of each config also records a floor in
BENCH_SUITE_FLOOR.json (both gitignored — machine-local measurements, not
source). Job-level elasticity (throughput under preemption) is measured
separately by bench_elasticity.py.
"""

import json
import os
import sys

import numpy as np

from benchlib import (
    enable_compile_cache,
    load_json,
    make_mnist_batch,
    measure_multi_step,
    merge_json,
)

# Regression-gate bands over the floor medians (BASELINE.md "Floor
# re-baseline", round 3): device rate showed <2% spread, so its band
# is tight; wall rate also carries host dispatch (±12% observed), so
# its band stays the round-2 0.85. On TPU the gate uses the device
# rate; wall is recorded evidence.
DEVICE_BAND = 0.95
WALL_BAND = 0.85

HERE = os.path.dirname(os.path.abspath(__file__))
FLOOR_FILE = os.path.join(HERE, "BENCH_SUITE_FLOOR.json")
OUT_FILE = os.path.join(HERE, "BENCH_SUITE.json")

# name -> (zoo model_def, batch, steps_per_task, measure_tasks)
# 128 fused steps/task for the sub-3ms-step configs: per-program host
# dispatch was 15-20% of program wall at round 2's 32-step programs
# (cifar10's ±12% swings). 128 steps puts program wall at ~300ms;
# production amortizes the same way via num_minibatches_per_task +
# fuse_task_steps. The regression gate additionally uses device time
# (benchlib.module_device_times), which dispatch cannot touch at all.
CONFIGS = {
    "mnist": ("mnist.mnist_functional.custom_model", 512, 128, 2),
    "cifar10": ("cifar10.cifar10_functional.custom_model", 256, 128, 2),
    # batch 128: best of the measured 64/128/256 sweep (2089/2154/2063
    # ex/s) — wider batches feed the MXU better until HBM pressure.
    # ~74ms steps: 4 fused steps is already a ~300ms program.
    "resnet50": ("resnet50.resnet50.custom_model", 128, 4, 1),
    "deepfm": ("deepfm.deepfm_functional.custom_model", 512, 128, 2),
    "census": ("census.census_wide_deep.custom_model", 512, 128, 2),
    # Flagship LM (net-new vs the reference): GPT-style blocks at a
    # realistic small-LM size; seq 1024 engages the Pallas flash
    # attention kernels (fwd + bwd). Reported in tokens/sec
    # (= examples x seq). Fused-task programs amortize host->device
    # dispatch (measured +17%/+26% at 16/32 steps over 4-step tasks
    # — the reference tunes the same knob as
    # num_minibatches_per_task). batch 16: sweep-confirmed at BOTH head
    # geometries (D=64 round 4: B8 42.4/B16 43.1/B32 39.7% MFU; D=128
    # round 5: B8 373.0k/B16 378.0k/B32 380.3k tok/s device — B32's
    # +0.6% is under the <2% device noise floor, B16 stands).
    "transformer": ("transformer.transformer_lm.transformer", 16, 16, 2),
    # Large-LM edition (d1024/H8(D128)/L12/ff4096): bigger matmuls
    # stretch the MXU where the d512 flagship is dispatch/HBM-shaped —
    # the config that shows the framework's MFU headroom at sizes
    # closer to real LM training. B16: the D=64-era "activation
    # pressure at B16" negative FLIPPED at D=128 heads (B8 107.0k vs
    # B16 109.8k tok/s device, 64.5% vs 66.2% MFU — fewer, wider heads
    # shrink the attention intermediates); steps halved so tokens/task
    # stays 65k. Few steps/task: each step is ~6x the d512 cost, so
    # dispatch amortization needs less fusing.
    "transformer_l": ("transformer.transformer_lm.transformer_l", 16, 4, 2),
    # Large-recsys flagship: 1M x 256 table trained through the
    # device-tier sparse plane (embedding/device_sparse.py) — row grads
    # for only the touched ids, scatter-apply, no dense (V, D) gradient.
    # The suite also measures the dense-embedding control (same model,
    # flax Embed + dense optimizer) and records the sparse/dense ratio.
    "recsys": ("recsys.recsys_sparse.custom_model", 512, 64, 2),
    # Switch-style MoE LM (net-new axis, VERDICT r4 #4): the d512
    # flagship with every 2nd MLP replaced by an 8-expert top-1 routed
    # layer under CAPACITY-SCATTER dispatch (models/transformer.py
    # _scatter_dispatch — one-hot-cumsum ranking, (E, C, D) scatter,
    # batched expert FFN, gather-combine). One chip = no ep all-to-all;
    # what this config times is the dispatch machinery itself against
    # the dense einsum the same model would otherwise run. Device sweep
    # (round 5): B8 257k / B16 265k / B32 246k tok/s at cf 1.25 — B16
    # stands; capacity factor 1.0/1.25/2.0 measured 271k/265k/245k —
    # cf 1.0 is +2.3% rate but drops more tokens (a quality trade), so
    # the config keeps the Switch-canonical 1.25. (MFU RISES with cf —
    # 38.4/39.3/41.0% — because capacity padding adds counted FLOPs;
    # token rate is the honest metric for this row.)
    "moe": ("transformer.transformer_lm.moe", 16, 16, 2),
}

# The LM widths live in the zoo module (WIDTHS there), so the suite,
# `--model_def` on a worker and chip_smoke.py build one model from one
# definition. head_dim 128 = the MXU/lane width: the round-5
# head-geometry sweep measured D=64 heads at HALF the attention-kernel
# throughput (d512: H8/D64 304.6k vs H4/D128 378.0k tok/s device,
# 43.1% -> 53.5% MFU; d1024: H16/D64 88.4k vs H8/D128 107.0k, 53.3% ->
# 64.5% MFU; H2/D256 only +1.5% more — diminishing). Flash 1024x1024
# blocks re-confirmed best at D=128 (1.231 ms fwd+bwd at the bench
# shape, vs 2.529 at D=64).


def lm_zoo():
    from model_zoo.transformer import transformer_lm

    return transformer_lm


def _is_lm(name: str) -> bool:
    """Configs that run the transformer zoo model (token-rate units,
    LM batch shape): the transformer/transformer_l flagships plus the
    MoE variant."""
    return CONFIGS[name][0].startswith("transformer.")


def _make_batch(name, batch, rng):
    if name == "mnist":
        return make_mnist_batch(batch, rng)
    if name == "cifar10":
        labels = rng.randint(0, 10, batch).astype(np.int32)
        features = rng.rand(batch, 32, 32, 3).astype(np.float32)
    elif name == "resnet50":
        labels = rng.randint(0, 10, batch).astype(np.int32)
        features = rng.rand(batch, 224, 224, 3).astype(np.float32)
    elif name == "deepfm":
        from model_zoo.deepfm import deepfm_functional as m

        labels = rng.randint(0, 2, batch).astype(np.int32)
        features = rng.randint(
            0, m.MAX_ID, (batch, m.INPUT_LENGTH)
        ).astype(np.int32)
    elif _is_lm(name):
        lm = lm_zoo()
        start = rng.randint(0, lm.VOCAB, (batch, 1))
        seq = (start + np.arange(lm.SEQ_LEN + 1)[None, :]) % lm.VOCAB
        labels = seq[:, 1:].astype(np.int32)
        features = seq[:, :-1].astype(np.int32)
    elif name == "census":
        from model_zoo.census import census_wide_deep as m

        labels = rng.randint(0, 2, batch).astype(np.int32)
        num_cols = len(m.FEATURE_GROUP.columns)
        features = {
            "ids": rng.randint(
                0, m.FEATURE_GROUP.total_buckets, (batch, num_cols)
            ).astype(np.int32),
            "dense": rng.rand(batch, len(m.NUMERIC_KEYS)).astype(np.float32),
        }
    elif name == "recsys":
        from model_zoo.recsys import recsys_sparse as m

        labels = rng.randint(0, 2, batch).astype(np.int32)
        features = {
            m.FEATURE_KEY: rng.randint(
                0, m.VOCAB, (batch, m.INPUT_LENGTH)
            ).astype(np.int64),
        }
    else:
        raise ValueError(name)
    return {
        "features": features,
        "labels": labels,
        "mask": np.ones((batch,), np.float32),
    }


def config_spec(name):
    """(spec, batch, steps, measure_tasks) with every bench-side spec
    fixup applied — the ONE place run_config and the measurement tools
    (benchlib.load_config_spec) get their spec, so a tool can never
    profile a different model than the suite measures."""
    from elasticdl_tpu.core.model_spec import get_model_spec
    from elasticdl_tpu.testing.data import model_zoo_dir

    model_def, batch, steps, measure_tasks = CONFIGS[name]
    spec = get_model_spec(model_zoo_dir(), model_def)
    if name == "recsys":
        # Bench-side EXPLICIT opt-in to the packed-slot layout (+37%
        # measured, BASELINE.md round-5) — the zoo factory defaults to
        # the split layout so production checkpoints stay compatible
        # with the row-sharded/elastic-relaunch runners.
        import functools

        spec.make_sparse_runner = functools.partial(
            spec.make_sparse_runner, packed_slots=True
        )
    return spec, batch, steps, measure_tasks


def run_config(name):
    """Measure one config; returns the benchlib.measure_multi_step dict
    with transformer rates scaled to tokens/sec. The sparse recsys
    config also carries its paired dense-embedding control
    (``rate_dense``/``rate_dense_device``/``sparse_speedup_vs_dense``)
    — the committed evidence for the sparse plane's architectural
    win."""
    import jax

    from elasticdl_tpu.core.step import stack_batches

    spec, batch, steps, measure_tasks = config_spec(name)
    rng = np.random.RandomState(0)
    task = jax.device_put(
        stack_batches([_make_batch(name, batch, rng) for _ in range(steps)])
    )
    measured = measure_multi_step(
        spec, task, batch, steps, measure_tasks, compute_mfu=True
    )
    if _is_lm(name):
        for key in ("eps", "eps_median", "eps_device"):
            measured[key] *= lm_zoo().SEQ_LEN  # examples -> tokens/sec
    if name == "recsys":
        # Paired dense-embedding control (same model, table as a flax
        # Embed under the dense optimizer): the ratio is the sparse
        # plane's architectural win — no dense (V, D) gradient, no
        # full-table optimizer traffic. (The Pallas-vs-XLA kernel
        # comparison lives in tools/bench_kernel_device_sweep.py /
        # EMBEDDING_SWEEP.json; auto-dispatch takes XLA — see
        # ops/pallas_embedding.py round-3 note.)
        import dataclasses

        dense_spec = dataclasses.replace(
            spec, model=spec.module.dense_model(),
            make_sparse_runner=None,
        )
        dense = measure_multi_step(
            dense_spec, task, batch, steps, measure_tasks,
            compute_mfu=False,
        )
        measured["rate_dense"] = round(dense["eps"], 2)
        measured["rate_dense_device"] = round(dense["eps_device"], 2)
        if dense["eps_device"] and measured["eps_device"]:
            measured["sparse_speedup_vs_dense"] = round(
                measured["eps_device"] / dense["eps_device"], 4
            )
    return measured


def main():
    import jax

    argv = sys.argv[1:]
    check_floors = "--check-floors" in argv
    names = [a for a in argv if not a.startswith("--")] or list(CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        raise SystemExit(f"unknown configs {unknown}; pick from {list(CONFIGS)}")

    enable_compile_cache()
    platform = jax.devices()[0].platform
    floors = load_json(FLOOR_FILE, {})

    def floor_entry(name):
        """The recorded floor, or {} when absent or STALE — a floor
        measured on a different harness granularity (steps/batch) or
        batch does not bound the current one; comparing across would
        silently neuter (or falsely trip) the gate."""
        entry = floors.get(name) or {}
        if not entry:
            return {}
        _, batch, steps, _ = CONFIGS[name]
        # Strict equality: a legacy entry with no recorded steps/batch
        # predates this harness and cannot be assumed comparable.
        if entry.get("steps") != steps or entry.get("batch") != batch:
            print(json.dumps({
                "config": name,
                "stale_floor": "harness changed "
                               f"(floor steps={entry.get('steps')} "
                               f"batch={entry.get('batch')}); reseeding",
            }), file=sys.stderr)
            return {}
        return entry

    def gate(name, measured):
        """(vs_floor, gate_kind). On a chip the gate is the device
        rate against the floor's device reading, and a floor without
        one is an error, not a reason to compare wall clocks. "none"
        means this machine holds no floor yet and the run seeds one
        (with --check-floors that is an error too). Wall gating exists
        for the CPU smoke only."""
        entry = floor_entry(name)
        if not entry:
            if check_floors and platform != "cpu":
                raise RuntimeError(
                    f"--check-floors: no floor recorded for {name!r} in "
                    f"{FLOOR_FILE}"
                )
            return 1.0, "none"
        if platform != "cpu":
            floor_dev = entry.get("rate_device")
            if not floor_dev:
                raise RuntimeError(
                    f"floor for {name!r} has no device reading; "
                    "re-derive it with tools/record_floor_readings.py"
                )
            return measured["eps_device"] / floor_dev, "device"
        floor = entry.get("rate", entry.get("examples_per_sec"))
        if floor:
            return measured["eps"] / floor, "wall"
        return 1.0, "none"

    results = {}
    for name in names:
        measured = run_config(name)
        unit = (
            "tokens/sec/chip" if _is_lm(name)
            else "examples/sec/chip"
        )
        vs, gate_kind = gate(name, measured)
        if vs < 1.0 and platform != "cpu":
            # One re-measurement before declaring a regression (a stray
            # partial event at the trace boundary can leak into one
            # reading); a real regression persists across both runs.
            remeasured = run_config(name)
            vs2, _ = gate(name, remeasured)
            if vs2 > vs:
                measured, vs = remeasured, vs2
        if not floor_entry(name) and platform != "cpu":
            # Provisional floor from this first clean run (also replaces
            # a stale-harness floor); the recorded procedure is to
            # overwrite it with the median of >= 5 isolated readings
            # (tools/record_floor_readings.py).
            floors[name] = {
                "rate": round(measured["eps"] * WALL_BAND, 2),
                "rate_device": round(
                    measured["eps_device"] * DEVICE_BAND, 2
                ) or None,
                "unit": unit, "platform": platform,
                "batch": CONFIGS[name][1],
                "steps": CONFIGS[name][2],
                "rebaselined_from_rate": round(measured["eps"], 2),
                "n_readings": 1,
                "procedure": f"PROVISIONAL single first-run reading x "
                             f"{WALL_BAND} wall / {DEVICE_BAND} device "
                             f"band; re-derive with "
                             f"tools/record_floor_readings.py",
            }
        results[name] = {
            "rate": round(measured["eps"], 2),
            "rate_device": round(measured["eps_device"], 2),
            "device_ms_per_task": measured["device_ms_per_task"],
            "wall_spread": round(measured["wall_spread"], 4),
            "vs_floor": round(vs, 4), "gate": gate_kind,
            "unit": unit, "platform": platform,
            "mfu": round(measured.get("mfu", 0.0), 4),
            "tflops_per_sec": round(
                measured.get("tflops_per_sec", 0.0), 2
            ),
            # HBM roofline companion (benchlib.program_cost): the
            # efficiency statement for embedding-bound configs.
            "hbm_frac": round(measured.get("hbm_frac", 0.0), 4),
            "hbm_gbps": round(measured.get("hbm_gbps", 0.0), 2),
            "bytes_per_step": measured.get("bytes_per_step", 0.0),
        }
        for extra in ("rate_dense", "rate_dense_device",
                      "sparse_speedup_vs_dense"):
            if extra in measured:
                results[name][extra] = measured[extra]
        print(json.dumps({
            "metric": f"{name}_train_{unit.split('/')[0]}_per_sec_per_chip"
                      f"[{platform}]",
            "value": round(measured["eps"], 2),
            "unit": unit,
            "vs_baseline": round(vs, 4),
            "mfu": round(measured.get("mfu", 0.0), 4),
            "hbm_frac": round(measured.get("hbm_frac", 0.0), 4),
            "rate_device": round(measured["eps_device"], 2),
            "gate": gate_kind,
        }))

    if platform != "cpu":
        with open(FLOOR_FILE, "w") as f:
            json.dump(floors, f, indent=1)
    merge_json(OUT_FILE, results)

    if check_floors:
        failed = {
            n: r["vs_floor"] for n, r in results.items()
            if r["vs_floor"] < 1.0
        }
        if failed:
            print(json.dumps({"floor_failures": failed}), file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
