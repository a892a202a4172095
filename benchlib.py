"""Shared measurement harness for bench.py / bench_suite.py.

One implementation of batch synthesis, the warmup/median measurement loop,
and floor-file bookkeeping so the driver bench (bench.py) and the breadth
suite (bench_suite.py) can't drift apart.

Measurement-integrity design (round 3): per-dispatch host latency
swings run to run (observed ±12% back-to-back on sub-ms-step configs —
BASELINE.md "Floor re-baseline"). Three defenses, all applied:

1. **Device-time rate**: one measuring round runs under
   ``jax.profiler`` and the per-program device execution time is read
   off the trace's "XLA Modules" lane (``module_device_times``; lane
   names checked against a libtpu 0.0.34 trace on a v5e). Device time
   is what the framework controls, so it is the regression-gating
   metric on TPU; wall rate is recorded alongside.
2. **Big fused programs**: dispatch-bound configs fuse 128 steps per
   XLA program (bench_suite CONFIGS), putting per-program wall at
   ~300ms, where round 2's 32-step programs spent ~15-20% in dispatch.
3. **Min-of-rounds wall estimator**: host noise is one-sided
   (contention only ever adds time), so the minimum over
   ``measure_rounds`` timed rounds estimates the true sustained rate;
   the spread across rounds is recorded as evidence.

A run off the CPU that cannot find what it measures fails: an unknown
device has no peak, and a trace without a device lane has no device
time. Only the CPU smoke runs without them.
"""

import glob
import gzip
import json
import os
import tempfile
import time

import numpy as np

from elasticdl_tpu.common.jax_env import enable_compile_cache  # noqa: F401


def make_mnist_batch(batch, rng, flat=False):
    """Label-correlated pixels (same scheme as
    testing.data.create_mnist_record_file) so measured steps are healthy
    training, not divergence to inf/nan."""
    labels = rng.randint(0, 10, batch).astype(np.int32)
    images = rng.rand(batch, 28 * 28).astype(np.float32) * 0.125
    block = (28 * 28) // 10
    for i, label in enumerate(labels):
        images[i, label * block:(label + 1) * block] += 0.75
    features = images if flat else images.reshape(batch, 28, 28)
    return {
        "features": features,
        "labels": labels,
        "mask": np.ones((batch,), np.float32),
    }


# Peak dense-matmul throughput per chip (bf16), for MFU accounting.
# Sources: public TPU spec sheets; device_kind prefixes as reported by
# jax.devices()[0].device_kind.
PEAK_BF16_FLOPS = (
    ("TPU v5 lite", 197e12),   # v5e
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v5", 459e12),
    ("TPU v4", 275e12),
    ("TPU v6", 918e12),        # Trillium
)


def _peak(table, device) -> float:
    kind = getattr(device, "device_kind", "") or ""
    for prefix, peak in table:
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no peak recorded for device kind {kind!r}; add it to the "
        "benchlib tables with its source"
    )


def peak_flops(device) -> float:
    return _peak(PEAK_BF16_FLOPS, device)


# Peak HBM bandwidth per chip (bytes/sec), for roofline accounting on
# embedding-bound configs (MFU is meaningless there — the honest
# efficiency metric is fraction of memory bandwidth). Public spec-sheet
# numbers, same prefix scheme as PEAK_BF16_FLOPS.
PEAK_HBM_BYTES_PER_SEC = (
    ("TPU v5 lite", 819e9),    # v5e
    ("TPU v5e", 819e9),
    ("TPU v5p", 2765e9),
    ("TPU v5", 2765e9),
    ("TPU v4", 1228e9),
    ("TPU v6", 1640e9),        # Trillium
)


def peak_hbm_bw(device) -> float:
    return _peak(PEAK_HBM_BYTES_PER_SEC, device)


def load_config_spec(name):
    """(spec, batch, steps, measure_tasks) for a bench_suite config —
    delegates to bench_suite.config_spec so tools always measure the
    exact spec (transformer sizes, recsys packed layout) the suite
    gates on."""
    import bench_suite

    return bench_suite.config_spec(name)


def load_config_harness(name, seed=0, spec_parts=None):
    """(spec, task, batch, steps, measure_tasks) for a bench_suite
    config: ``load_config_spec`` plus a device-resident stacked task of
    ``steps`` deterministic batches — the prologue every measurement
    tool shares (profile_config, measure_config, duel_fused_head).
    ``spec_parts`` reuses an
    existing ``load_config_spec(name)`` result instead of rebuilding
    the zoo spec (tools that sweep model variants)."""
    import jax
    import numpy as np

    import bench_suite
    from elasticdl_tpu.core.step import stack_batches

    spec, batch, steps, measure_tasks = (
        spec_parts if spec_parts is not None else load_config_spec(name)
    )
    rng = np.random.RandomState(seed)
    task = jax.device_put(stack_batches(
        [bench_suite._make_batch(name, batch, rng) for _ in range(steps)]
    ))
    return spec, task, batch, steps, measure_tasks


def program_cost(spec, batch, state=None, step=None):
    """XLA cost analysis of ONE compiled optimizer step (forward +
    backward + apply): {"flops": ...}. The bench configs run without
    rematerialization, so flops equals the model's analytic FLOPs (no
    recompute inflation) — the numerator MFU is defined over.

    Device-tier sparse specs (``make_sparse_runner``) are costed through
    THEIR program — the runner's lookup + row-kernel step — not the
    dense ``build_train_step``, which would never compile against a
    SparseTrainState. Pass ``state``/``step`` to reuse a live state and
    step function (measure_multi_step does — building a second sparse
    state would transiently double the production table in HBM)."""
    import jax

    from elasticdl_tpu.core.step import build_train_step
    from elasticdl_tpu.core.train_state import init_train_state

    if (state is None) != (step is None):
        # A lone state would be silently discarded and rebuilt — for a
        # sparse spec that transiently doubles the table in HBM, the
        # exact hazard passing state exists to avoid.
        raise ValueError("pass state and step together, or neither")
    if state is None:
        if getattr(spec, "make_sparse_runner", None):
            runner = spec.make_sparse_runner()
            state = runner.init_state(
                spec.model, spec.make_optimizer(), batch, seed=0
            )
            step = runner.train_step(spec.loss)
        else:
            state = init_train_state(
                spec.model, spec.make_optimizer(), batch, seed=0
            )
            step = build_train_step(spec.loss)
    cost = step.lower(state, batch).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def program_flops(spec, batch, state=None, step=None):
    """FLOPs of one optimizer step (see ``program_cost``)."""
    return float(
        program_cost(spec, batch, state=state, step=step)
        .get("flops", 0.0)
    )


def analytic_bytes_per_step(state, batch, table_specs=()) -> float:
    """USEFUL HBM traffic of one optimizer step, in bytes — the
    numerator ``hbm_frac`` is defined over.

    Deliberately analytic, not XLA's "bytes accessed": the cost model
    charges a gather/scatter the FULL operand (a 1M-row table per
    lookup), which measured >1.0 "of peak" on deepfm — an estimator
    that can exceed the roofline attributes nothing. The analytic count
    is the traffic the training math REQUIRES; achieved/peak below 1.0
    then honestly splits into "moving bytes slower than the pin limit"
    vs "spending time on non-traffic work" (dispatch, sorts, compute).

    Model (documented so the number is auditable):
    - dense params ``p``: read at forward + read at backward + write at
      apply (3p), gradient write + read (2p) -> 5 x param bytes;
    - optimizer-state leaves: read + write at apply -> 2 x their bytes;
    - device-sparse tables (``table_specs``, SparseTrainState): per id
      in the batch (upper bound of unique rows) one row of traffic for
      forward read, row-grad write+read, apply read+write, and
      read+write per slot table -> (5 + 2*n_slots) x ids x row bytes;
      untouched rows move nothing — that IS the sparse plane's claim.
    - activations and the ids themselves are excluded (second-order at
      these shapes; documented as such in BASELINE.md).
    """
    import jax

    def nbytes(tree):
        return float(sum(
            np.size(leaf) * np.dtype(
                getattr(leaf, "dtype", np.float32)
            ).itemsize
            for leaf in jax.tree.leaves(tree)
        ))

    total = 5.0 * nbytes(state.params) + 2.0 * nbytes(state.opt_state)
    tables = getattr(state, "tables", None) or {}
    slot_tables = getattr(state, "slot_tables", None) or {}
    for spec in table_specs:
        if spec.name not in tables:
            continue
        ids = batch["features"][spec.feature_key]
        ids = getattr(ids, "ids", ids)          # RaggedIds -> ids
        itemsize = np.dtype(tables[spec.name].dtype).itemsize
        width = int(np.shape(tables[spec.name])[-1])
        if width > spec.dim:
            # packed_slots layout (optimizer.pack_table): forward reads
            # the full packed row (1x), apply gathers + scatters it
            # (2x), row grads write+read at model dim (2x).
            total += np.size(ids) * itemsize * (
                3.0 * width + 2.0 * spec.dim
            )
        else:
            n_slots = len(slot_tables.get(spec.name, {}))
            total += (
                (5.0 + 2.0 * n_slots) * np.size(ids) * spec.dim * itemsize
            )
    return total


def module_device_times(trace_dir, name_filter="multi_step"):
    """Per-program device execution times (ms) from the newest
    ``jax.profiler`` trace under ``trace_dir``.

    Reads the Perfetto JSON the profiler writes and returns the
    durations of complete events on the device process's "XLA Modules"
    lane — one event per executed XLA program, timed ON the device, so
    host and dispatch time is excluded by construction.
    ``name_filter`` keeps only the measured program (e.g. the
    ``jit_multi_step`` task program), dropping incidental transfers or
    helper programs that executed inside the trace window; if nothing
    matches, all module events are returned (program naming is backend
    -dependent). Empty list when the trace has no device lane (CPU).
    """
    return [d for _, d in module_device_events(trace_dir, name_filter)]


def module_device_events(trace_dir, name_filter="multi_step"):
    """(start_ms, dur_ms) per device execution of the measured program,
    sorted by start — same lane/name-filter/fallback semantics as
    ``module_device_times`` (which is a view over this); the starts let
    callers measure inter-program host gaps."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz"
    )))
    if not paths:
        return []
    with gzip.open(paths[-1]) as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    dev_pids = set()
    module_lanes = set()
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name" and "/device:" in (
            args.get("name") or ""
        ):
            dev_pids.add(e.get("pid"))
        if e.get("name") == "thread_name" and args.get("name") == "XLA Modules":
            module_lanes.add((e.get("pid"), e.get("tid")))
    lanes = {(p, t) for (p, t) in module_lanes if p in dev_pids}
    mods = [
        e for e in events
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in lanes
    ]
    named = [e for e in mods if name_filter in (e.get("name") or "")]
    return sorted(
        (e.get("ts", 0) / 1e3, e.get("dur", 0) / 1e3)
        for e in (named or mods)
    )


def _measure_device_time(multi_step, state, task, sync, measure_tasks):
    """Run ``measure_tasks`` programs under a profiler trace; return
    (state, median per-program device ms). The CPU backend's trace has
    no device lane and reads 0.0; on any other backend a trace without
    one is an error."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as td:
        jax.profiler.start_trace(td)
        try:
            for _ in range(measure_tasks):
                state, metrics = multi_step(state, task)
            sync(metrics)
        finally:
            jax.profiler.stop_trace()
        times = module_device_times(td)
    if not times:
        platform = jax.devices()[0].platform
        if platform != "cpu":
            raise RuntimeError(
                f"profiler trace on {platform!r} has no device "
                "'XLA Modules' lane; cannot report device time"
            )
        return state, 0.0
    # Median over programs: device time is already near-constant
    # (<2% observed spread); the median shrugs off a stray partial
    # event at the trace boundary.
    return state, float(np.median(times))


def measure_multi_step(spec, task, batch, steps_per_task, measure_tasks,
                       warmup_tasks=2, measure_rounds=5,
                       compute_mfu=False, device_time=True):
    """Time the fused task-granular step (core/step.build_multi_step) on
    a device-resident task.

    Returns a dict:
      ``eps``                examples/sec from the MIN wall time over
                             ``measure_rounds`` rounds (host noise is
                             one-sided — see module docstring)
      ``eps_median``         median-of-rounds wall rate
      ``wall_spread``        (max-min)/min over the timed rounds — the
                             recorded variance evidence
      ``device_ms_per_task`` median per-program device time off the
                             profiler trace (0.0 on the CPU backend)
      ``eps_device``         examples/sec over device time alone — the
                             regression-gating rate
      ``mfu`` / ``tflops_per_sec``  (with ``compute_mfu``, off the CPU)
                             achieved FLOPs/sec over bf16 peak on
                             device time — MFU is a device-efficiency
                             statement, so it is never computed from
                             wall time and never on the CPU
    """
    import jax

    from elasticdl_tpu.core.step import build_multi_step, build_train_step
    from elasticdl_tpu.core.train_state import init_train_state

    if getattr(spec, "make_sparse_runner", None):
        # Device-tier sparse models (embedding/device_sparse.py): the
        # runner owns state init and the fused multi-step — the Pallas
        # lookup + row-kernel path this config exists to measure.
        runner = spec.make_sparse_runner()
        sparse_specs = runner.specs
        state = runner.init_state(
            spec.model, spec.make_optimizer(),
            jax.tree.map(lambda x: x[0], task), seed=0,
        )
        multi_step = runner.train_multi_step(spec.loss)
        cost_step = runner.train_step(spec.loss)
    else:
        sparse_specs = ()
        state = init_train_state(
            spec.model, spec.make_optimizer(),
            jax.tree.map(lambda x: x[0], task), seed=0,
        )
        multi_step = build_multi_step(spec.loss)
        cost_step = build_train_step(spec.loss)

    def sync(metrics):
        # Host transfer of the last step's loss: a hard sync.
        return float(np.asarray(metrics["loss"][-1]))

    for _ in range(warmup_tasks):
        state, metrics = multi_step(state, task)
    sync(metrics)

    rounds = []
    final_loss = 0.0
    for _ in range(measure_rounds):
        start = time.perf_counter()
        for _ in range(measure_tasks):
            state, metrics = multi_step(state, task)
        final_loss = sync(metrics)
        rounds.append(time.perf_counter() - start)
    assert np.isfinite(final_loss), f"bench diverged: loss={final_loss}"

    examples = batch * steps_per_task * measure_tasks
    best = float(np.min(rounds))
    result = {
        "eps": examples / best,
        "eps_median": examples / float(np.median(rounds)),
        "wall_spread": float((np.max(rounds) - np.min(rounds))
                             / np.min(rounds)),
        "rounds_sec": [round(r, 5) for r in rounds],
    }

    device_ms = 0.0
    if device_time:
        state, device_ms = _measure_device_time(
            multi_step, state, task, sync, measure_tasks
        )
    result["device_ms_per_task"] = round(device_ms, 3)
    result["eps_device"] = (
        batch * steps_per_task / (device_ms / 1e3) if device_ms else 0.0
    )

    if compute_mfu and device_ms:
        one_batch = jax.tree.map(lambda x: x[0], task)
        flops_step = program_flops(
            spec, one_batch, state=state, step=cost_step
        )
        bytes_step = analytic_bytes_per_step(
            state, one_batch, table_specs=sparse_specs
        )
        sec = device_ms / 1e3 / steps_per_task
        device = jax.devices()[0]
        result["mfu"] = flops_step / sec / peak_flops(device)
        result["tflops_per_sec"] = flops_step / sec / 1e12
        # Roofline companion: achieved USEFUL bandwidth as a fraction
        # of the chip's peak (analytic_bytes_per_step) — the honest
        # efficiency statement for embedding-bound configs
        # (deepfm/census/recsys), where the step streams table rows and
        # mfu is structurally ~0. Near-1.0 means the program is at the
        # memory roofline and "faster" requires touching fewer bytes;
        # far below it with mfu also ~0 means the time goes to
        # non-traffic work — attribute before optimizing.
        result["bytes_per_step"] = bytes_step
        result["hbm_gbps"] = bytes_step / sec / 1e9
        result["hbm_frac"] = bytes_step / sec / peak_hbm_bw(device)
    return result


def load_json(path, default):
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except Exception:
            pass
    return default


def merge_json(path, updates):
    """Read-modify-write so subset runs don't drop other entries."""
    data = load_json(path, {})
    data.update(updates)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return data
