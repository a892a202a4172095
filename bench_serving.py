"""Serving-plane benchmark: latency/throughput vs. batch deadline.

Answers the question the batching knobs exist for: what does adaptive
micro-batching buy over single-request serving, and what does the
flush deadline cost in p50/p99? The harness is fully in-process (an
``InferenceServer`` on an ephemeral port over a freshly exported
bundle) so the artifact measures the serving plane, not a network.

Phases:
1. export a dense MLP bundle and warm every batch bucket (the
   StableHLO artifact compiles once per power-of-two shape);
2. single-request closed-loop baseline (concurrency 1) — the
   no-batching reference point;
3. a deadline sweep at fixed concurrency: throughput, p50/p99, and
   the measured mean batch occupancy per flush (from the
   ``edl_tpu_serving_batch_occupancy`` histogram);
4. scrape ``/metrics`` over HTTP and record which
   ``edl_tpu_serving_*`` families are live.

With ``--router`` (ISSUE 6) two fleet sections run as well, over a
DeepFM host-tier bundle served through a LIVE in-process row service:

5. fleet points: N ``serve`` replica SUBPROCESSES (each with a
   hot-row cache) behind an in-process ``serving/router.py`` — fleet
   throughput, per-replica cache hit rate, hedge fire/win counts for
   each N in --replicas, vs a single-replica single-request baseline;
6. cache trace evidence: one in-process replica with the flight
   recorder on, cold (no cache) vs warm (cache): per-phase p99
   breakdown of request spans + ``row_resolve`` p99 +
   ``rpc/pull_rows`` span counts — showing the warm cache removes
   the row-service round trip from the p99 critical path.

Writes ``BENCH_SERVING.json`` (override with --out) and prints one
summary line with the best batched-vs-single speedup.

Usage: python bench_serving.py [--requests N] [--concurrency C]
       [--deadlines 0,2,5,10] [--router] [--replicas 1,2,4]
       [--out BENCH_SERVING.json]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

FEATURE_DIM = 64
# Wide enough that per-call predict cost dominates the HTTP handler
# cost (the regime batching exists for): bs=1 ~1.2ms vs ~0.14ms/ex
# amortized at bs=16 on the 2-core CI host.
HIDDEN = 1024
CLASSES = 10


def _spawn_load(addr: str, requests: int, processes: int,
                threads_per: int, warmup: int = 2,
                payload_pool: int = 1) -> dict:
    """Closed-loop load from SEPARATE client processes (the server
    process must not share its GIL with the generator — in-process
    client threads throttle the very handler threads they measure),
    aggregated into one run_load-shaped dict. serve_client imports
    only numpy+msgpack, so client startup is cheap. ``payload_pool``:
    distinct payloads cycled per process (deterministic per process
    index), so a serving-side row cache sees realistic id diversity
    instead of one repeated request."""
    per = max(1, requests // processes)

    def cmd(i):
        return [
            sys.executable,
            os.path.join(_ROOT, "tools", "serve_client.py"),
            "--addr", addr, "--requests", str(per),
            "--concurrency", str(threads_per),
            "--warmup", str(warmup), "--dump-latencies",
            "--seed", str(31 * i),
            "--payload_pool", str(payload_pool),
        ]

    procs = [
        subprocess.Popen(
            cmd(i), stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, cwd=_ROOT,
        )
        for i in range(processes)
    ]
    outputs = []
    for proc in procs:
        out, _ = proc.communicate(timeout=600)
        if proc.returncode:
            raise RuntimeError(
                f"serve_client exited {proc.returncode}"
            )
        outputs.append(json.loads(out))
    latencies = [v for o in outputs for v in o["latencies_ms"]]
    ok = sum(o["ok"] for o in outputs)
    elapsed = max(o["elapsed_s"] for o in outputs)
    statuses = {}
    for o in outputs:
        for code, count in o["statuses"].items():
            statuses[code] = statuses.get(code, 0) + count
    return {
        "requests": per * processes,
        "client_processes": processes,
        "threads_per_process": threads_per,
        "elapsed_s": round(elapsed, 4),
        "ok": ok,
        "statuses": statuses,
        "throughput_rps": round(ok / elapsed, 2) if elapsed else 0.0,
        "p50_ms": round(float(np.percentile(latencies, 50)), 3)
        if latencies else 0.0,
        "p99_ms": round(float(np.percentile(latencies, 99)), 3)
        if latencies else 0.0,
    }


def _build_bundle(tmpdir: str) -> str:
    import flax.linen as nn
    import optax

    from elasticdl_tpu.core.train_state import init_train_state
    from elasticdl_tpu.serving.export import export_serving_bundle

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            x = nn.relu(nn.Dense(HIDDEN)(x))
            x = nn.relu(nn.Dense(HIDDEN)(x))
            return nn.Dense(CLASSES)(x)

    model = Mlp()
    batch = {
        "features": np.random.RandomState(0)
        .rand(8, FEATURE_DIM).astype(np.float32),
        "labels": np.zeros((8,), np.int32),
        "mask": np.ones((8,), np.float32),
    }
    state = init_train_state(model, optax.sgd(0.1), batch, seed=0)
    bundle = os.path.join(tmpdir, "v1")
    export_serving_bundle(
        bundle, model, state, batch_example=batch,
        model_def="bench_serving.Mlp",
    )
    return bundle


def _occupancy(registry) -> tuple:
    """(sum, count) of the batch-occupancy histogram right now."""
    for family in registry.snapshot()["families"]:
        if family["name"] == "edl_tpu_serving_batch_occupancy":
            series = family["series"]
            if series:
                return series[0]["sum"], series[0]["count"]
    return 0.0, 0


def _scrape_families(addr: str):
    with urllib.request.urlopen(f"http://{addr}/metrics") as resp:
        text = resp.read().decode("utf-8")
    return sorted({
        line.split()[2]
        for line in text.splitlines()
        if line.startswith("# TYPE edl_tpu_serving")
    })


def _scrape_counter_totals(addr: str, names) -> dict:
    """Sum each named counter family's series from a /metrics scrape."""
    with urllib.request.urlopen(
        f"http://{addr}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode("utf-8")
    totals = {name: 0.0 for name in names}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        metric, _, value = line.rpartition(" ")
        family = metric.split("{", 1)[0]
        if family in totals:
            try:
                totals[family] += float(value)
            except ValueError:
                pass
    return totals


# ---- fleet mode (ISSUE 6) --------------------------------------------


def _free_port() -> int:
    import socket

    sock = socket.socket()
    sock.bind(("", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def _wait_healthy(addr: str, timeout: float = 120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://{addr}/healthz", timeout=2
            ) as resp:
                if resp.status == 200:
                    return
        except Exception:
            pass
        time.sleep(0.25)
    raise RuntimeError(f"replica {addr} never became healthy")


# This bench measures the host-side serving plane; its replica
# processes stay off any chip.
_CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _spawn_row_service():
    """The deepfm host row plane as its OWN process — sharing the
    bench process's GIL with the router would throttle both."""
    import socket

    from elasticdl_tpu.testing.data import model_zoo_dir

    port = _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "elasticdl_tpu.embedding.row_service",
            "--model_zoo", model_zoo_dir(),
            "--model_def", "deepfm.deepfm_host.custom_model",
            "--addr", f"localhost:{port}",
        ],
        cwd=_ROOT, env=_CPU_ENV, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("localhost", port),
                                     timeout=1).close()
            return proc, f"localhost:{port}"
        except OSError:
            if proc.poll() is not None:
                raise RuntimeError("row service process died")
            time.sleep(0.25)
    proc.kill()
    raise RuntimeError("row service never came up")


def _spawn_replicas(bundle: str, row_addr: str, n: int,
                    max_batch: int, cache_rows: int):
    """N real ``serve`` processes (the deployment unit) — separate
    processes, NOT threads: a fleet bench through one GIL would
    measure contention the production fleet doesn't have. Each
    replica is PINNED to one core (taskset, round-robin): colocated
    replicas otherwise thrash each other's XLA thread pools — the
    same one-core-per-replica cpuset a production pod gets."""
    import shutil

    pin = shutil.which("taskset") is not None
    cores = max(1, os.cpu_count() or 1)
    replicas = []
    for i in range(n):
        port = _free_port()
        cmd = [
            sys.executable, "-m", "elasticdl_tpu.serving.server",
            "--model_dir", bundle,
            "--row_service_addr", row_addr,
            "--port", str(port),
            "--max_batch_size", str(max_batch),
            "--batch_deadline_ms", "5",
            "--poll_seconds", "3600",
            "--row_cache_capacity", str(cache_rows),
            "--row_cache_version_check_ms", "50",
        ]
        if pin:
            cmd = ["taskset", "-c", str(i % cores)] + cmd
        proc = subprocess.Popen(
            cmd, cwd=_ROOT, env=_CPU_ENV, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        replicas.append((proc, f"localhost:{port}"))
    for _, addr in replicas:
        _wait_healthy(addr)
    return replicas


def _stop_replicas(replicas):
    import signal as _signal

    for proc, _ in replicas:
        proc.send_signal(_signal.SIGTERM)
    for proc, _ in replicas:
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


_CACHE_COUNTERS = (
    "edl_tpu_serving_row_cache_hits_total",
    "edl_tpu_serving_row_cache_misses_total",
)


def _warm_replicas(replicas, concurrency: int):
    """TWO warm passes per replica at MEASUREMENT concurrency: the
    batch-polymorphic sparse artifact compiles one program per
    (batch bucket, row bucket) pair, and the pairs reached depend on
    occupancy — warming at low concurrency leaves the saturated
    shapes cold and the timed window then measures XLA compiles
    (4.4x observed error on the 2-core host). The second pass runs
    over already-warm shapes and fills the hot-row cache."""
    import threading as _threading

    def warm(addr):
        # Until-stable, not fixed-pass: with several replicas
        # compiling at once on a small host, two passes can end with
        # shapes still cold (observed: a 5x-slow "measured" window
        # that was really XLA compile time).
        last = 0.0
        for _ in range(6):
            run = _spawn_load(
                addr, requests=max(160, 16 * concurrency),
                processes=1, threads_per=concurrency,
                payload_pool=8,
            )
            rps = run["throughput_rps"]
            if last and rps < last * 1.15:
                break
            last = rps

    threads = [
        _threading.Thread(target=warm, args=(addr,))
        for _, addr in replicas
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _drive_direct(replicas, requests: int, concurrency: int) -> dict:
    """Aggregate fleet capacity: one client process per replica,
    total offered concurrency split evenly — the L4-load-balancer
    deployment shape (the in-process router hop is measured
    separately as via_router)."""
    import threading as _threading

    n = len(replicas)
    results = [None] * n
    per_conc = max(2, concurrency // n)

    def drive(i, addr):
        results[i] = _spawn_load(
            addr, requests=requests // n, processes=1,
            threads_per=per_conc, payload_pool=8,
        )

    threads = [
        _threading.Thread(target=drive, args=(i, addr))
        for i, (_, addr) in enumerate(replicas)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ok = sum(r["ok"] for r in results)
    elapsed = max(r["elapsed_s"] for r in results)
    lat = {
        "p50_ms": round(float(np.median(
            [r["p50_ms"] for r in results]
        )), 3),
        "p99_ms": round(max(r["p99_ms"] for r in results), 3),
    }
    statuses = {}
    for r in results:
        for code, count in r["statuses"].items():
            statuses[code] = statuses.get(code, 0) + count
    return {
        "requests": requests // n * n,
        "client_processes": n,
        "threads_per_process": per_conc,
        "elapsed_s": round(elapsed, 4),
        "ok": ok,
        "statuses": statuses,
        "throughput_rps": round(ok / elapsed, 2) if elapsed else 0.0,
        **lat,
    }


def _fleet_cache_rate(replicas) -> float:
    hits = misses = 0.0
    for _, addr in replicas:
        totals = _scrape_counter_totals(addr, _CACHE_COUNTERS)
        hits += totals[_CACHE_COUNTERS[0]]
        misses += totals[_CACHE_COUNTERS[1]]
    return round(hits / (hits + misses), 4) if hits + misses else 0.0


def _bench_fleet(bundle: str, row_addr: str, sizes, requests: int,
                 concurrency: int, max_batch: int) -> dict:
    """Fleet points: N pinned replica processes per point, recording
    direct aggregate throughput, via-router throughput, cache hit
    rate, and hedge fire/win counts."""
    from elasticdl_tpu.observability import MetricsRegistry
    from elasticdl_tpu.serving.router import RouterServer

    out = {"requests": requests, "concurrency": concurrency,
           "points": []}
    baseline = None
    for n in sizes:
        replicas = _spawn_replicas(
            bundle, row_addr, n, max_batch, cache_rows=8192
        )
        try:
            _warm_replicas(replicas, max(2, concurrency // n))
            if baseline is None:
                # Single-request single-replica reference (occupancy
                # 1, no router): the PR 2 serving shape this fleet is
                # measured against.
                baseline = _spawn_load(
                    replicas[0][1], requests=min(requests, 200),
                    processes=1, threads_per=1, payload_pool=8,
                )
                out["single_replica_baseline"] = baseline
                print(
                    "fleet baseline (1 replica, concurrency 1): "
                    f"{baseline['throughput_rps']} req/s",
                    flush=True,
                )
            run = _drive_direct(replicas, requests, concurrency)
            run["replicas"] = n
            run["cache_hit_rate"] = _fleet_cache_rate(replicas)
            run["speedup_vs_single_replica"] = round(
                run["throughput_rps"]
                / max(baseline["throughput_rps"], 1e-9), 2
            )
            # Via-router pass: the same fleet behind serving/router.py
            # (policy + hedging + shed tiers). Shy hedge floor: on a
            # saturated small host an eager hedge would double load
            # exactly when there is no headroom.
            registry = MetricsRegistry()
            router = RouterServer(
                [addr for _, addr in replicas], port=0,
                metrics_registry=registry,
                hedge_min_ms=200, hedge_max_ms=2000,
                replica_timeout=30.0,
            ).start()
            try:
                via = _spawn_load(
                    f"localhost:{router.port}", requests=requests,
                    processes=max(1, concurrency // 8),
                    threads_per=min(concurrency, 8),
                    payload_pool=8,
                )
            finally:
                router.drain(grace=10.0)
            hedges = {}
            for family in registry.snapshot()["families"]:
                if family["name"] == "edl_tpu_router_hedges_total":
                    hedges = {
                        s["labels"][0]: s["value"]
                        for s in family["series"]
                    }
            run["via_router"] = {
                "throughput_rps": via["throughput_rps"],
                "p50_ms": via["p50_ms"],
                "p99_ms": via["p99_ms"],
                "statuses": via["statuses"],
                "hedges_fired": hedges.get("fired", 0.0),
                "hedges_won": hedges.get("won", 0.0),
                "hedges_cancelled": hedges.get("cancelled", 0.0),
            }
            out["points"].append(run)
            print(
                f"fleet n={n}: direct {run['throughput_rps']} req/s "
                f"({run['speedup_vs_single_replica']}x baseline, "
                f"p99 {run['p99_ms']}ms), via router "
                f"{via['throughput_rps']} req/s, "
                f"cache_hit={run['cache_hit_rate']}, hedges "
                f"{int(run['via_router']['hedges_fired'])} fired / "
                f"{int(run['via_router']['hedges_won'])} won",
                flush=True,
            )
        finally:
            _stop_replicas(replicas)
    points = {p["replicas"]: p for p in out["points"]}
    if 1 in points and max(points) > 1:
        top = points[max(points)]
        out["fleet_scaling_vs_one_replica"] = round(
            top["throughput_rps"]
            / max(points[1]["throughput_rps"], 1e-9), 2
        )
    return out


def _percentile_ms(durs, q) -> float:
    return round(
        float(np.percentile(np.asarray(durs), q)) * 1e3, 3
    ) if durs else 0.0


def _trace_section(spans) -> dict:
    """Reduce one run's recorder spans into the cache-evidence view:
    p99 per-phase breakdown of request spans + row_resolve /
    rpc/pull_rows stats."""
    from elasticdl_tpu.observability.critical_path import (
        build_index,
        phase_breakdown,
    )

    by_name = {}
    for span in spans:
        by_name.setdefault(span["name"], []).append(span)
    requests = by_name.get("request", [])
    _, children = build_index(spans)
    section = {
        "request_spans": len(requests),
        "row_resolve_p99_ms": _percentile_ms(
            [s["dur"] for s in by_name.get("row_resolve", [])], 99
        ),
        "pull_rows_spans": len(by_name.get("rpc/pull_rows", [])),
        "pull_rows_total_ms": round(
            sum(s["dur"] for s in by_name.get("rpc/pull_rows", []))
            * 1e3, 3,
        ),
    }
    if requests:
        ordered = sorted(requests, key=lambda s: s["dur"])
        p99_span = ordered[min(
            len(ordered) - 1, int(0.99 * len(ordered))
        )]
        section["request_p99_ms"] = round(p99_span["dur"] * 1e3, 3)
        section["request_p99_phases_ms"] = {
            name: round(dur * 1e3, 3)
            for name, dur in sorted(
                phase_breakdown(p99_span, children).items()
            )
        }
    return section


def _bench_cache_trace(bundle: str, row_addr: str,
                       requests: int) -> dict:
    """Trace-plane evidence (acceptance): cold (no cache) vs warm
    (hot-row cache) single replica, flight recorder on — the warm run
    must show the row-service round trip gone from the p99 path."""
    from elasticdl_tpu.observability import (
        FlightRecorder,
        MetricsRegistry,
        tracing,
    )
    from elasticdl_tpu.serving.model_store import ModelStore
    from elasticdl_tpu.serving.server import InferenceServer

    out = {}
    for mode, cache_rows in (("cold", 0), ("warm", 8192)):
        registry = MetricsRegistry()
        store = ModelStore(
            bundle, row_service_addr=row_addr, poll_seconds=3600,
            row_cache_capacity=cache_rows,
            row_cache_version_check_secs=0.05,
            metrics_registry=registry,
        )
        store.load_initial()
        server = InferenceServer(
            store, max_batch_size=16, batch_deadline_ms=2.0, port=0,
            metrics_registry=registry,
        ).start()
        try:
            addr = f"localhost:{server.port}"
            # Unrecorded warmup at MEASUREMENT concurrency, twice:
            # the saturated (batch bucket, row bucket) shapes must
            # all be compiled before the recorder goes on, and for
            # the warm mode the cache must be filled (the claim under
            # test is the WARM hit path, not the fill).
            for _ in range(2):
                _spawn_load(addr, requests=200, processes=1,
                            threads_per=4, payload_pool=8)
            tracing.set_process_role("serving")
            tracing.install_recorder(FlightRecorder(65536))
            try:
                run = _spawn_load(
                    addr, requests=requests, processes=1,
                    threads_per=4, payload_pool=8,
                )
                spans = tracing.recorder_spans()
            finally:
                tracing.uninstall_recorder()
            section = _trace_section(spans)
            section.update({
                "throughput_rps": run["throughput_rps"],
                "p50_ms": run["p50_ms"],
                "p99_ms": run["p99_ms"],
            })
            totals = _scrape_counter_totals(addr, _CACHE_COUNTERS)
            hits = totals[_CACHE_COUNTERS[0]]
            misses = totals[_CACHE_COUNTERS[1]]
            section["cache_hit_rate"] = round(
                hits / (hits + misses), 4
            ) if hits + misses else 0.0
            out[mode] = section
            print(
                f"cache {mode}: p99={section['p99_ms']}ms "
                f"row_resolve_p99="
                f"{section['row_resolve_p99_ms']}ms "
                f"pull_rows_spans={section['pull_rows_spans']} "
                f"hit_rate={section['cache_hit_rate']}",
                flush=True,
            )
        finally:
            server.stop()
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("bench_serving")
    parser.add_argument("--requests", type=int, default=600)
    parser.add_argument(
        "--concurrency", type=int, default=8,
        help="total in-flight requests (client procs x threads); "
             "past ~8 on a small host the clients' own CPU starves "
             "the server they measure",
    )
    parser.add_argument("--deadlines", default="0,2,5,10",
                        help="comma list of batch deadlines (ms)")
    parser.add_argument("--max_batch_size", type=int, default=64)
    parser.add_argument(
        "--router", action="store_true",
        help="Also bench the serving fleet (ISSUE 6): router + N "
             "replica processes over a live row service, plus the "
             "cold/warm hot-row-cache trace evidence",
    )
    parser.add_argument(
        "--replicas", default="1,2,4",
        help="Comma list of fleet sizes for --router mode",
    )
    parser.add_argument("--fleet_requests", type=int, default=600)
    parser.add_argument(
        "--fleet_concurrency", type=int, default=16,
        help="Total in-flight requests during fleet points",
    )
    parser.add_argument("--out", default="BENCH_SERVING.json")
    args = parser.parse_args(argv)

    from elasticdl_tpu.observability import default_registry
    from elasticdl_tpu.serving.model_store import ModelStore
    from elasticdl_tpu.serving.server import InferenceServer

    registry = default_registry()
    deadlines = [float(d) for d in args.deadlines.split(",")]
    processes = max(1, args.concurrency // 4)
    threads_per = max(1, args.concurrency // processes)
    result = {
        "config": {
            "requests": args.requests,
            "concurrency": args.concurrency,
            "client_processes": processes,
            "threads_per_process": threads_per,
            "max_batch_size": args.max_batch_size,
            "model": f"MLP {FEATURE_DIM}-{HIDDEN}-{HIDDEN}-{CLASSES}",
        },
    }
    with tempfile.TemporaryDirectory(prefix="bench_serving_") as td:
        _build_bundle(td)
        store = ModelStore(td, poll_seconds=3600)
        store.load_initial()

        # Warm every bucket shape once so the sweep never pays a
        # compile inside a timed window.
        model = store.current()
        bucket = 1
        while bucket <= args.max_batch_size:
            model.predict(np.zeros((bucket, FEATURE_DIM), np.float32))
            bucket *= 2

        server = InferenceServer(
            store, max_batch_size=args.max_batch_size,
            batch_deadline_ms=deadlines[0], port=0,
        ).start()
        addr = f"localhost:{server.port}"

        # Single-request baseline: one in-flight request -> every
        # batch has occupancy 1 regardless of deadline. Measured
        # TWICE (before and after the sweep) and the FASTER run is
        # the speedup denominator — host noise must make the batched
        # claim conservative, not inflate it.
        single = _spawn_load(
            addr, requests=min(args.requests, 200), processes=1,
            threads_per=1,
        )
        result["single_request"] = single
        print(f"single-request: {single['throughput_rps']} req/s "
              f"p50={single['p50_ms']}ms p99={single['p99_ms']}ms",
              flush=True)

        sweep = []
        for deadline in deadlines:
            server.predictor.batch_deadline = deadline / 1e3
            occ_sum0, occ_count0 = _occupancy(registry)
            run = _spawn_load(
                addr, requests=args.requests, processes=processes,
                threads_per=threads_per,
            )
            occ_sum1, occ_count1 = _occupancy(registry)
            flushes = occ_count1 - occ_count0
            occupancy = (
                (occ_sum1 - occ_sum0) / flushes if flushes else 0.0
            )
            run.update({
                "batch_deadline_ms": deadline,
                "mean_batch_occupancy": round(occupancy, 2),
            })
            sweep.append(run)
            print(
                f"deadline={deadline}ms: {run['throughput_rps']} req/s "
                f"occupancy={run['mean_batch_occupancy']} "
                f"p50={run['p50_ms']}ms p99={run['p99_ms']}ms",
                flush=True,
            )
        result["metrics_families"] = _scrape_families(addr)
        # Restore the first deadline: a lone request must not sit out
        # the LAST sweep value's window (that would deflate the
        # baseline and flatter the speedup).
        server.predictor.batch_deadline = deadlines[0] / 1e3
        single2 = _spawn_load(
            addr, requests=min(args.requests, 200), processes=1,
            threads_per=1,
        )
        result["single_request_recheck"] = single2
        server.stop()

    baseline = max(
        single["throughput_rps"], single2["throughput_rps"], 1e-9
    )
    result["single_baseline_rps"] = baseline
    for run in sweep:
        run["speedup_vs_single"] = round(
            run["throughput_rps"] / baseline, 2
        )
    result["deadline_sweep"] = sweep

    batched = [r for r in sweep if r["mean_batch_occupancy"] > 1.0]
    best = max(
        batched, key=lambda r: r["speedup_vs_single"], default=None
    )
    result["best"] = best

    if args.router:
        # Fleet sections run over a DeepFM host-tier bundle with a
        # LIVE row-service process — the sparse serving shape the
        # hot-row cache and the router exist for.
        from elasticdl_tpu.chaos.serving_drill import (
            export_sparse_bundle,
        )

        fleet_tmp = tempfile.mkdtemp(prefix="bench_fleet_")
        bundle, _ = export_sparse_bundle(fleet_tmp, seed=0)
        row_proc, row_addr = _spawn_row_service()
        try:
            sizes = [
                int(s) for s in args.replicas.split(",") if s.strip()
            ]
            result["fleet"] = _bench_fleet(
                bundle, row_addr, sizes,
                requests=args.fleet_requests,
                concurrency=args.fleet_concurrency,
                # 16, not 64: every extra batch bucket is another
                # (batch, row-bucket) XLA compile per replica, and
                # per-replica occupancy can't exceed the split
                # concurrency anyway.
                max_batch=16,
            )
            result["cache_trace_evidence"] = _bench_cache_trace(
                bundle, row_addr, requests=300,
            )
        finally:
            row_proc.terminate()
            try:
                row_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                row_proc.kill()

    result["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    if best is None:
        print("BENCH_SERVING: no batched regime reached (occupancy "
              "<= 1 everywhere)")
        return 1
    print(
        "BENCH_SERVING: best "
        f"{best['speedup_vs_single']}x single-request throughput at "
        f"deadline={best['batch_deadline_ms']}ms "
        f"(occupancy {best['mean_batch_occupancy']}, "
        f"p99 {best['p99_ms']}ms); families="
        f"{len(result['metrics_families'])}; artifact -> {args.out}"
    )
    if "fleet" in result and result["fleet"]["points"]:
        top = max(
            result["fleet"]["points"], key=lambda p: p["replicas"]
        )
        via = top.get("via_router", {})
        print(
            f"BENCH_SERVING fleet: {top['replicas']} replicas -> "
            f"{top['throughput_rps']} req/s "
            f"({top['speedup_vs_single_replica']}x single-replica "
            f"baseline), cache_hit={top['cache_hit_rate']}, "
            f"hedges fired/won "
            f"{int(via.get('hedges_fired', 0))}/"
            f"{int(via.get('hedges_won', 0))}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
