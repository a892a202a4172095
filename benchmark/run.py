"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json``: it writes the cell's records from
the seed, starts ``elasticdl_tpu.master.main`` and
``elasticdl_tpu.worker.main`` as the real processes a user starts (the
worker alone touches the chip; this process never imports jax), lets the
job warm up to the version its traffic mix fixes, measures whole tasks
for ``--seconds``, kills the job (no drain, no final save), has the
plain reference replay the job's first task in a process of its own and
holds what the worker's compiled program printed for that task to it,
and prints the contract's result as the last line of its output. Without a
TPU it prints no result and exits non-zero.

Everything that belongs to one configuration, one traffic mix or one
metric is a file found by name (see README.md beside this file); this
file holds no cell's, configuration's or metric's name.
"""

import argparse
import importlib.util
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import paths, procs, records  # noqa: E402
from benchmark.metrics._common import master_delta, window_tasks  # noqa: E402

FIRST_TASK_TIMEOUT = 1100.0   # a first run compiles; the contract: 1200 s
STEP_TIMEOUT = 240.0


class BenchFailure(Exception):
    """The run cannot give a result: no line is printed, exit code 1."""


def say(*parts):
    print(*parts, flush=True)


# ---------------------------------------------------------------- the cell

def load_cell(manifest_path: str, workload: str) -> dict:
    manifest = paths.load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in {manifest_path}")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]

    def wanted(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    config_file = os.path.join(ROOT, config["file"])
    base = paths.base_of(config_file)
    traffic_file = os.path.join(base, "traffic", f"{cell['traffic']}.json")
    cfg = paths.load_json(config_file)
    return {
        "workload": workload, "chips": int(cell["chips"]),
        "config_file": config_file, "traffic_file": traffic_file,
        "model_zoo": os.path.join(base, "models"),
        "cfg": cfg, "traffic": paths.load_json(traffic_file),
        "end_to_end": wanted(manifest["end_to_end"]),
        "per_layer": wanted(manifest["per_layer"]),
        "limits": cfg["limits"],
    }


def read_metric(name: str, run: dict):
    """``benchmark/metrics/<name>.py::read(run)``: a number, or None
    where the reader finds nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('.', '_').replace('-', '_')}",
        paths.metric_path(name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(run)
    if value is None:
        return None
    value = float(value)
    return value if math.isfinite(value) else None


# ------------------------------------------------------------ the processes

def scrape(port: int) -> dict:
    """The master's Prometheus page as {series: value}; {} if it does
    not answer."""
    try:
        with urllib.request.urlopen(
                f"http://localhost:{port}/metrics", timeout=5) as reply:
            text = reply.read().decode("utf-8", "replace")
    except OSError:
        return {}
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            try:
                out[series] = float(value)
            except ValueError:
                pass
    return out


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def read_probe(path: str):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class Job:
    """One master and one worker over one work directory."""

    def __init__(self, cell, seed, platform, workdir):
        self.cell, self.seed, self.platform = cell, seed, platform
        self.workdir = workdir
        cfg, traffic = cell["cfg"], cell["traffic"]
        self.cfg, self.traffic = cfg, traffic
        self.records = os.path.join(workdir, "train.rec")
        self.trace_dir = os.path.join(workdir, "trace")
        self.probe_file = os.path.join(workdir, "probe.jsonl")
        self.feed_file = os.path.join(workdir, "feed.jsonl")
        self.metrics_port = procs.free_port()
        self.addr = f"localhost:{procs.free_port()}"
        self.cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(ROOT, ".jax_cache"))
        self.children = []
        self.tails = []
        self.steps_per_task = int(traffic["minibatches_per_task"])
        self.tokens_per_task = (
            cfg["minibatch"] * cfg["seq_len"] * self.steps_per_task)
        self.replay_tasks = int(cell["limits"]["replay_tasks"])

    def flags(self):
        cfg, traffic = self.cfg, self.traffic
        return [
            "--model_zoo", self.cell["model_zoo"],
            "--model_def", cfg["model_def"],
            "--training_data", self.records,
            "--minibatch_size", str(cfg["minibatch"]),
            "--num_minibatches_per_task", str(self.steps_per_task),
            "--num_epochs", str(traffic["records"]["epochs"]),
            "--fuse_task_steps", "true",
            "--random_seed", str(self.seed & 0x7FFFFFFF),
            "--job_name", f"bench-{self.cell['workload']}",
            "--master_addr", self.addr,
        ]

    def start_master(self):
        child = procs.Child(
            [sys.executable, "-m", "elasticdl_tpu.master.main",
             *self.flags(), "--metrics_port", str(self.metrics_port)],
            os.path.join(self.workdir, "master.log"), procs.child_env())
        self.children.append(child)
        return child

    def start_worker(self, profile=None):
        argv = [sys.executable, "-m", "elasticdl_tpu.worker.main",
                "--worker_id", "0", *self.flags()]
        if profile:
            argv += ["--profile_dir", self.trace_dir,
                     "--profile_start_step", str(profile["start"]),
                     "--profile_steps", str(profile["steps"])]
        log_path = os.path.join(self.workdir, "worker.log")
        child = procs.Child(argv, log_path, procs.child_env(
            JAX_PLATFORMS=self.platform, JAX_LOG_COMPILES="1",
            BENCH_WEIGHT_SEED=self.seed, BENCH_PROBE_FILE=self.probe_file,
            BENCH_FEED_FILE=self.feed_file,
            BENCH_FEED_BATCHES=self.replay_tasks * self.steps_per_task))
        tail = procs.LogTail(log_path)
        self.children.append(child)
        self.tails.append(tail)
        return child, tail

    def stop(self):
        for child in self.children:
            child.stop()
        for tail in self.tails:
            tail.close()


def wait_task(job, worker, tail, predicate, timeout, what):
    """The first 'Task N trained' line for which predicate(version, t)
    holds; the run fails if the worker or the master dies first."""
    master = job.children[0]

    def alive():
        return worker.proc.poll() is None and master.proc.poll() is None

    event = tail.wait_for(
        lambda e: e.kind == "task" and predicate(int(e.groups[2]), e.t),
        time.time() + timeout, alive)
    if event is None:
        raise BenchFailure(
            f"{what}: not seen within {timeout:.0f}s (worker exit "
            f"{worker.proc.poll()}, master exit {master.proc.poll()})\n"
            f"--- worker log tail\n{worker.log_text()[-3000:]}\n"
            f"--- master log tail\n{master.log_text()[-1500:]}")
    return event


def task_rows(tail):
    return [{"task_id": int(e.groups[0]), "batches": int(e.groups[1]),
             "version": int(e.groups[2]), "loss": float(e.groups[3]),
             "t": e.t} for e in tail.of("task")]


def probe_now(job, worker, want: int):
    """Ask the worker for a reading and wait (briefly) until it is
    written; returns the readings so far."""
    worker.signal(signal.SIGUSR1)
    deadline = time.time() + 10.0
    while time.time() < deadline:
        readings = read_probe(job.probe_file)
        if len(readings) >= want:
            return readings
        time.sleep(0.01)
    return read_probe(job.probe_file)


# ----------------------------------------------------------------- one run

def run_cell(manifest_path, workload, seed, seconds, trace,
             platform="tpu"):
    """Runs the cell; returns (result line as a dict, exit code)."""
    launched = time.time()
    cell = load_cell(manifest_path, workload)
    cfg, traffic = cell["cfg"], cell["traffic"]
    workdir = os.path.join(ROOT, ".bench_work", workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    job = Job(cell, seed, platform, workdir)
    run = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "seed": seed,
        "seconds": float(seconds), "traced": bool(trace),
        "launched": launched, "tokens_per_task": job.tokens_per_task,
        "steps_per_task": job.steps_per_task,
        "trace_dir": job.trace_dir if trace else None,
    }
    try:
        made = records.generate(job.records, traffic, cfg, seed)
        say(f"records: {made['records']} of {cfg['seq_len'] + 1} tokens, "
            f"{made['bytes']} bytes, in {time.time() - launched:.2f}s")
        open_version = int(traffic["open_version"])
        profile = None
        if trace:
            # Traced before the window opens: stopping a trace stalls
            # the loop for many seconds, and the window stays the one
            # an untraced run measures.
            profile = {"start": int(traffic["trace"]["first_step"]),
                       "steps": int(traffic["trace"]["steps"])}
            if profile["start"] + profile["steps"] > open_version:
                raise BenchFailure("the traced steps reach into the window")
        job.start_master()
        worker, tail = job.start_worker(profile)
        run["worker_launched"] = worker.started

        first = wait_task(job, worker, tail, lambda v, t: True,
                          FIRST_TASK_TIMEOUT, "first trained task")
        run["first_task_t"] = first.t
        runs_on = tail.of("runs_on")
        if not runs_on:
            raise BenchFailure("the worker never said what it runs on")
        device = procs.parse_runs_on(runs_on[0])
        if device["platform"] != platform or (
                device["device_count"] < cell["chips"]):
            raise BenchFailure(
                f"the worker runs on {device}; the cell needs "
                f"{cell['chips']} x {platform}")
        run["device"] = device

        opened = wait_task(job, worker, tail,
                           lambda v, t: v >= open_version, STEP_TIMEOUT,
                           f"version {open_version} (window opens)")
        if int(opened.groups[2]) != open_version:
            raise BenchFailure(
                f"the window was to open at version {open_version}; the "
                f"first task boundary at or past it is {opened.groups[2]}")
        readings = probe_now(job, worker, 1)
        run["open_t"] = opened.t
        run["cache_entries_open"] = cache_entries(job.cache_dir)
        run["master_open"] = scrape(job.metrics_port)

        remaining = opened.t + float(seconds) - time.time()
        time.sleep(max(0.0, remaining))
        closed = wait_task(
            job, worker, tail,
            lambda v, t: t >= opened.t + float(seconds), STEP_TIMEOUT,
            "the task boundary that closes the window")
        run["close_t"] = closed.t
        readings = probe_now(job, worker, 2)
        run["cache_entries_close"] = cache_entries(job.cache_dir)
        run["master_close"] = scrape(job.metrics_port)
        if trace:
            wait_for_trace(job, worker, tail)
        run["probe"] = read_probe(job.probe_file) or readings
        run["master_end"] = scrape(job.metrics_port)
    finally:
        job.stop()
    run["tasks"] = task_rows(job.tails[0])
    run["events"] = {
        kind: [(e.t, e.groups) for e in job.tails[0].of(kind)]
        for kind in ("compiling", "task_failed")}
    return finish(run, job)


def wait_for_trace(job, worker, tail):
    written = tail.wait_for(
        lambda e: e.kind == "profiler" and e.groups[0] == "trace written",
        time.time() + 180.0, lambda: worker.proc.poll() is None)
    if written is None:
        raise BenchFailure(
            "the worker did not write its profiler trace\n"
            + worker.log_text()[-2000:])


# -------------------------------------------------------------- the result

def run_check(job, run):
    """The comparison with the reference, in a child that may use the
    chip now that the worker is gone."""
    cell = run["cell"]
    log_path = os.path.join(job.workdir, "check.log")
    out_path = os.path.join(job.workdir, "check.out")
    worker_losses = [t["loss"] for t in run["tasks"][:job.replay_tasks]]
    started = time.time()
    with open(out_path, "w") as out, open(log_path, "w") as log:
        code = subprocess.call(
            [sys.executable, "-m", "benchmark.lib.check",
             "--config-file", cell["config_file"],
             "--traffic-file", cell["traffic_file"],
             "--seed", str(run["seed"]), "--feed-file", job.feed_file,
             "--worker-losses", json.dumps(worker_losses)],
            cwd=ROOT, env=procs.child_env(JAX_PLATFORMS=job.platform),
            stdout=out, stderr=log, start_new_session=True)
    if code != 0:
        with open(log_path, errors="replace") as f:
            say(f"check: exit code {code}\n{f.read()[-2500:]}")
        return None, time.time() - started
    with open(out_path) as f:
        lines = [line for line in f.read().splitlines() if line.strip()]
    return json.loads(lines[-1]), time.time() - started


def decide_correct(run, check, limits):
    """Every number that decides ``correct`` beside its limit: what the
    check compared with the reference (every name under the
    configuration's ``limits.compared``), and the run's own conditions."""
    from benchmark.lib import compare

    rows = []
    numbers = (check or {}).get("numbers", {})
    for name, value, limit, ok in compare.verdicts(
            numbers, limits["compared"]):
        rows.append((name, value, f"<= {limit}", ok))
    tasks = run["tasks"]
    losses = [t["loss"] for t in tasks]
    rows.append(("losses_finite", sum(not math.isfinite(x) for x in losses),
                 "== 0", bool(losses) and all(map(math.isfinite, losses))))
    k = int(limits["loss_drop_tasks"])
    in_window = window_tasks(run)
    if len(tasks) >= 2 * k and len(in_window) >= k:
        first = sum(t["loss"] for t in tasks[:k]) / k
        last = sum(t["loss"] for t in in_window[-k:]) / k
        drop = first - last
    else:
        drop = None
    rows.append(("loss_drop", drop, f">= {limits['loss_drop_min']}",
                 drop is not None and drop >= limits["loss_drop_min"]))
    failed_lines = len(run["events"]["task_failed"])
    master_failed = master_delta(run, "edl_tpu_master_tasks_failed_total")
    rows.append(("failed_tasks", failed_lines + master_failed, "== 0",
                 failed_lines + master_failed == 0))
    compiles = read_metric("compiles_in_window", run)
    rows.append(("compiles_in_window", compiles, "== 0", compiles == 0))
    if check is not None:
        rows.append(("check_platform", check["platform"],
                     f"== {run['device']['platform']}",
                     check["platform"] == run["device"]["platform"]))
    return rows


def device_block(run, trace_summary):
    probe = run.get("probe") or []
    peaks = [p for r in probe for p in (r.get("peak_bytes_in_use") or [])
             if p is not None]
    device = {
        "platform": run["device"]["platform"],
        "kind": run["device"]["device_kind"],
        "count": run["device"]["device_count"],
        "memory_peak_bytes": max(peaks) if peaks else None,
    }
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
    return device


def summarise_trace(run):
    """The device's busy seconds and the longest idle gaps over the
    traced window: from the first task program's start to the last's
    end on the ``XLA Modules`` lane, so that the trace's own head and
    tail (host spans before the first program, the profiler's stopping)
    are no part of the idle share."""
    from benchmark.lib import trace as trace_lib
    from benchmark.metrics._common import task_programs

    path = trace_lib.find_trace(run["trace_dir"])
    if path is None:
        raise BenchFailure(f"no trace under {run['trace_dir']}")
    parsed = trace_lib.Trace.load(path)
    run["trace"] = parsed
    programs = task_programs(run)
    if not programs:
        raise BenchFailure("no task program on the trace's XLA Modules lane")
    start, end = programs[0][0], programs[-1][0] + programs[-1][1]
    return {
        "busy_s": trace_lib.busy_seconds(parsed, start, end),
        "window_s": end - start,
        "breakdown": {
            "device_ops": [[n, s] for n, s in trace_lib.top_ops(parsed)],
            "idle_gaps": [[n, s] for n, s in trace_lib.idle_gaps(
                parsed, start, end)],
        },
    }


def say_window(run, in_window):
    """The window's tasks on an earlier line: how many, how long, and
    every task that took a fifth longer than the median, with its id and
    when in the window it ended."""
    ends = [run["open_t"]] + [t["t"] for t in in_window]
    times = [b - a for a, b in zip(ends, ends[1:])]
    median = sorted(times)[len(times) // 2] if times else None
    slow = [(t["task_id"], round(d, 3), round(t["t"] - run["open_t"], 3))
            for t, d in zip(in_window, times) if d > 1.2 * median]
    say(f"window: opened at version {run['traffic']['open_version']}, "
        f"{len(in_window)} whole tasks in "
        f"{run['close_t'] - run['open_t']:.3f}s, task seconds: median "
        f"{None if median is None else round(median, 3)}, slow tasks "
        f"(id, seconds, ended at) {slow}")


def finish(run, job):
    cell = run["cell"]
    trace_summary = summarise_trace(run) if run["traced"] else None
    say_window(run, window_tasks(run))
    setup_closed = time.time()
    check, check_seconds = run_check(job, run)
    say(f"check: {check_seconds:.1f}s "
        + (json.dumps({k: check[k] for k in (
            "worker_task_losses", "reference_task_losses",
            "reference_step_losses", "program_first_loss", "widest_leaves",
            "rows", "steps", "seconds")}) if check else "gave nothing"))
    rows = decide_correct(run, check, cell["limits"])
    for name, value, limit, ok in rows:
        say(f"compared: {name} = {value} (limit {limit}) "
            f"{'ok' if ok else 'NOT OK'}")
    correct = all(ok for _, _, _, ok in rows)

    wanted = cell["per_layer"] if run["traced"] else cell["end_to_end"]
    metrics = {}
    for metric in wanted:
        value = read_metric(metric["name"], run)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    attempted = int(master_delta(
        run, "edl_tpu_master_tasks_dispatched_total", "master_close"))
    failed = int(
        master_delta(run, "edl_tpu_master_tasks_failed_total",
                     "master_close")
        + master_delta(run, "edl_tpu_master_task_requeues_total",
                       "master_close")
        + len([e for e in run["events"]["task_failed"]
               if run["open_t"] < e[0] <= run["close_t"]]))
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device_block(run, trace_summary),
    }
    if trace_summary is not None:
        result["breakdown"] = trace_summary["breakdown"]
    say(f"after the window: {time.time() - setup_closed:.1f}s")
    return result, 0


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result, code = run_cell(
            os.path.join(ROOT, "BENCHMARK.json"), args.workload,
            args.seed, args.seconds, args.trace)
    except BenchFailure as exc:
        print(f"benchmark FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
