"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call:
two real processes, ``python -m elasticdl_tpu.master.main`` and
``python -m elasticdl_tpu.worker.main``, over gRPC: dispatch -> record
reader -> fused task step -> checkpoint, then a relaunch that restores.

- leg A: a master (pinned to the CPU by its own code) and one worker
  that must get the accelerator. 384 records, batch 16, 4 minibatches
  fused per task, a checkpoint every 8 steps: 6 tasks, 24 steps;
- leg B, the paper's claim in small: a fresh master and a fresh worker
  over the same checkpoint directory and compile cache, started in the
  other order, 2 more tasks from a second file. The worker must restore
  version 24 and end at version 32.

``python chip_smoke.py`` runs the ``transformer_l`` width on the TPU and
takes no flag or variable that would downgrade either. It exits 0, with
``{"ok": true, "device": {...}}`` as the last line of its output, only
if every check held; otherwise it prints no result and exits non-zero.
This process never initialises a JAX backend: a chip belongs to one
process, and that process is the worker.
"""

import json
import math
import os
import re
import resource
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

MINIBATCH = 16
MINIBATCHES_PER_TASK = 4
CHECKPOINT_STEPS = 8
# Full f32 state is 2.5 GiB a version at the flagship width, and the
# driver's chip machine refused a file that large (EFBIG). 64 shard
# files keep the largest near 180 MiB: a 128 MiB embedding-sized leaf
# is the floor, leaves are not split.
CHECKPOINT_SHARDS = 64
LEG_A_RECORDS = 384     # 6 tasks, 24 optimizer steps
LEG_B_RECORDS = 128     # 2 tasks, 8 optimizer steps
# The width `python chip_smoke.py` runs: the suite's transformer_l cell
# (d1024 / 8x128 heads / L12 / ff4096, bf16, no remat), by the name a
# user gives --model_def, with records of the zoo's SEQ_LEN and VOCAB.
FLAGSHIP = dict(
    model_def="transformer.transformer_lm.transformer_l",
    seq_len=1024, vocab=32768,
)

_TASK_LINE = re.compile(
    r"Task (\d+) trained: batches=(\d+) version=(\d+) mean_loss=(\S+)"
)
_DONE_LINE = re.compile(r"Worker \d+ done: (\{.*\})")
_ATTENTION_LINE = re.compile(r"attention: traced (.+?) for q")
_CACHE_LINE = re.compile(r"XLA compilation cache at (\S+)")


class SmokeFailure(Exception):
    pass


def _check(condition, message):
    if not condition:
        raise SmokeFailure(message)


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def _child_env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(overrides)
    return env


class _Child:
    """A child process in its own session, logging to a file, that is
    always stopped (with everything it started) when the smoke ends."""

    def __init__(self, argv, log_path, env):
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait(self, timeout: float):
        """Exit code, or None if still running at the timeout."""
        try:
            return self.proc.wait(timeout=max(0.0, timeout))
        except subprocess.TimeoutExpired:
            return None

    def stop(self):
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()


def _run_to_end(name, argv, log_path, env, timeout):
    child = _Child(argv, log_path, env)
    try:
        code = child.wait(timeout)
        _check(code is not None, f"{name}: no exit within {timeout}s")
        _check(code == 0,
               f"{name}: exit code {code}\n{child.log_text()[-3000:]}")
    finally:
        child.stop()


def _write_records(workdir, seq_len, vocab):
    """Both record files, written from a seed in a child: the data
    module imports jax, and this process stays off it."""
    paths = [os.path.join(workdir, "leg_a.rec"),
             os.path.join(workdir, "leg_b.rec")]
    script = (
        "from elasticdl_tpu.testing.data import create_lm_record_file\n"
        f"create_lm_record_file({paths[0]!r}, {LEG_A_RECORDS}, seed=1, "
        f"seq_len={seq_len}, vocab={vocab})\n"
        f"create_lm_record_file({paths[1]!r}, {LEG_B_RECORDS}, seed=2, "
        f"seq_len={seq_len}, vocab={vocab})\n"
    )
    _run_to_end(
        "records", [sys.executable, "-c", script],
        os.path.join(workdir, "records.log"),
        _child_env(JAX_PLATFORMS="cpu"), timeout=300,
    )
    return paths


def _run_leg(tag, model_zoo, model_def, platform, records, workdir,
             checkpoint_dir, worker_first, timeout):
    """One master and one worker to the end of the job. Returns what
    the worker said: per-task (version, mean loss), its closing report,
    seconds from its launch to its first trained task, and its log."""
    addr = f"localhost:{_free_port()}"
    job = [
        "--model_zoo", model_zoo,
        "--model_def", model_def,
        "--training_data", records,
        "--minibatch_size", str(MINIBATCH),
        "--num_minibatches_per_task", str(MINIBATCHES_PER_TASK),
        "--num_epochs", "1",
        "--fuse_task_steps", "true",
        "--checkpoint_dir", checkpoint_dir,
        "--checkpoint_steps", str(CHECKPOINT_STEPS),
        "--checkpoint_shards", str(CHECKPOINT_SHARDS),
        "--keep_checkpoint_max", "2",
        "--job_name", f"chip-smoke-{tag}",
        "--master_addr", addr,
    ]
    launches = {
        # The master gets no platform from here: it pins itself.
        "master": lambda: _Child(
            [sys.executable, "-m", "elasticdl_tpu.master.main", *job],
            os.path.join(workdir, f"master_{tag}.log"), _child_env(),
        ),
        # The worker is told the platform by name, so that JAX itself
        # refuses to start where that platform is missing.
        "worker": lambda: _Child(
            [sys.executable, "-m", "elasticdl_tpu.worker.main",
             "--worker_id", "0", *job],
            os.path.join(workdir, f"worker_{tag}.log"),
            _child_env(JAX_PLATFORMS=platform),
        ),
    }
    order = ["worker", "master"] if worker_first else ["master", "worker"]
    children = {}
    try:
        for role in order:
            children[role] = launches[role]()
        master, worker = children["master"], children["worker"]
        deadline = time.monotonic() + timeout
        first_task_secs = None
        while worker.proc.poll() is None:
            # Messages are built only on failure: they read whole logs.
            if time.monotonic() >= deadline:
                raise SmokeFailure(
                    f"worker {tag}: no exit within {timeout}s\n"
                    f"{worker.log_text()[-3000:]}")
            if master.proc.poll() not in (None, 0):
                raise SmokeFailure(
                    f"master {tag}: exit code {master.proc.poll()}\n"
                    f"{master.log_text()[-3000:]}")
            if first_task_secs is None and _TASK_LINE.search(
                    worker.log_text()):
                first_task_secs = time.monotonic() - worker.started
            time.sleep(0.2)
        if first_task_secs is None:
            first_task_secs = time.monotonic() - worker.started
        worker_log = worker.log_text()
        _check(worker.proc.returncode == 0,
               f"worker {tag}: exit code {worker.proc.returncode}\n"
               f"{worker_log[-3000:]}")
        # The master's run loop polls every 5 s, then drains its server.
        master_code = master.wait(deadline - time.monotonic() + 60)
        _check(master_code == 0,
               f"master {tag}: exit code {master_code}\n"
               f"{master.log_text()[-3000:]}")
    finally:
        for child in children.values():
            child.stop()
    done = _DONE_LINE.search(worker_log)
    _check(done, f"worker {tag}: no closing line\n{worker_log[-3000:]}")
    tasks = [
        (int(m.group(3)), float(m.group(4)))
        for m in _TASK_LINE.finditer(worker_log)
    ]
    return {
        "tasks": tasks,
        "report": json.loads(done.group(1)),
        "first_task_secs": first_task_secs,
        "log": worker_log,
    }


def _cache_entries(cache_dir) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _largest_file_bytes(top) -> int:
    return max(
        (os.path.getsize(os.path.join(d, name))
         for d, _, names in os.walk(top) for name in names), default=0)


def run_smoke(model_zoo, model_def, seq_len, vocab, platform,
              workdir=None, leg_timeout=800.0):
    """Legs A and B for one width on one platform. Returns the record
    (device, times, steps, losses, peak bytes); raises SmokeFailure on
    the first check that does not hold. ``platform`` is what the worker
    is required to run on ("tpu", or "cpu" for the test of this very
    function at a toy width)."""
    from elasticdl_tpu.common.jax_env import compile_cache_dir

    own_workdir = workdir is None
    if own_workdir:
        workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    try:
        checkpoint_dir = os.path.join(workdir, "ckpt")
        cache_dir = compile_cache_dir()
        rec_a, rec_b = _write_records(workdir, seq_len, vocab)
        common = dict(model_zoo=model_zoo, model_def=model_def,
                      platform=platform, workdir=workdir,
                      checkpoint_dir=checkpoint_dir, timeout=leg_timeout)
        leg_a = _run_leg("a", records=rec_a, worker_first=False, **common)
        cache_after_a = _cache_entries(cache_dir)
        _check(cache_after_a > 0,
               f"compile cache {cache_dir} is empty after leg A")
        leg_b = _run_leg("b", records=rec_b, worker_first=True, **common)

        expected_attention = (
            "pallas flash kernel" if platform == "tpu"
            else "dense reference"
        )
        steps_a = LEG_A_RECORDS // MINIBATCH
        steps_b = LEG_B_RECORDS // MINIBATCH
        for tag, leg, steps, start in (
            ("a", leg_a, steps_a, 0), ("b", leg_b, steps_b, steps_a),
        ):
            report = leg["report"]
            _check(report["platform"] == platform,
                   f"worker {tag} ran on {report['platform']!r}, "
                   f"not {platform!r}")
            _check(report["device_kind"] and report["device_count"] >= 1,
                   f"worker {tag} reported no device: {report}")
            _check(report["failed_tasks"] == 0,
                   f"worker {tag}: {report['failed_tasks']} failed tasks")
            _check(report["trained_batches"] == steps,
                   f"worker {tag} trained {report['trained_batches']} "
                   f"batches, expected {steps}")
            _check(report["final_version"] == start + steps,
                   f"worker {tag} ended at version "
                   f"{report['final_version']}, expected {start + steps}")
            versions = [v for v, _ in leg["tasks"]]
            _check(versions == list(range(
                       start + MINIBATCHES_PER_TASK, start + steps + 1,
                       MINIBATCHES_PER_TASK)),
                   f"worker {tag} task versions {versions}: not a run "
                   f"from version {start}")
            losses = [loss for _, loss in leg["tasks"]]
            _check(all(math.isfinite(loss) for loss in losses),
                   f"worker {tag}: non-finite loss in {losses}")
            traced = set(_ATTENTION_LINE.findall(leg["log"]))
            _check(traced == {expected_attention},
                   f"worker {tag} traced attention {sorted(traced)}, "
                   f"expected only {expected_attention!r}")
            logged_cache = _CACHE_LINE.search(leg["log"])
            _check(logged_cache and logged_cache.group(1) == cache_dir,
                   f"worker {tag} cache "
                   f"{logged_cache and logged_cache.group(1)!r}, "
                   f"expected {cache_dir!r}")
        loss_a = [loss for _, loss in leg_a["tasks"]]
        loss_b = [loss for _, loss in leg_b["tasks"]]
        _check(loss_a[-1] < loss_a[0],
               f"leg A loss did not fall: {loss_a}")
        _check(loss_b[0] < loss_a[0],
               f"leg B began at loss {loss_b[0]}, not below leg A's "
               f"first {loss_a[0]}: the state did not come back")
        _run_to_end(
            "check_checkpoint",
            [sys.executable, os.path.join(ROOT, "tools",
                                          "check_checkpoint.py"),
             checkpoint_dir],
            os.path.join(workdir, "fsck.log"),
            _child_env(JAX_PLATFORMS="cpu"), timeout=600,
        )
        report = leg_a["report"]
        return {
            "device": {"platform": report["platform"],
                       "kind": report["device_kind"],
                       "count": report["device_count"]},
            "model_def": model_def,
            "steps": [steps_a, steps_b],
            "cold_first_task_secs": round(leg_a["first_task_secs"], 1),
            "warm_first_task_secs": round(leg_b["first_task_secs"], 1),
            "task_losses": [loss_a, loss_b],
            "peak_bytes_in_use": [leg_a["report"]["peak_bytes_in_use"],
                                  leg_b["report"]["peak_bytes_in_use"]],
            "largest_checkpoint_file_bytes":
                _largest_file_bytes(checkpoint_dir),
            "compile_cache": {"dir": cache_dir,
                              "entries_after_a": cache_after_a,
                              "entries_after_b": _cache_entries(cache_dir)},
        }
    finally:
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    try:
        record = run_smoke(
            model_zoo=os.path.join(ROOT, "model_zoo"), platform="tpu",
            **FLAGSHIP,
        )
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        # What the machine allows, for reading a refusal from afar.
        print(f"RLIMIT_FSIZE (soft, hard; -1 = unlimited): "
              f"{resource.getrlimit(resource.RLIMIT_FSIZE)}; free in "
              f"{tempfile.gettempdir()}: "
              f"{shutil.disk_usage(tempfile.gettempdir()).free >> 20} MiB",
              file=sys.stderr)
        return 1
    print(json.dumps({"record": record}))
    print(json.dumps({"ok": True, "device": record["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
