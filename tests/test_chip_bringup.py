"""What must hold for a chip run to be believed (ISSUE 21): failures
fail, the compile cache is placed from outside, the kernel that was
traced is named, and the smoke's job itself runs — here at the toy width
on the CPU, so the command is debugged before chip time is spent on it.
"""

import inspect
import logging
import os
import sys

import jax
import numpy as np
import pytest

from elasticdl_tpu.common import jax_env
from elasticdl_tpu.testing.cluster import MiniCluster
from elasticdl_tpu.testing.data import (
    create_mnist_record_file,
    model_zoo_dir,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


# ---- the compile cache -------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = {}
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: calls.update({key: value})
    )
    return calls


def test_cache_dir_from_the_standard_variable(monkeypatch, tmp_path,
                                              config_updates):
    """Where JAX_COMPILATION_CACHE_DIR is set the program uses that
    directory (JAX reads it itself) and sets no other in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_env.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates


def test_cache_dir_fixed_under_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert jax_env.enable_compile_cache() == fixed
    assert config_updates["jax_compilation_cache_dir"] == fixed
    # The path is part of the cache key: nothing may make it move.
    source = inspect.getsource(jax_env)
    for moving in ("tempfile", "mkdtemp", "getpid", "import time"):
        assert moving not in source


# ---- one owner per chip --------------------------------------------------


@pytest.mark.parametrize("module", [
    "elasticdl_tpu.master.main",            # also the --standby role
    "elasticdl_tpu.embedding.row_service",
    "elasticdl_tpu.serving.router",
])
def test_control_plane_mains_pin_the_cpu_first(module, monkeypatch):
    """These processes import zoo code, and with it jax, but must never
    initialise an accelerator backend (the first process to do so holds
    every chip of the host): main() pins the CPU before anything else,
    argument parsing included."""
    import importlib

    class Pinned(Exception):
        pass

    def pinned():
        raise Pinned

    mod = importlib.import_module(module)
    monkeypatch.setattr(jax_env, "force_cpu", pinned)
    if hasattr(mod, "force_cpu"):
        monkeypatch.setattr(mod, "force_cpu", pinned)
    with pytest.raises(Pinned):
        mod.main(["--not-a-flag"])


def test_force_cpu_names_the_platform(monkeypatch, config_updates):
    jax_env.force_cpu()
    assert config_updates == {"jax_platforms": "cpu"}


# ---- failures that fail ------------------------------------------------

_RAISING_LOSS_ZOO = '''
from model_zoo.mnist.mnist_functional import *  # noqa: F401,F403


def loss(labels, predictions, mask):
    raise RuntimeError("this loss always raises")
'''


def test_job_whose_every_task_fails_exits_nonzero_twice(tmp_path):
    """Real master and worker processes over a zoo model whose loss
    raises: every task fails, is re-queued three times and then fails
    permanently. Neither process may exit 0 after training nothing."""
    zoo = tmp_path / "zoo" / "bad"
    zoo.mkdir(parents=True)
    (zoo / "raising.py").write_text(_RAISING_LOSS_ZOO)
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 32)
    port = chip_smoke._free_port()
    job = [
        "--model_zoo", str(tmp_path / "zoo"),
        "--model_def", "bad.raising.custom_model",
        "--training_data", train,
        "--minibatch_size", "16",
        "--num_minibatches_per_task", "2",
        "--job_name", "raising-loss",
        "--master_addr", f"localhost:{port}",
    ]
    env = chip_smoke._child_env(JAX_PLATFORMS="cpu")
    # Logs go to files: a pipe nobody drains fills up on tracebacks.
    master, worker = (
        chip_smoke._Child(
            [sys.executable, "-m", module, *extra, *job],
            str(tmp_path / f"{name}.log"), env,
        )
        for name, module, extra in (
            ("master", "elasticdl_tpu.master.main", []),
            ("worker", "elasticdl_tpu.worker.main", ["--worker_id", "0"]),
        )
    )
    try:
        worker.wait(timeout=180)
        master.wait(timeout=60)
    finally:
        worker.stop()
        master.stop()
    worker_out, master_out = worker.log_text(), master.log_text()
    assert worker.proc.returncode not in (0, -9), worker_out[-2000:]
    assert master.proc.returncode not in (0, -9), master_out[-2000:]
    assert '"trained_batches": 0' in worker_out
    # One task, MAX_TASK_RETRIES re-queues: four attempts, all counted.
    assert '"failed_tasks": 4' in worker_out
    assert "failed permanently" in master_out


def test_device_error_is_fatal_at_once_not_retried(tmp_path):
    """A compile error or RESOURCE_EXHAUSTED cannot succeed on retry
    (and the step donated the state it failed on): the step runs once,
    the task goes back to the master, and the worker dies."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 64)
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train, minibatch_size=16,
    )
    worker = cluster.workers[0]
    calls = []

    def refused(state, batch):
        calls.append(1)
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Mosaic failed to compile TPU kernel"
        )

    real_init = worker._maybe_init

    def init_then_break(batch):
        real_init(batch)
        worker._train_step = refused

    worker._maybe_init = init_then_break
    with pytest.raises(jax.errors.JaxRuntimeError):
        cluster.run()
    assert len(calls) == 1
    assert worker._failed_tasks == 1
    # The master got the task back: it is queued again, not lost.
    assert not cluster.finished


# ---- the kernel that was traced ------------------------------------------


def test_flagship_shape_reaches_the_pallas_kernel(monkeypatch):
    """On the TPU backend the transformer_l attention shape must pass
    the kernel's gate, and the trace says so in the line chip_smoke.py
    reads. (eval_shape traces without compiling, so this runs on the
    CPU with only the backend's name patched.)"""
    import jax.numpy as jnp

    from elasticdl_tpu.models.transformer import SelfAttention
    from elasticdl_tpu.ops import flash_attention as flash
    from model_zoo.transformer import transformer_lm

    cfg = transformer_lm.width_config("transformer_l")
    q_shape = (16, transformer_lm.SEQ_LEN, cfg.n_heads, cfg.head_dim)
    assert q_shape == (16, 1024, 8, 128)
    assert flash.supports(q_shape)

    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash.logger.addHandler(handler)
    flash.log_traced.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        x = jax.ShapeDtypeStruct((16, 1024, cfg.d_model), jnp.float32)
        jax.eval_shape(
            lambda x: SelfAttention(cfg).init(jax.random.PRNGKey(0), x), x
        )
    finally:
        flash.logger.removeHandler(handler)
        flash.log_traced.cache_clear()
    traced = [
        m.group(1) for m in map(chip_smoke._ATTENTION_LINE.search, records)
        if m
    ]
    assert traced == ["pallas flash kernel"], records


def test_suite_worker_and_smoke_build_one_model():
    """The transformer_l cell of the suite, the --model_def a worker is
    given and the smoke's width are one definition in the zoo module."""
    import bench_suite
    from model_zoo.transformer import transformer_lm

    model_def, batch, steps, _ = bench_suite.CONFIGS["transformer_l"]
    assert model_def == chip_smoke.FLAGSHIP["model_def"]
    assert (batch, steps) == (
        chip_smoke.MINIBATCH, chip_smoke.MINIBATCHES_PER_TASK
    )
    assert (chip_smoke.FLAGSHIP["seq_len"], chip_smoke.FLAGSHIP["vocab"]
            ) == (transformer_lm.SEQ_LEN, transformer_lm.VOCAB)
    spec = bench_suite.config_spec("transformer_l")[0]
    assert spec.model.cfg == transformer_lm.width_config("transformer_l")
    assert spec.model.cfg.d_model == 1024 and not spec.model.cfg.remat


# ---- hiding in the bench harness and the native build ---------------------


def test_unknown_device_has_no_peak():
    import benchlib

    class Unknown:
        device_kind = "cpu"

    with pytest.raises(ValueError, match="no peak recorded"):
        benchlib.peak_flops(Unknown())
    with pytest.raises(ValueError, match="no peak recorded"):
        benchlib.peak_hbm_bw(Unknown())


def test_native_build_keyed_on_content_and_failure_raises(
        monkeypatch, tmp_path):
    from elasticdl_tpu import native

    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    src = tmp_path / "lib.c"
    src.write_text("int one;")
    builds = []

    def command(out):
        builds.append(out)
        return ["sh", "-c", f"echo built > {out}"]

    first = native._ensure_built("_lib", [str(src)], command)
    # A copy may reset mtimes: same content, same binary, no rebuild.
    os.utime(src, (1, 1))
    assert native._ensure_built("_lib", [str(src)], command) == first
    assert len(builds) == 1
    src.write_text("int two;")
    second = native._ensure_built("_lib", [str(src)], command)
    assert second != first and len(builds) == 2
    assert os.path.exists(second) and not os.path.exists(first)
    src.write_text("int three;")
    with pytest.raises(RuntimeError, match="native build of _lib failed"):
        native._ensure_built("_lib", [str(src)], lambda out: ["false"])
    # No half-built file is left behind, and the last good build stays.
    assert set(os.listdir(tmp_path)) == {
        "lib.c", os.path.basename(second)
    }


# ---- the smoke's own job ---------------------------------------------------


def _toy_smoke(tmp_path, **overrides):
    kwargs = dict(
        model_zoo=model_zoo_dir(),
        model_def="transformer.transformer_lm.custom_model",
        seq_len=64, vocab=256, platform="cpu",
        workdir=str(tmp_path / "work"), leg_timeout=300.0,
    )
    kwargs.update(overrides)
    return chip_smoke.run_smoke(**kwargs)


@pytest.mark.slow
def test_smoke_job_at_toy_width_on_cpu(tmp_path, monkeypatch):
    """The function chip_smoke.py runs at the flagship width on the
    TPU, at the toy width on the CPU: both legs, every check."""
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    record = _toy_smoke(tmp_path)
    assert record["device"]["platform"] == "cpu"
    assert record["steps"] == [24, 8]
    loss_a, loss_b = record["task_losses"]
    assert len(loss_a) == 6 and len(loss_b) == 2
    assert np.isfinite(loss_a + loss_b).all()
    assert record["compile_cache"]["dir"] == str(cache)
    # With the variable set nothing is cached anywhere else, and the
    # relaunch compiled nothing the first process had not left there.
    assert record["compile_cache"]["entries_after_a"] > 0
    assert (record["compile_cache"]["entries_after_b"]
            == record["compile_cache"]["entries_after_a"])


@pytest.mark.slow
def test_smoke_fails_without_the_platform_it_demands(tmp_path):
    """No TPU visible to the worker: JAX refuses to start it."""
    with pytest.raises(chip_smoke.SmokeFailure, match="worker a"):
        _toy_smoke(tmp_path, platform="tpu")


@pytest.mark.slow
def test_smoke_fails_when_the_checkpoint_dir_is_unwritable(tmp_path):
    work = tmp_path / "work"
    work.mkdir()
    (work / "ckpt").write_text("a file where the directory should be")
    with pytest.raises(chip_smoke.SmokeFailure, match="worker a"):
        _toy_smoke(tmp_path)


@pytest.mark.slow
def test_smoke_passes_under_a_file_size_limit(tmp_path, monkeypatch):
    """The driver's chip machine refused a one-file checkpoint version
    (EFBIG at 2.5 GiB). The smoke shards its checkpoints: here the job
    passes under a limit that one toy version in one file would break."""
    import resource

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    limit = 1 << 20
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
    try:
        record = _toy_smoke(tmp_path)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    version = tmp_path / "work" / "ckpt" / "version-32"
    files = [p.stat().st_size for p in version.iterdir()]
    assert len(files) == chip_smoke.CHECKPOINT_SHARDS
    assert sum(files) > limit
    assert max(files) == record["largest_checkpoint_file_bytes"] < limit
