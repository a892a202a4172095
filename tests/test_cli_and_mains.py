"""Stage 9 e2e: CLI subcommands, master/worker process assembly over RPC.

Mirrors the reference's client_test.sh train/evaluate/predict flows, but
in-process (SURVEY.md §4: everything distributed must be drivable
in-process)."""

import threading

import numpy as np
import pytest

from elasticdl_tpu.api.client import main as cli_main
from elasticdl_tpu.common.args import (
    build_parser,
    parse_worker_args,
)
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.testing.data import (
    create_mnist_record_file,
    model_zoo_dir,
)
from elasticdl_tpu.worker.main import build_worker

MODEL_DEF = "mnist.mnist_functional.custom_model"


def _train_argv(train_path, tmp_path, extra=()):
    return [
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train_path,
        "--minibatch_size", "16",
        "--num_epochs", "1",
        "--job_name", "cli-test",
        "--checkpoint_dir", str(tmp_path / "ckpt"),
        *extra,
    ]


def test_cli_local_train(tmp_path):
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 64)
    rc = cli_main(["train", *_train_argv(train, tmp_path),
                   "--max_steps", "2"])
    assert rc == 0


def test_cli_evaluate_and_predict_from_checkpoint(tmp_path):
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 128)
    rc = cli_main(["train", *_train_argv(train, tmp_path)])
    assert rc == 0
    ckpt = str(tmp_path / "ckpt")

    rc = cli_main([
        "evaluate",
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--validation_data", train,
        "--checkpoint_dir_for_init", ckpt,
        "--minibatch_size", "16",
    ])
    assert rc == 0

    rc = cli_main([
        "predict",
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--prediction_data", train,
        "--checkpoint_dir_for_init", ckpt,
        "--minibatch_size", "16",
    ])
    assert rc == 0


def test_cli_rejects_unknown_subcommand():
    assert cli_main(["frobnicate"]) == 2
    assert cli_main([]) == 2


def test_cli_submit_without_k8s_renders_manifests(tmp_path, capsys):
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 32)
    rc = cli_main([
        "train", *_train_argv(train, tmp_path),
        "--distribution_strategy", "MeshStrategy",
        "--image_name", "img:latest",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "kind: Pod" in out and "kind: Service" in out
    assert "elasticdl_tpu.master.main" in out


def test_master_and_worker_mains_over_rpc(tmp_path):
    """Full process assembly: Master RPC server + a build_worker() worker
    driving it over localhost gRPC until the job drains."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 96)
    eval_rec = create_mnist_record_file(str(tmp_path / "e.rec"), 32)
    master_args = build_parser("master").parse_args([
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train,
        "--validation_data", eval_rec,
        "--evaluation_steps", "3",
        "--minibatch_size", "16",
        "--num_epochs", "1",
        "--master_addr", "localhost:0",  # OS-assigned port
        "--job_name", "rpc-test",
    ])
    master = Master(master_args)
    master.prepare()
    assert master.port
    try:
        worker_args = parse_worker_args([
            "--worker_id", "0",
            "--model_zoo", model_zoo_dir(),
            "--model_def", MODEL_DEF,
            "--training_data", train,
            "--validation_data", eval_rec,
            "--minibatch_size", "16",
            "--num_epochs", "1",
            "--master_addr", f"localhost:{master.port}",
            "--job_name", "rpc-test",
        ])
        worker = build_worker(worker_args)
        run_thread = threading.Thread(target=worker.run, daemon=True)
        run_thread.start()
        run_thread.join(timeout=180)
        assert not run_thread.is_alive()
        assert master.task_dispatcher.finished()
        # Eval round completed on the master with real metrics.
        assert master.evaluation_service.completed_results
        for metrics in master.evaluation_service.completed_results.values():
            assert "accuracy" in metrics
    finally:
        master.stop()


def test_master_worker_command_wires_relaunch_checkpoint(tmp_path):
    """Relaunched workers must boot from the job's rolling checkpoint dir
    (elastic recovery without a PS)."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 32)
    ckpt = str(tmp_path / "ckpt")
    master_args = build_parser("master").parse_args([
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train,
        "--minibatch_size", "16",
        "--checkpoint_dir", ckpt,
        "--job_name", "relaunch-test",
    ])
    master = Master(master_args)
    cmd = master._worker_command(7)
    joined = " ".join(cmd)
    assert "--worker_id 7" in joined
    assert f"--checkpoint_dir {ckpt}" in joined  # workers know the dir

    # Worker-side restore resolution: empty rolling dir → fresh start;
    # once the rolling dir holds a valid version, relaunch prefers it.
    from elasticdl_tpu.worker.main import resolve_init_checkpoint

    worker_args = parse_worker_args([
        "--worker_id", "3",
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train,
        "--minibatch_size", "16",
        "--checkpoint_dir", ckpt,
        "--job_name", "relaunch-test",
    ])
    resolved = resolve_init_checkpoint(worker_args)
    assert resolved["checkpoint_dir_for_init"] == ""  # nothing to restore

    from elasticdl_tpu.checkpoint.saver import CheckpointSaver

    CheckpointSaver(ckpt).save(5, {"w": np.ones((2,), np.float32)}, {})
    resolved = resolve_init_checkpoint(worker_args)
    assert resolved == {
        "checkpoint_dir_for_init": ckpt,
        "checkpoint_init_required": True,
    }

    # A user warm-start dir passes through when the rolling dir is empty.
    warm_args = parse_worker_args([
        "--worker_id", "3",
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train,
        "--minibatch_size", "16",
        "--checkpoint_dir_for_init", "/pretrained",
        "--job_name", "relaunch-test",
    ])
    resolved = resolve_init_checkpoint(warm_args)
    assert resolved == {
        "checkpoint_dir_for_init": "/pretrained",
        "checkpoint_init_required": True,
    }
    # Train-end callback registered → dispatcher emits it when drained.
    from elasticdl_tpu.common.constants import TaskType
    types = []
    while True:
        t = master.task_dispatcher.get(0)
        if t is None:
            break
        types.append(t.type)
        master.task_dispatcher.report(t.task_id, True)
    assert types[-1] == TaskType.TRAIN_END_CALLBACK


def test_master_cli_max_steps_beats_callback(tmp_path):
    """--max_steps wins over a model-zoo MaxStepsStopping (same precedence
    as LocalExecutor)."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 64)
    zoo = tmp_path / "zoo" / "m"
    zoo.mkdir(parents=True)
    base = open(
        f"{model_zoo_dir()}/mnist/mnist_functional.py"
    ).read()
    base += (
        "\n\ndef callbacks():\n"
        "    from elasticdl_tpu.callbacks import MaxStepsStopping\n"
        "    return [MaxStepsStopping(1)]\n"
    )
    (zoo / "m.py").write_text(base)
    master_args = build_parser("master").parse_args([
        "--model_zoo", str(tmp_path / "zoo"),
        "--model_def", "m.m.custom_model",
        "--training_data", train,
        "--minibatch_size", "16",
        "--max_steps", "3",
        "--job_name", "prec-test",
    ])
    master = Master(master_args)
    total = 0
    while True:
        t = master.task_dispatcher.get(0)
        if t is None:
            break
        if t.type == "training":
            total += t.num_records
        master.task_dispatcher.report(t.task_id, True)
    assert total == 48  # 3 steps × 16, not 1 × 16


def test_worker_fresh_start_on_empty_rolling_dir(tmp_path):
    """A replacement worker whose rolling checkpoint dir has no valid
    version yet starts fresh instead of crashing."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 32)
    ckpt = str(tmp_path / "empty_ckpt")
    worker_args = parse_worker_args([
        "--worker_id", "1",
        "--model_zoo", model_zoo_dir(),
        "--model_def", MODEL_DEF,
        "--training_data", train,
        "--minibatch_size", "16",
        "--checkpoint_dir", ckpt,
        "--job_name", "lenient-test",
    ])

    class _StubMaster:  # no RPC: only _maybe_init is exercised
        pass

    worker = build_worker(worker_args, master_client=_StubMaster())
    batch = {
        "features": np.zeros((16, 28, 28), np.float32),
        "labels": np.zeros((16,), np.int32),
        "mask": np.ones((16,), np.float32),
    }
    worker._maybe_init(batch)  # must not raise FileNotFoundError
    assert worker.state is not None


def test_cli_output_flag_exports_bundle(tmp_path):
    """--output auto-injects a SavedModelExporter (reference
    `elasticdl train --output`): the bundle appears without the zoo
    module defining any callbacks."""
    import sys

    from elasticdl_tpu.api.client import main as cli_main

    train = create_mnist_record_file(str(tmp_path / "t.rec"), 32)
    out = str(tmp_path / "bundle")
    argv = ["prog", "train",
            "--model_zoo", model_zoo_dir(),
            "--model_def", MODEL_DEF,
            "--minibatch_size", "16",
            "--distribution_strategy", "Local",
            "--job_name", "outjob",
            "--training_data", train,
            "--num_epochs", "1",
            "--output", out]
    old = sys.argv
    try:
        sys.argv = argv
        assert cli_main() == 0
    finally:
        sys.argv = old
    import os

    assert os.path.exists(os.path.join(out, "params.msgpack"))
    assert os.path.exists(os.path.join(out, "metadata.json"))


def test_predict_from_checkpoint_with_lr_scheduler_callback(tmp_path):
    """Regression (caught by the raw-data e2e): a model whose callbacks
    wrap the optimizer (LearningRateScheduler -> optax chain) saves a
    chained opt_state; the eval/predict executor must rebuild the SAME
    optimizer tree or restore fails on the extra schedule leaves."""
    from elasticdl_tpu.testing.data import create_census_record_file

    train = create_census_record_file(str(tmp_path / "c.rec"), 64)
    census = "census.census_wide_deep.custom_model"
    rc = cli_main([
        "train",
        "--model_zoo", model_zoo_dir(),
        "--model_def", census,
        "--training_data", train,
        "--minibatch_size", "16",
        "--num_epochs", "1",
        "--job_name", "cb-restore",
        "--checkpoint_dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 0
    rc = cli_main([
        "predict",
        "--model_zoo", model_zoo_dir(),
        "--model_def", census,
        "--prediction_data", train,
        "--checkpoint_dir_for_init", str(tmp_path / "ckpt"),
        "--minibatch_size", "16",
    ])
    assert rc == 0
