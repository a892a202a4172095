"""Fused multi-step (scan over a task's minibatches) == per-step loop,
plus the worker/mesh production wiring."""

import jax
import numpy as np
import optax

from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import (
    build_multi_step,
    build_train_step,
    stack_batches,
)
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.testing.data import model_zoo_dir


def _batches(n=4, b=16, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "features": rng.rand(b, 28, 28).astype(np.float32),
            "labels": rng.randint(0, 10, b).astype(np.int32),
            "mask": np.ones((b,), np.float32),
        }
        for _ in range(n)
    ]


def test_multi_step_matches_per_step_loop():
    spec = get_model_spec(model_zoo_dir(),
                          "mnist.mnist_functional.custom_model")
    batches = _batches()

    s0 = init_train_state(spec.model, optax.sgd(0.1, momentum=0.9),
                          batches[0], seed=0)
    s1 = init_train_state(spec.model, optax.sgd(0.1, momentum=0.9),
                          batches[0], seed=0)

    step = build_train_step(spec.loss)
    losses0 = []
    for b in batches:
        s0, m = step(s0, b)
        losses0.append(float(m["loss"]))

    multi = build_multi_step(spec.loss)
    s1, metrics = multi(s1, stack_batches(batches))

    np.testing.assert_allclose(
        np.asarray(metrics["loss"]), np.asarray(losses0),
        rtol=1e-4, atol=3e-5,
    )
    assert int(s1.step) == int(s0.step) == 4
    # bf16 forward compute recompiled as a scan body fuses differently,
    # so 4 accumulated applies drift ~1e-3 relative; this asserts
    # semantic equivalence, not bitwise.
    for a, b in zip(jax.tree.leaves(s0.params),
                    jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-3)
    # BatchNorm running stats advanced equivalently too.
    for a, b in zip(jax.tree.leaves(s0.batch_stats),
                    jax.tree.leaves(s1.batch_stats)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-3)


def test_fused_worker_drains_and_learns(tmp_path):
    """--fuse_task_steps through the MiniCluster: job drains, loss drops,
    checkpoints still written at (crossed) intervals."""
    from elasticdl_tpu.checkpoint import CheckpointSaver
    from elasticdl_tpu.testing.cluster import MiniCluster
    from elasticdl_tpu.testing.data import create_mnist_record_file

    train = create_mnist_record_file(str(tmp_path / "t.rec"), 192, seed=1)
    ckpt = str(tmp_path / "ckpt")
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train,
        minibatch_size=16,
        num_minibatches_per_task=3,   # odd: exercises interval crossing
        num_epochs=2,
        checkpoint_dir=ckpt,
        checkpoint_steps=4,
    )
    for worker in cluster.workers:
        worker._fuse_task_steps = True
    results = cluster.run()
    assert cluster.finished
    assert results[0]["trained_batches"] == 24
    assert results[0]["final_version"] == 24
    assert results[0]["final_loss"] < 1.0
    version = CheckpointSaver(ckpt).get_valid_latest_version()
    assert version == 24


def test_fused_mesh_runner_matches_stepwise():
    """MeshRunner.train_multi_step == stepwise mesh training (transformer
    with dp/sp/tp batch rules: place_task shifts specs right one dim)."""
    _fused_mesh_matches_stepwise(
        dict(d_model=32, n_heads=4, n_layers=1, d_ff=64),
        (2, 2, 2), ("dp", "sp", "tp"),
    )


def test_fused_mesh_task_matches_stepwise_on_dp2_tp2():
    """The same on the fast lane, at a size that runs in seconds: the
    fused task program is the one every job on a mesh runs."""
    _fused_mesh_matches_stepwise(
        dict(d_model=64, n_heads=4, n_layers=2, d_ff=128),
        (2, 2), ("dp", "tp"),
    )


def _fused_mesh_matches_stepwise(widths, mesh_shape, mesh_axes):
    import importlib.util
    import os

    from elasticdl_tpu.core.step import stack_batches
    from elasticdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        transformer_sharding_rules,
    )
    from elasticdl_tpu.parallel import rules as rules_lib
    from elasticdl_tpu.parallel.mesh import make_mesh
    from elasticdl_tpu.parallel.mesh_runner import MeshRunner

    zoo_path = os.path.join(
        model_zoo_dir(), "transformer", "transformer_lm.py"
    )
    zspec = importlib.util.spec_from_file_location("tlm", zoo_path)
    zoo = importlib.util.module_from_spec(zspec)
    zspec.loader.exec_module(zoo)

    cfg = TransformerConfig(
        vocab_size=32, max_len=32, compute_dtype=np.float32, **widths
    )
    mesh = make_mesh(mesh_shape, mesh_axes,
                     devices=jax.devices()[:int(np.prod(mesh_shape))])

    rng = np.random.RandomState(0)

    def lm_batch(seed):
        r = np.random.RandomState(seed)
        start = r.randint(0, 32, (8, 1))
        seq = (start + np.arange(17)[None, :]) % 32
        return {
            "features": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32),
            "mask": np.ones((8,), np.float32),
        }

    batches = [lm_batch(i) for i in range(3)]

    def build(donate):
        model = TransformerLM(cfg, mesh=mesh)
        runner = MeshRunner(
            mesh=mesh,
            param_rule=rules_lib.regex_param_rule(
                transformer_sharding_rules(), mesh=mesh
            ),
            batch_rule=zoo.batch_sharding_rule,
            donate_state=donate,
        )
        state = runner.init_state(model, optax.adam(1e-2), batches[0],
                                  seed=0)
        return runner, state

    runner0, s0 = build(donate=False)
    step = runner0.train_step(zoo.loss)
    for b in batches:
        s0, m0 = step(s0, b)

    runner1, s1 = build(donate=False)
    multi = runner1.train_multi_step(zoo.loss)
    s1, m1 = multi(s1, stack_batches(batches))

    assert int(s1.step) == int(s0.step) == 3
    np.testing.assert_allclose(
        float(m1["loss"][-1]), float(m0["loss"]), rtol=1e-4, atol=1e-4
    )
    # Adam's eps term amplifies compile-order noise on near-zero params
    # early in training; the loss equality above is the tight check.
    for a, b in zip(jax.tree.leaves(s0.params),
                    jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-3)
