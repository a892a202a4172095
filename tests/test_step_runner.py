"""The runner seam (``core/step.py::StepRunner``): the one-device runner
hands out ``core/step.py``'s programs as they are, the mesh runner's
programs are built from the same body, and every runner answers
everything the worker reads."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from elasticdl_tpu.core.model_spec import load_module
from elasticdl_tpu.core.step import (
    StepRunner,
    build_multi_step,
    stack_batches,
)
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.parallel.mesh import make_mesh
from elasticdl_tpu.parallel.mesh_runner import MeshRunner
from elasticdl_tpu.worker import worker as worker_module


def _tiny_transformer():
    from elasticdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    zoo = load_module("model_zoo/transformer/transformer_lm.py")
    model = TransformerLM(TransformerConfig(
        vocab_size=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=16, compute_dtype=np.float32,
    ))
    tokens = np.zeros((4, 16), np.int32)
    return model, zoo.loss, optax.adam(1e-3), tokens


def _tiny_mla_moe():
    from elasticdl_tpu.models.mla_moe import MlaMoeLM
    from tests.test_mla_moe import ROWS, SEQ, ZOO, program_config

    tokens = np.zeros((ROWS, SEQ), np.int32)
    return MlaMoeLM(program_config()), ZOO.loss, ZOO.optimizer(), tokens


@pytest.mark.parametrize("tiny", [_tiny_transformer, _tiny_mla_moe])
def test_one_device_task_program_is_build_multi_steps(tiny):
    """The worker's 8-step task program, taken through the runner, is
    ``build_multi_step``'s to the letter: the jitted function itself, no
    wrapper around it (the task cycle's host time has no room for one)."""
    model, loss, tx, tokens = tiny()
    one = {"features": tokens, "labels": tokens,
           "mask": np.ones((tokens.shape[0],), np.float32)}
    state = jax.eval_shape(lambda: init_train_state(model, tx, one))
    task = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((8,) + x.shape, x.dtype), one)
    program = StepRunner().train_multi_step(loss)
    text = program.lower(state, task).as_text()
    assert text == build_multi_step(loss).lower(state, task).as_text()
    assert text.startswith("module @jit_multi_step")
    assert "stablehlo.while" in text


class _Counting(nn.Module):
    """A model whose training output carries ``metrics``."""

    @nn.compact
    def __call__(self, features, training=False):
        logits = nn.Dense(4)(features)
        return {"logits": logits,
                "metrics": {"rows_seen": jnp.float32(features.shape[0])}}


def _counting_loss(labels, preds, mask):
    losses = optax.softmax_cross_entropy_with_integer_labels(
        preds["logits"], labels)
    return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def test_mesh_steps_return_the_models_metrics():
    """``_model_metrics`` leaves the mesh's programs as it leaves the
    one-device ones, per batch and per step of a fused task."""
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    rng = np.random.RandomState(0)
    batches = [{"features": rng.rand(8, 6).astype(np.float32),
                "labels": rng.randint(0, 4, 8).astype(np.int32),
                "mask": np.ones((8,), np.float32)} for _ in range(3)]
    runner = MeshRunner(mesh=mesh, donate_state=False)
    state = runner.init_state(_Counting(), optax.sgd(0.1), batches[0])
    _, metrics = runner.train_step(_counting_loss)(state, batches[0])
    assert set(metrics) == {"loss", "rows_seen"}
    assert float(metrics["rows_seen"]) == 8.0
    _, metrics = runner.train_multi_step(_counting_loss)(
        state, stack_batches(batches))
    assert metrics["loss"].shape == (3,)
    np.testing.assert_array_equal(np.asarray(metrics["rows_seen"]), [8.0] * 3)


def _sparse_runner():
    from elasticdl_tpu.embedding.device_sparse import (
        DeviceSparseRunner,
        TableSpec,
    )
    from elasticdl_tpu.embedding.optimizer import Adagrad

    return DeviceSparseRunner(
        (TableSpec(name="t", vocab=16, dim=4),), Adagrad(lr=0.05))


def _host_runner():
    from elasticdl_tpu.embedding.host_engine import (
        HostEmbeddingEngine,
        HostStepRunner,
    )
    from elasticdl_tpu.embedding.optimizer import (
        SGD,
        HostOptimizerWrapper,
    )
    from elasticdl_tpu.embedding.table import EmbeddingTable

    return HostStepRunner(HostEmbeddingEngine(
        {"t": EmbeddingTable("t", 4)}, HostOptimizerWrapper(SGD(lr=0.5)),
        id_keys={"t": "ids"},
    ))


# What a runner may leave out, and the answer that says so.
_OPTIONAL = {"train_multi_step": "can_fuse", "resize": "can_resize",
             "iter_prepared": "pull_ahead"}


@pytest.mark.parametrize("make_runner", [
    StepRunner,
    lambda: MeshRunner(mesh=make_mesh((2,), ("dp",),
                                      devices=jax.devices()[:2])),
    _sparse_runner,
    _host_runner,
], ids=["one_device", "mesh", "device_sparse", "host"])
def test_every_runner_answers_all_the_worker_reads(make_runner):
    """The seam is what ``worker.py`` reads off its runner, taken from
    its source: a runner answers each name, or says through the seam's
    own flag that it cannot; the worker never asks what a runner is."""
    source = inspect.getsource(worker_module)
    read = set(re.findall(r"\b(?:self\._step_runner|runner)\.(\w+)", source))
    assert {"init_state", "train_step", "eval_step", "mesh", "accum_steps",
            "host_tables", "pull_ahead", "place_state", "flush",
            *_OPTIONAL, *_OPTIONAL.values()} <= read
    runner = make_runner()
    assert isinstance(runner, StepRunner)
    for name in sorted(read):
        flag = _OPTIONAL.get(name)
        if flag is None or getattr(runner, flag):
            assert hasattr(runner, name), name
    assert isinstance(runner.can_fuse, bool)
    assert isinstance(runner.can_resize, bool)
    assert isinstance(runner.pull_ahead, bool)
    assert runner.accum_steps >= 1
    assert not re.search(
        r"(hasattr|getattr)\(\s*(self\._step_runner|runner)\b", source)
    assert not re.search(
        r"(self\._step_runner|runner) is (not )?None", source)
