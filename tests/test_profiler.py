"""Profiler: step-window jax.profiler trace through the worker path."""

import glob
import json
import logging
import os

import jax
import numpy as np
import pytest

from elasticdl_tpu.testing.cluster import MiniCluster
from elasticdl_tpu.testing.data import (
    create_mnist_record_file,
    model_zoo_dir,
)
from elasticdl_tpu.utils import profiler as profiler_module
from elasticdl_tpu.utils.profiler import Profiler, from_args


def test_window_opens_and_closes(tmp_path):
    prof = Profiler(str(tmp_path / "trace"), start_step=2, num_steps=2)
    assert prof.enabled
    prof.observe_step(1)
    assert not prof._active
    prof.observe_step(2)
    assert prof._active
    prof.observe_step(3)
    assert prof._active
    prof.observe_step(4)  # window [2, 4) closed
    assert not prof._active and prof._done
    # Idempotent / no restart after done.
    prof.observe_step(5)
    assert not prof._active
    plugins = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*")
    )
    assert plugins, "no profile trace written"


def test_from_args_gate():
    class Args:
        profile_dir = ""

    assert from_args(Args()) is None

    class Args2:
        profile_dir = "/tmp/x"
        profile_start_step = 1
        profile_steps = 3

    prof = from_args(Args2())
    assert prof.start_step == 1 and prof.num_steps == 3


class _FakeBackend:
    """jax.profiler stand-in: records start/stop calls, no tracing.
    ``annotations`` holds the name of every ``TraceAnnotation`` entered
    (the phase seam's third sink, tests/test_tracing.py)."""

    def __init__(self):
        self.calls = []
        self.annotations = []
        backend = self

        class TraceAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                backend.annotations.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

        self.TraceAnnotation = TraceAnnotation

    def start_trace(self, logdir):
        self.calls.append(("start", logdir))

    def stop_trace(self):
        self.calls.append(("stop",))


def test_stop_closes_unfinished_window():
    fake = _FakeBackend()
    prof = Profiler("/tmp/trace", start_step=1, num_steps=100,
                    backend=fake)
    prof.observe_step(1)
    assert prof._active and fake.calls == [("start", "/tmp/trace")]
    prof.stop()
    assert not prof._active and prof._done
    assert fake.calls == [("start", "/tmp/trace"), ("stop",)]
    prof.stop()  # idempotent
    prof.observe_step(2)  # no restart after done
    assert fake.calls == [("start", "/tmp/trace"), ("stop",)]


def test_out_of_order_final_steps_tolerated():
    fake = _FakeBackend()
    prof = Profiler("/tmp/trace", start_step=5, num_steps=3, backend=fake)
    prof.observe_step(5)
    # A restored checkpoint can rewind the step counter mid-window;
    # the trace must neither crash nor double-start.
    prof.observe_step(3)
    assert prof._active
    prof.stop()
    assert fake.calls == [("start", "/tmp/trace"), ("stop",)]


def test_worker_loop_exit_closes_open_window(tmp_path):
    """Regression: if training ends before the step window fills, the
    worker must still call ``profiler.stop()`` on loop exit — the leak
    left jax.profiler mid-trace, so no trace file landed and a later
    ``start_trace`` in the process raised "already started"."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 96, seed=2)
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train,
        minibatch_size=16,
        num_epochs=1,
    )
    worker = cluster.workers[0]
    fake = _FakeBackend()
    # Window far larger than the job: it can only close via stop().
    worker._profiler = Profiler(
        str(tmp_path / "trace"), start_step=1, num_steps=10**6,
        backend=fake,
    )
    worker.run()
    assert cluster.finished
    assert worker._profiler._done and not worker._profiler._active
    assert fake.calls[0][0] == "start"
    assert fake.calls[-1] == ("stop",)


def test_worker_writes_trace(tmp_path):
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 96, seed=1)
    trace_dir = str(tmp_path / "trace")
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train,
        minibatch_size=16,
        num_epochs=1,
    )
    worker = cluster.workers[0]
    worker._profiler = Profiler(trace_dir, start_step=2, num_steps=2)
    worker.run()
    assert cluster.finished
    assert worker._profiler._done
    assert glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*")
    ), "worker did not write a profile trace"


# ---- the operation table (utils/hlo_ops.py) ------------------------------


class _Lines(logging.Handler):
    """The profiler logger's messages, in order (its logger does not
    propagate, so caplog does not see them)."""

    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append((record.levelname, record.getMessage()))

    def __enter__(self):
        profiler_module.logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        profiler_module.logger.removeHandler(self)


def _tables(trace_dir):
    return {
        os.path.basename(path): json.load(open(path))
        for path in glob.glob(os.path.join(trace_dir, "programs", "*"))
    }


def _a_train_step():
    """``core/step.py``'s one-device step of a one-layer model, and the
    arguments of a call."""
    import optax
    from flax import linen as nn

    from elasticdl_tpu.core.step import build_train_step
    from elasticdl_tpu.core.train_state import init_train_state

    class Net(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            return nn.Dense(3, name="head")(features)

    def loss(labels, logits, mask):
        per_row = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
        return (per_row * mask).sum() / mask.sum()

    batch = {"features": np.ones((4, 5), np.float32),
             "labels": np.zeros((4,), np.int32),
             "mask": np.ones((4,), np.float32)}
    state = init_train_state(Net(), optax.adam(1e-3), batch)
    return build_train_step(loss), state, batch


def test_stop_writes_the_table_before_its_trace_written_line(tmp_path):
    """``benchmark/run.py::wait_for_trace`` kills the worker once it has
    seen ``trace written``: the table has to be on disk by then."""
    trace_dir = str(tmp_path / "trace")
    prof = Profiler(trace_dir, start_step=1, num_steps=2,
                    backend=_FakeBackend())
    step, state, batch = _a_train_step()
    prof.note_program(step, state, batch)
    held = prof._program[1]
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(held))
    with _Lines() as log:
        prof.observe_step(1)
        # The first call's shapes are the ones kept.
        prof.note_program(step, state, {"features": np.ones((9, 5))})
        assert prof._program[1] is held
        assert not os.path.exists(os.path.join(trace_dir, "programs"))
        prof.stop()
    messages = [m for _, m in log.lines]
    (table_at,) = [i for i, m in enumerate(messages)
                   if "operation table of jit_train_step" in m]
    (written_at,) = [i for i, m in enumerate(messages)
                     if m.startswith("profiler: trace written")]
    assert table_at < written_at
    (table,) = _tables(trace_dir).values()
    assert list(_tables(trace_dir)) == ["jit_train_step.ops.json"]
    assert table["module"] == "jit_train_step"
    phases = {row["phase"] for row in table["ops"]}
    assert {"forward", "backward", "optimizer"} <= phases
    assert {"head", "loss", "optimizer"} <= {
        row["module"] for row in table["ops"]}
    for row in table["ops"]:
        assert set(row) - {"mixed"} == {
            "name", "opcode", "op_name", "phase", "module"}
    # Dropped with the window: nothing is kept past it.
    assert prof._program is None
    prof.note_program(step, state, batch)
    assert prof._program is None


def test_stop_writes_no_table_when_no_program_was_told(tmp_path):
    trace_dir = str(tmp_path / "trace")
    prof = Profiler(trace_dir, start_step=1, num_steps=2,
                    backend=_FakeBackend())
    with _Lines() as log:
        prof.observe_step(1)
        prof.stop()
    assert [m for _, m in log.lines][-1].startswith(
        "profiler: trace written")
    assert not os.path.exists(os.path.join(trace_dir, "programs"))


def test_a_step_that_is_no_compiled_program_has_no_table(tmp_path):
    """The host tier's step pulls rows around its program: a plain
    function, nothing to lower."""
    prof = Profiler(str(tmp_path / "trace"), backend=_FakeBackend())
    prof.note_program(lambda state, batch: (state, {}), {}, {})
    assert prof._program is None


def test_a_table_that_cannot_be_written_leaves_the_trace(tmp_path):
    class Unlowerable:
        def lower(self, *shapes):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    trace_dir = str(tmp_path / "trace")
    fake = _FakeBackend()
    prof = Profiler(trace_dir, start_step=1, num_steps=2, backend=fake)
    prof.note_program(Unlowerable(), np.ones((2,), np.float32))
    with _Lines() as log:
        prof.observe_step(1)
        prof.stop()
    assert fake.calls[-1] == ("stop",)
    levels = [level for level, _ in log.lines]
    messages = [m for _, m in log.lines]
    assert levels[-2:] == ["WARNING", "INFO"]
    assert "no operation table: RuntimeError: RESOURCE_EXHAUSTED" in (
        messages[-2])
    assert messages[-1].startswith("profiler: trace written")
    assert not _tables(trace_dir)


@pytest.mark.parametrize("fused,module", [
    (True, "jit_multi_step"), (False, "jit_train_step")])
def test_worker_tells_the_profiler_its_program(tmp_path, fused, module):
    """The worker's loop names the program it runs, fused task or
    single step, and the window leaves its table: every phase a step
    has, the optimizer's scope among them."""
    train = create_mnist_record_file(str(tmp_path / "t.rec"), 128, seed=4)
    trace_dir = str(tmp_path / "trace")
    cluster = MiniCluster(
        model_zoo=model_zoo_dir(),
        model_def="mnist.mnist_functional.custom_model",
        training_data=train, minibatch_size=16, num_epochs=1,
        num_minibatches_per_task=2, fuse_task_steps=fused,
    )
    worker = cluster.workers[0]
    worker._profiler = Profiler(trace_dir, start_step=2, num_steps=2,
                                backend=_FakeBackend())
    worker.run()
    assert cluster.finished and worker._profiler._done
    tables = _tables(trace_dir)
    assert list(tables) == [module + ".ops.json"]
    table = tables[module + ".ops.json"]
    assert table["module"] == module
    assert {"forward", "backward", "optimizer"} <= {
        row["phase"] for row in table["ops"]}
