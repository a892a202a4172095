"""Profiler: step-window jax.profiler trace through the worker path."""

import glob
import os

from elasticdl_tpu.testing.cluster import MiniCluster
from elasticdl_tpu.testing.data import (
    create_mnist_record_file,
    model_zoo_dir,
)
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
