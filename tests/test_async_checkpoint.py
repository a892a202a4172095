"""Async checkpoint writes: ordering, flush semantics, deferred errors,
and the snapshot-before-donation guarantee."""

import os
import time

import numpy as np
import optax
import pytest

from elasticdl_tpu.checkpoint import CheckpointHook, CheckpointSaver
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import build_train_step
from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.testing.data import model_zoo_dir


def _state(seed=0):
    spec = get_model_spec(model_zoo_dir(),
                          "mnist.mnist_functional.custom_model")
    rng = np.random.RandomState(seed)
    batch = {
        "features": rng.rand(8, 28, 28).astype(np.float32),
        "labels": rng.randint(0, 10, 8).astype(np.int32),
        "mask": np.ones((8,), np.float32),
    }
    state = init_train_state(spec.model, optax.sgd(0.1), batch, seed=0)
    return spec, state, batch


def test_async_save_lands_after_flush(tmp_path):
    _, state, _ = _state()
    hook = CheckpointHook(str(tmp_path), checkpoint_steps=1,
                          async_save=True)
    state = state.replace(step=state.step + 1)
    assert hook.maybe_save(state)
    hook.flush()
    assert CheckpointSaver(str(tmp_path)).get_valid_latest_version() == 1


def test_save_final_flushes(tmp_path):
    _, state, _ = _state()
    hook = CheckpointHook(str(tmp_path), checkpoint_steps=2,
                          async_save=True)
    state = state.replace(step=state.step + 3)
    assert hook.save_final(state)
    # No explicit flush needed: save_final joined the writer.
    assert CheckpointSaver(str(tmp_path)).get_valid_latest_version() == 3


def test_interval_counts_from_versions_handed_to_a_slow_writer():
    """A write that outlasts several tasks (a multi-GB state) must not
    change which versions are saved: every 8 steps means 8, 16, 24 for
    a job of fused 4-step tasks, and the final version once. Counting
    from the last LANDED write saved 8, 12, 16, 20, 24, 24."""
    _, state, _ = _state()
    saved = []

    class SlowSaver:
        def save(self, version, leaves):
            time.sleep(0.2)
            saved.append(version)

    hook = CheckpointHook(checkpoint_steps=8, saver=SlowSaver(),
                          async_save=True)
    for version in range(4, 25, 4):
        state = state.replace(step=version)
        hook.maybe_save(state)
    hook.save_final(state)
    assert saved == [8, 16, 24]


def test_deferred_write_error_surfaces_on_flush(tmp_path):
    _, state, _ = _state()

    class BrokenSaver:
        def save(self, version, leaves):
            raise IOError("disk full")

    hook = CheckpointHook(checkpoint_steps=1, saver=BrokenSaver(),
                          async_save=True)
    state = state.replace(step=state.step + 1)
    hook.maybe_save(state)
    with pytest.raises(IOError, match="disk full"):
        hook.flush()


def test_snapshot_is_consistent_despite_donation(tmp_path):
    """The device->host copy happens before the next (donating) train
    step mutates buffers: the checkpoint equals the state at save time,
    not whatever the buffers hold later."""
    spec, state, batch = _state()
    hook = CheckpointHook(str(tmp_path), checkpoint_steps=1,
                          async_save=True)
    step = build_train_step(spec.loss)
    state, _ = step(state, batch)
    saved_version = int(state.step)
    want = np.asarray(
        state.params["Dense_0"]["kernel"]
    ).copy()
    hook.maybe_save(state)
    # Donating steps immediately reuse/overwrite the old buffers.
    for _ in range(3):
        state, _ = step(state, batch)
    hook.flush()
    saver = CheckpointSaver(str(tmp_path))
    _, dense, _ = saver.restore(version=saved_version)
    got = dense["params['Dense_0']['kernel']"]
    np.testing.assert_array_equal(got, want)


def test_sync_mode_writes_inline(tmp_path):
    _, state, _ = _state()
    hook = CheckpointHook(str(tmp_path), checkpoint_steps=1,
                          async_save=False)
    state = state.replace(step=state.step + 1)
    assert hook.maybe_save(state)
    # Visible immediately, no flush required.
    assert CheckpointSaver(str(tmp_path)).get_valid_latest_version() == 1