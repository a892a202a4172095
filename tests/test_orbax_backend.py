"""Orbax checkpoint backend: sharded-state roundtrip, mesh-resize
restore, GC, and rng/opt-state fidelity."""

import jax
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.checkpoint.orbax_backend import (
    OrbaxSaver,
    restore_state,
    save_state,
)
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    transformer_sharding_rules,
)
from elasticdl_tpu.parallel import rules as rules_lib
from elasticdl_tpu.parallel.mesh import make_mesh
from elasticdl_tpu.parallel.mesh_runner import MeshRunner
from elasticdl_tpu.testing.data import model_zoo_dir

CFG = TransformerConfig(
    vocab_size=32, d_model=32, n_heads=4, n_layers=1, d_ff=64,
    max_len=32, compute_dtype=np.float32,
)


def _batch(b=8, s=16):
    rng = np.random.RandomState(0)
    seq = rng.randint(0, 32, (b, s + 1))
    return {
        "features": seq[:, :-1].astype(np.int32),
        "labels": seq[:, 1:].astype(np.int32),
        "mask": np.ones((b,), np.float32),
    }


def _mesh_state(mesh):
    model = TransformerLM(CFG, mesh=mesh)
    runner = MeshRunner(
        mesh=mesh,
        param_rule=rules_lib.regex_param_rule(
            transformer_sharding_rules(), mesh=mesh
        ),
    )
    state = runner.init_state(model, optax.adam(1e-2), _batch(), seed=0)
    return runner, state


def test_sharded_roundtrip(tmp_path):
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    _, state = _mesh_state(mesh)
    state = state.replace(step=state.step + 7)
    saver = OrbaxSaver(str(tmp_path))
    save_state(saver, state)
    assert saver.get_valid_latest_version() == 7

    _, fresh = _mesh_state(mesh)
    restored = restore_state(saver, fresh)
    assert int(restored.step) == 7
    wi = restored.params["block_0"]["mlp"]["wi"]["kernel"]
    assert wi.sharding.spec == P(None, "tp")  # placement preserved
    np.testing.assert_array_equal(
        np.asarray(wi),
        np.asarray(state.params["block_0"]["mlp"]["wi"]["kernel"]),
    )
    np.testing.assert_array_equal(
        np.asarray(restored.rng), np.asarray(state.rng)
    )
    # Adam moments survived too.
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(restored.opt_state)[0]),
        np.asarray(jax.tree.leaves(state.opt_state)[0]),
    )


def test_mesh_resize_restore(tmp_path):
    """Saved on dp/sp/tp, restored onto a dp-only mesh layout."""
    mesh8 = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                      devices=jax.devices()[:8])
    _, state8 = _mesh_state(mesh8)
    state8 = state8.replace(step=state8.step + 3)
    saver = OrbaxSaver(str(tmp_path))
    save_state(saver, state8)

    mesh4 = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    _, state4 = _mesh_state(mesh4)
    restored = restore_state(OrbaxSaver(str(tmp_path)), state4)
    assert int(restored.step) == 3
    wi = restored.params["block_0"]["mlp"]["wi"]["kernel"]
    assert wi.sharding.mesh.shape == {"dp": 4}
    np.testing.assert_allclose(
        np.asarray(wi),
        np.asarray(state8.params["block_0"]["mlp"]["wi"]["kernel"]),
        rtol=0, atol=0,
    )


def test_gc_keeps_max(tmp_path):
    spec = get_model_spec(model_zoo_dir(),
                          "mnist.mnist_functional.custom_model")
    from elasticdl_tpu.core.train_state import init_train_state

    rng = np.random.RandomState(0)
    batch = {
        "features": rng.rand(4, 28, 28).astype(np.float32),
        "labels": rng.randint(0, 10, 4).astype(np.int32),
        "mask": np.ones((4,), np.float32),
    }
    state = init_train_state(spec.model, optax.sgd(0.1), batch, seed=0)
    saver = OrbaxSaver(str(tmp_path), keep_max=2)
    for v in (1, 2, 3, 4):
        save_state(saver, state.replace(step=state.step * 0 + v))
    saver.wait()  # join the in-flight write, then GC prunes to keep_max
    assert saver.versions() == [3, 4]


def test_restore_missing_raises(tmp_path):
    saver = OrbaxSaver(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        saver.restore_tree({})


def test_restore_from_dir_detects_orbax_backend(tmp_path):
    """The generic restore entry routes to orbax when the dir holds
    orbax versions (the path a gang-restarted worker takes), and honors
    required=False over a dir with only torn tmp writes."""
    from elasticdl_tpu.checkpoint import restore_from_dir

    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])
    _, state = _mesh_state(mesh)
    state = state.replace(step=state.step + 5)
    saver = OrbaxSaver(str(tmp_path))
    save_state(saver, state)
    saver.wait()        # the saver that wrote: another's waits on nothing

    _, fresh = _mesh_state(mesh)
    restored = restore_from_dir(fresh, str(tmp_path))
    assert int(restored.step) == 5
    np.testing.assert_array_equal(
        np.asarray(restored.params["block_0"]["mlp"]["wi"]["kernel"]),
        np.asarray(state.params["block_0"]["mlp"]["wi"]["kernel"]),
    )

    # Torn first write only: orbax tmp dir name must not be mistaken
    # for a finalized version; required=False starts fresh.
    torn = tmp_path / "torn"
    torn.mkdir()
    (torn / "orbax-3.orbax-checkpoint-tmp-123").mkdir()
    _, fresh2 = _mesh_state(mesh)
    out = restore_from_dir(fresh2, str(torn), required=False)
    assert int(out.step) == 0  # started fresh, no crash