"""Device-mesh construction.

The TPU-native replacement for the reference's process topology: where
ElasticDL wires worker/PS pods together over gRPC, this framework lays all
devices out on a ``jax.sharding.Mesh`` and lets XLA place collectives on
ICI. Axis conventions:

- ``dp``  — data parallel (batch dimension),
- ``mp``  — model/tensor parallel (optional),
- ``sp``  — sequence/context parallel for long-context models (optional).

``--mesh_shape 4,2 --mesh_axes dp,mp`` on 8 devices builds a (4,2) mesh.
Empty shape = all local devices on one ``dp`` axis.
"""

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_mesh_args(mesh_shape: str, mesh_axes: str) -> Tuple[
    Optional[Tuple[int, ...]], Tuple[str, ...]
]:
    axes = tuple(a.strip() for a in mesh_axes.split(",") if a.strip())
    if not mesh_shape.strip():
        return None, axes or ("dp",)
    shape = tuple(int(s) for s in mesh_shape.split(",") if s.strip())
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh_shape {shape} and mesh_axes {axes} length mismatch"
        )
    return shape, axes


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axes: Sequence[str] = ("dp",),
    devices=None,
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
        axes = tuple(axes[:1]) or ("dp",)
    size = int(np.prod(shape))
    if size != len(devices):
        raise ValueError(
            f"Mesh shape {tuple(shape)} needs {size} devices, "
            f"have {len(devices)}"
        )
    dev_array = np.asarray(devices).reshape(tuple(shape))
    return Mesh(dev_array, tuple(axes))


def placement_summary(tree) -> str:
    """One log line on how a tree of device arrays is laid out: bytes
    held per device, and the global and per-device shard shape of its
    largest leaf. The check that state and batch are spread over the
    mesh and not piled on its first device (``rules.fit_spec`` drops an
    axis that does not divide without a word)."""
    per_device = {}
    largest_name, largest = "", None
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not isinstance(leaf, jax.Array):
            continue
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
        if largest is None or leaf.size > largest.size:
            largest_name, largest = jax.tree_util.keystr(path), leaf
    if largest is None:
        return "no device arrays"
    shards = sorted({
        tuple(s.data.shape) for s in largest.addressable_shards
    })
    return (
        f"bytes per device {dict(sorted(per_device.items()))}; largest "
        f"leaf {largest_name} {tuple(largest.shape)} held as shards "
        f"{shards} on {len(largest.addressable_shards)} device(s)"
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))


def shard_leaf_over_axis(mesh: Mesh, leaf, axis: str = "dp") -> NamedSharding:
    """ZeRO-style sharding for one array: partition the first dimension
    divisible by the axis size; replicate if none divides.

    This is how optimizer state avoids living fully replicated on every
    device (the reference instead centralizes it on PS pods;
    docs/designs/parameter_server.md "Model Parameter Partition").
    """
    axis_size = mesh.shape[axis]
    shape = getattr(leaf, "shape", ())
    for dim, size in enumerate(shape):
        if size % axis_size == 0 and size >= axis_size:
            spec = [None] * len(shape)
            spec[dim] = axis
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())
