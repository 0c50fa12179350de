"""The batch mesh the serving plane shards its dispatches over.

`lgrass_device_batched` is embarrassingly parallel over its leading
(graph) axis: `SparsifyService(mesh=batch_mesh(k))` places each chunk's
batch axis across the mesh (`shard_batch_leading`), so each chip runs
its own rows of one dispatch. The graphs never meet; each batch-
dependent while loop's predicate is one `pred[]` all-reduce a round.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def batch_mesh(n_devices: int | None = None, axis: str = "batch") -> Mesh:
    """A 1-axis mesh over the local devices for batch-axis sharding.

    `lgrass_device_batched` is embarrassingly parallel over its leading
    (graph) axis, so the serving plane shards that axis across this mesh
    (`SparsifyService(mesh=...)`). On CPU CI the multi-device path is
    exercised with XLA_FLAGS=--xla_force_host_platform_device_count=N
    (the bayespec/olmax trick from the related-repo snippets).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n > len(devs):
        raise ValueError(f"batch_mesh({n}) but only {len(devs)} devices")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def mesh_size(mesh: Mesh) -> int:
    """Total device count of `mesh` (the batch axis is sharded over ALL
    of its axes, so multi-axis meshes flatten into one factor)."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """The leading axis split across every axis of `mesh`, the other
    dims replicated: shard s holds rows [s*B/S, (s+1)*B/S) of a B-row
    array, S = `mesh_size(mesh)`."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def shard_batch_leading(arrays, mesh: Mesh):
    """device_put each array with `batch_sharding(mesh)`. The leading
    dim must be divisible by `mesh_size(mesh)` — the service pads the
    batch axis to guarantee that."""
    sh = batch_sharding(mesh)
    return tuple(jax.device_put(a, sh) for a in arrays)
