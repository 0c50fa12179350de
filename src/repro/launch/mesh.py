"""Production mesh construction (pure function — importing this module
never touches jax device state).

Target: TPU v5e pods. Single pod = 16×16 = 256 chips, axes
('data', 'model'); multi-pod = 2 pods = 512 chips, axes
('pod', 'data', 'model') where 'pod' is the DCN-connected pure-DP axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

TP_SIZE = 16  # 'model' axis extent on both meshes


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """Whatever devices exist, as a 1-D 'data' mesh (CPU tests, examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def batch_axes_for(global_batch: int, mesh: Mesh):
    """Largest prefix of ('pod','data') whose product divides the batch.

    decode long_500k has batch 1 — unsharded; train_4k batch 256 shards
    over pod×data = 32 ways.
    """
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen = []
    prod = 1
    for a in axes:
        if global_batch % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
        else:
            break
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s per link
