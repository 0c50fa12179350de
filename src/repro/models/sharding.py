"""Logical-axis sharding (MaxText-style rules, mesh-optional).

Every tensor dimension gets a *logical* name; `AXIS_RULES` maps logical
names to mesh axes of the production mesh ('pod', 'data', 'model').
When no mesh is active (CPU smoke tests) every constraint is a no-op, so
model code is written once and runs anywhere.

Param placement (ZeRO-3 / FSDP + TP hybrid):
    embed dim  -> 'data'   (fully-sharded params, all-gathered per layer;
                            XLA's latency-hiding scheduler overlaps the
                            all-gather with the previous layer's compute)
    heads/mlp/experts/vocab -> 'model' (tensor parallel)
    batch      -> ('pod', 'data')  (pods are pure data parallel)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[str, None, Tuple[Union[str, None], ...]]

AXIS_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",        # FSDP shard dim of params
    "embed_tp": "model",    # opt: d_model of the lookup table on 'model'
    "act_embed": None,      # activations keep d_model replicated
    "heads": "model",
    "kv_heads": "model",    # only applied when divisible (see spec())
    "kv_heads_rep": None,   # non-divisible kv heads: replicate
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "vocab": "model",
    "ssm_heads": "model",
    "ssm_heads_rep": None,
    "ssm_inner": "model",
    "state": None,
    "conv": None,
    "lora": None,
    "stack": None,          # scan-stacked layer axis
    "cache_seq": None,
    "frame": None,
}

_state = threading.local()

# Beyond-paper optimisation toggles (see EXPERIMENTS.md §Perf). Default
# OFF = paper-faithful baseline; the dry-run's --opt flag flips them for
# the hillclimbed variants.
OPTIMIZATIONS = set()


def opt_enabled(name: str) -> bool:
    return name in OPTIMIZATIONS


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        _state.mesh = prev


def spec(*logical: Axes) -> P:
    """Translate logical dim names to a PartitionSpec via AXIS_RULES.
    Mesh axes absent from the currently active mesh are dropped, so the
    same model code lowers on the multi-pod, single-pod and host meshes."""
    mesh = current_mesh()
    names = set(mesh.axis_names) if mesh is not None else None

    def fix(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry
                         if names is None or a in names)
            return kept if kept else None
        if names is not None and entry not in names:
            return None
        return entry

    out = []
    for name in logical:
        if name is None:
            out.append(None)
        else:
            out.append(fix(AXIS_RULES.get(name, None)))
    return P(*out)


def shard(x: jax.Array, *logical: Axes) -> jax.Array:
    """with_sharding_constraint when a mesh is active; identity otherwise."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(*logical))
    )


def named_sharding(mesh: Mesh, p: P) -> NamedSharding:
    return NamedSharding(mesh, p)


def fsdp_use(w: jax.Array, *logical: Axes) -> jax.Array:
    """Constrain an FSDP-sharded weight at its use site to be gathered
    over the 'data' axis (logical 'embed' -> replicated) while keeping
    its 'model' (TP) sharding.

    Why: with params P('data','model') and batch P(('pod','data')), the
    SPMD partitioner resolves x @ w by partial-summing the contraction
    and ALL-REDUCING ACTIVATIONS per matmul (expensive: per-layer, per-
    microbatch). Forcing the weight gathered makes XLA emit one weight
    all-gather per layer instead — ~8x less wire on chameleon train_4k
    (§Perf opt 'fsdp_gather_weights'). No-op unless the opt is enabled.
    """
    if not opt_enabled("fsdp_gather_weights"):
        return w
    fixed = tuple(None if name == "embed" else name for name in logical)
    return shard(w, *fixed)
