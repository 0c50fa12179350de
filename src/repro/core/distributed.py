"""Distributed LGRASS phase 1: groups sharded across the mesh (§4.2).

The paper dispatches per-LCA marking subtasks to threads with a greedy
dynamic scheduler. The multi-pod JAX equivalent:

  * host: `partition_groups` — greedy longest-processing-time bin packing
    of groups onto shards (the paper's greedy scheduler, done once up
    front since group sizes are known after the radix sort);
  * device: `phase1_sharded` — shard_map over ('pod', 'data'); every
    shard runs the rank-lockstep greedy on its own contiguous group block.
    Tree tables (lifting, depth) are replicated — they are O(N log N)
    int32, tiny next to the edge partition at scale. No collective is
    needed inside the loop because groups are provably independent
    (Lemma 3.1/3.2); one all-gather of accept flags at the end feeds the
    sequential recovery tail.

Fault-tolerance note: because shards are pure functions of (tables,
edge block), a failed worker's block can be re-dispatched to any survivor
— the trainer-level elastic machinery (repro.ft) reuses this property.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.lca import LiftingTables, lca
from repro.core.marking import _ball_pair_covered


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


@dataclasses.dataclass
class ShardedGroupPlan:
    """Host-side plan mapping sorted slots onto shards (padded, contiguous)."""

    slot_edge: np.ndarray     # (S * Lloc,) int64 — edge id per padded slot (-1 pad)
    group_start: np.ndarray   # (S * Lloc,) int32 — local starts per shard lane
    group_size: np.ndarray    # (S * Lloc,) int32
    n_shards: int
    local_len: int
    load: np.ndarray          # (S,) int64 — slots per shard (diagnostics)


def partition_groups(
    perm: np.ndarray,
    gidx: np.ndarray,
    active: np.ndarray,
    n_shards: int,
) -> ShardedGroupPlan:
    """Greedy LPT packing of whole groups onto shards.

    perm/gidx/active come from marking.build_group_layout (host copies).
    Groups never straddle shards, so shard-local greedy == global greedy
    per group (Lemma 3.1 independence).
    """
    m = len(perm)
    n_groups = int(gidx[-1]) + 1 if m else 0
    # group extents in sorted-slot space (active slots only)
    sizes = np.zeros(n_groups, np.int64)
    np.add.at(sizes, gidx[active], 1)
    starts = np.full(n_groups, m, np.int64)
    np.minimum.at(starts, gidx, np.arange(m))
    order = np.argsort(-sizes, kind="stable")  # LPT: big groups first
    load = np.zeros(n_shards, np.int64)
    assign = np.zeros(n_groups, np.int64)
    for gid in order:
        if sizes[gid] == 0:
            continue
        s = int(np.argmin(load))
        assign[gid] = s
        load[s] += sizes[gid]
    local_len = max(1, int(load.max()))
    slot_edge = np.full(n_shards * local_len, -1, np.int64)
    gstart = np.zeros(n_shards * local_len, np.int32)
    gsize = np.zeros(n_shards * local_len, np.int32)
    cursor = np.zeros(n_shards, np.int64)
    for gid in range(n_groups):
        size = int(sizes[gid])
        if size == 0:
            continue
        s = int(assign[gid])
        base = s * local_len + int(cursor[s])
        span = perm[starts[gid]: starts[gid] + size]
        slot_edge[base: base + size] = span
        gstart[base: base + size] = int(cursor[s])
        gsize[base: base + size] = size
        cursor[s] += size
    return ShardedGroupPlan(
        slot_edge=slot_edge,
        group_start=gstart,
        group_size=gsize,
        n_shards=n_shards,
        local_len=local_len,
        load=load,
    )


def _local_lockstep(up, depth, su, sv, sbeta, gstart, gsize, active, k_cap,
                    vary_axes=()):
    """Rank-lockstep greedy on one shard's block (no collectives)."""
    t = LiftingTables(up=up, depth=depth)
    m = su.shape[0]
    lanes = jnp.arange(m, dtype=jnp.int32)
    # lane g is live iff slot g begins a group (gstart == own local index)
    is_head = active & (gstart == lanes)
    max_r = jnp.max(jnp.where(is_head, gsize, 0))

    acc_u = jnp.zeros((m, k_cap), jnp.int32)
    acc_v = jnp.zeros((m, k_cap), jnp.int32)
    acc_b = jnp.full((m, k_cap), -1, jnp.int32)
    cnt = jnp.zeros((m,), jnp.int32)
    ovf = jnp.zeros((m,), bool)
    out = jnp.zeros((m,), bool)
    if vary_axes:
        # under shard_map the carries become device-varying on first write;
        # the initial values must carry the same varying type.
        acc_u, acc_v, acc_b, cnt, ovf, out = jax.tree.map(
            lambda a: jax.lax.pcast(a, vary_axes, to="varying"),
            (acc_u, acc_v, acc_b, cnt, ovf, out),
        )

    def cond(state):
        return state[0] < max_r

    def body(state):
        r, acc_u, acc_v, acc_b, cnt, ovf, out = state
        i = jnp.minimum(lanes + r, m - 1)  # head lane g owns slots g..g+size-1
        lane_act = is_head & (r < gsize)
        lane_act = lane_act & active[i]
        x = jnp.where(lane_act, su[i], 0)
        y = jnp.where(lane_act, sv[i], 0)
        cov = _ball_pair_covered(t, x, y, acc_u, acc_v, acc_b, cnt)
        accept = lane_act & ~cov
        full = cnt >= k_cap
        ovf = ovf | (accept & full)
        slot = jnp.minimum(cnt, k_cap - 1)
        store = accept & ~full
        acc_u = acc_u.at[lanes, slot].set(jnp.where(store, x, acc_u[lanes, slot]))
        acc_v = acc_v.at[lanes, slot].set(jnp.where(store, y, acc_v[lanes, slot]))
        acc_b = acc_b.at[lanes, slot].set(
            jnp.where(store, sbeta[i], acc_b[lanes, slot])
        )
        cnt = cnt + store.astype(jnp.int32)
        write_i = jnp.where(lane_act, i, m)
        out = out.at[write_i].set(accept, mode="drop")
        return r + 1, acc_u, acc_v, acc_b, cnt, ovf, out

    _, _, _, _, _, ovf, out = jax.lax.while_loop(
        cond, body, (jnp.int32(0), acc_u, acc_v, acc_b, cnt, ovf, out)
    )
    return out, ovf


def make_phase1_sharded(mesh: Mesh, shard_axes: Tuple[str, ...], k_cap: int = 32):
    """Builds the shard_mapped phase-1 over `shard_axes` of `mesh`.

    Inputs (global shapes):
      up (LOG, n), depth (n,)              — replicated
      su/sv/sbeta/gstart/gsize/active (S*Lloc,) — sharded over shard_axes
    Output: accept flags + per-slot overflow, sharded the same way.

    NOTE on `gstart` semantics here: in the sharded plan, `group_start`
    is the *local* start index and each group-head lane is the slot where
    gstart equals its own local position (see partition_groups), which is
    what `_local_lockstep` expects.
    """
    spec_e = P(shard_axes)
    spec_r = P()

    def fn(up, depth, su, sv, sbeta, gstart, gsize, active):
        return _local_lockstep(
            up, depth, su, sv, sbeta, gstart, gsize, active, k_cap,
            vary_axes=shard_axes,
        )

    return jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec_r, spec_r, spec_e, spec_e, spec_e, spec_e, spec_e,
                      spec_e),
            out_specs=(spec_e, spec_e),
        )
    )


def lgrass_phase1_distributed(g, mesh: Mesh, shard_axes=("data",),
                              k_cap: int = 32):
    """Host orchestration: device pipeline for tables -> plan -> sharded
    lockstep. Returns (accept_by_edge, overflow_dirty_by_edge, artifacts).
    """
    from repro.core.sparsify import phase1_device  # cycle-free local import

    n, L = g.n, g.m
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    w = jnp.asarray(g.w, jnp.float32)
    d = jax.device_get(phase1_device(u, v, w, n, k_cap, True))

    perm = d["perm"].astype(np.int64)
    gidx = d["gidx"].astype(np.int64)
    active = d["crossing"].astype(bool)[perm]
    n_shards = int(np.prod([mesh.shape[a] for a in shard_axes]))
    plan = partition_groups(perm, gidx, active, n_shards)

    eid = np.where(plan.slot_edge >= 0, plan.slot_edge, 0)
    su = jnp.asarray(g.u[eid], jnp.int32)
    sv = jnp.asarray(g.v[eid], jnp.int32)
    sbeta = jnp.asarray(d["beta"][eid], jnp.int32)
    act = jnp.asarray(plan.slot_edge >= 0)
    fn = make_phase1_sharded(mesh, tuple(shard_axes), k_cap)
    with jax.set_mesh(mesh):
        out, ovf = fn(
            jnp.asarray(d["up"]),
            jnp.asarray(d["depth_t"]),
            su, sv, sbeta,
            jnp.asarray(plan.group_start),
            jnp.asarray(plan.group_size),
            act,
        )
    out = np.asarray(jax.device_get(out))
    ovf = np.asarray(jax.device_get(ovf))
    accept_by_edge = np.zeros(L, bool)
    valid = plan.slot_edge >= 0
    accept_by_edge[plan.slot_edge[valid]] = out[valid]
    # overflow lane -> dirty every edge of that shard-local group
    dirty_by_edge = np.zeros(L, bool)
    if ovf.any():
        lanes = np.where(ovf)[0]
        for lane in lanes:
            shard = lane // plan.local_len
            lo = lane  # head lane owns slots lane..lane+size-1
            size = int(plan.group_size[lane])
            ids = plan.slot_edge[lo: lo + size]
            dirty_by_edge[ids[ids >= 0]] = True
    return accept_by_edge, dirty_by_edge, d
