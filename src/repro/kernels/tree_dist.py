"""Binary-lifting tree-distance kernel (Pallas TPU) — the hot gather of
the recovery coverage test.

The Algorithm-6 replay asks, per scanned edge, for tree hop distances
from its endpoints to every buffered accepted endpoint. Each distance is
an LCA climb: O(log n) dependent gathers from the (LOG, n) lifting
table. On TPU a data-dependent gather is the wrong native shape; the
dense mapping is a one-hot contraction on the MXU — `table[:, idx]`
becomes `table @ onehot(idx)`.

Exactness: the MXU multiplies bf16, which holds integers only up to
256. So the wrapper (ops.py) splits every int32 table entry into its
four bytes, each a row of a bf16 byte-plane table. A one-hot column
selects exactly one product per output, so every f32 accumulation is an
exact byte value, and recombining the four bytes reproduces the int32
entry bit for bit (INF depths and negative values included).

Layout (what the chip's compiler accepts): every operand is rank 2,
because Mosaic refuses rank-1 blocks. Queries are lane-dense (1, Q)
rows and the table is (rows, n) with nodes on lanes. The node axis is
walked in `n_chunk` slices by a loop inside the kernel, and the climb
levels by another, so the compiled code stays small whatever n and LOG
are: Mosaic unrolls vector code per vreg, and an unrolled (block, n)
one-hot per level takes minutes to compile.

VMEM bound: the whole byte-plane table stays resident, so the kernel
targets the serving regime (n up to a few thousand per graph);
ops.py checks the bound at trace time and pads queries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_BYTES = 4  # byte planes per int32 table entry


def _gather(tab_ref, idx, *, k_pad: int, n_chunk: int, n_chunks: int):
    """idx: (1, W) int32 node ids -> (N_BYTES * k_pad, W) f32 byte planes
    of every table row at those nodes (exact small integers)."""
    w = idx.shape[1]

    def body(c, acc):
        start = pl.multiple_of(c * n_chunk, n_chunk)
        nodes = jax.lax.broadcasted_iota(jnp.int32, (n_chunk, w), 0) + start
        onehot = jnp.where(nodes == idx, 1.0, 0.0).astype(jnp.bfloat16)
        planes = tab_ref[:, pl.ds(start, n_chunk)]
        return acc + jnp.dot(planes, onehot,
                             preferred_element_type=jnp.float32)

    acc = jnp.zeros((N_BYTES * k_pad, w), jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, body, acc)


def _row(planes, r, *, k_pad: int):
    """Table row `r` (traced) at the gathered nodes: (1, W) int32,
    recombined from its byte planes."""
    sel = jax.lax.broadcasted_iota(jnp.int32, (k_pad, planes.shape[1]), 0)
    out = jnp.zeros((1, planes.shape[1]), jnp.int32)
    for j in range(N_BYTES):
        plane = planes[j * k_pad:(j + 1) * k_pad]
        byte = jnp.sum(jnp.where(sel == r, plane, 0.0), axis=0, keepdims=True)
        out = out | (byte.astype(jnp.int32) << (8 * j))
    return out


def _tree_dist_kernel(tab_ref, a_ref, b_ref, out_ref, *, log: int,
                      k_pad: int, n_chunk: int, n_chunks: int):
    # table rows: 0 = depth, 1 + i = up[i]
    gather = functools.partial(_gather, tab_ref, k_pad=k_pad,
                               n_chunk=n_chunk, n_chunks=n_chunks)
    row = functools.partial(_row, k_pad=k_pad)
    a = a_ref[...]          # (1, Q)
    b = b_ref[...]
    q = a.shape[1]
    ab = jnp.concatenate([a, b], axis=1)            # both climbs at once
    d_ab = row(gather(ab), 0)
    da, db = d_ab[:, :q], d_ab[:, q:]
    # lift the deeper endpoint to the shallower one's level
    k = jnp.concatenate([jnp.maximum(da - db, 0), jnp.maximum(db - da, 0)],
                        axis=1)

    def lift(i, c):
        return jnp.where(((k >> i) & 1) == 1, row(gather(c), i + 1), c)

    c = jax.lax.fori_loop(0, log, lift, ab)

    # descend in lockstep to just below the LCA
    def descend(i, c):
        up = row(gather(c), log - i)
        ca, cb, ua, ub = c[:, :q], c[:, q:], up[:, :q], up[:, q:]
        jump = (ca != cb) & (ua != ub)
        return jnp.concatenate([jnp.where(jump, ua, ca),
                                jnp.where(jump, ub, cb)], axis=1)

    c = jax.lax.fori_loop(0, log, descend, c)
    ca, cb = c[:, :q], c[:, q:]
    par = row(gather(c), 1)[:, :q]
    w = jnp.where(ca == cb, ca, par)
    dw = row(gather(jnp.concatenate([w, w], axis=1)), 0)[:, :q]
    out_ref[...] = da + db - 2 * dw


def tree_dist_pairs(planes: jax.Array, a: jax.Array, b: jax.Array, *,
                    log: int, k_pad: int, n_chunk: int, block: int,
                    vmem_limit_bytes: int, interpret: bool = False
                    ) -> jax.Array:
    """planes: (N_BYTES * k_pad, n_pad) bf16 byte-plane table (see
    ops.tree_dist_pairs, which builds it); a, b: (1, M) int32 query
    pairs, M a multiple of `block`, itself a multiple of 128. Returns
    (1, M) int32 tree hop distances."""
    rows, n_pad = planes.shape
    m = a.shape[1]
    assert rows == N_BYTES * k_pad and n_pad % n_chunk == 0
    assert m % block == 0 and block % 128 == 0, "pad queries to a block"
    kernel = functools.partial(_tree_dist_kernel, log=log, k_pad=k_pad,
                               n_chunk=n_chunk, n_chunks=n_pad // n_chunk)
    qspec = pl.BlockSpec((1, block), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(m // block,),
        in_specs=[pl.BlockSpec((rows, n_pad), lambda i: (0, 0)), qspec,
                  qspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="tree_dist_pairs",
    )(planes, a, b)
