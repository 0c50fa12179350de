"""jit'd public wrappers for the Pallas kernels.

`interpret=None` auto-selects: compiled Mosaic on TPU backends, Pallas
interpret mode elsewhere (CPU CI) — same kernel body either way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bitmap_intersect import bitmap_intersect_any as _bitmap
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.radix_hist import bucket_rank_hist as _brh
from repro.kernels.spmv import laplacian_spmv as _spmv

# Scoped-VMEM limit the kernels compile with (a TPU v5e core has 128
# MiB of VMEM; the compiler's default scope is 16 MiB), and the share of
# it the resident blocks of one call may take — the rest is headroom for
# the compiler's own temporaries. The regime top this admits: spmv at
# n·P = 2^19 (n = 8192 with 64 probes).
VMEM_LIMIT_BYTES = 32 * 2 ** 20
VMEM_BUDGET_BYTES = 24 * 2 ** 20
# node-axis slice the kernels' inner loops walk (lanes, multiple of 128)
NODE_CHUNK = 512


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=None,
                    qpos=None, kpos=None, block_q=128, block_k=128,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, H, d); k/v: (B, Sk, Kv, d) (GQA kv repeated as needed).

    Returns (B, Sq, H, d).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b * h, k.shape[1], d)
    vb = v.transpose(0, 2, 1, 3).reshape(b * h, v.shape[1], d)
    if qpos is None:
        qpos = jnp.arange(sq, dtype=jnp.int32)
    if kpos is None:
        kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    out = flash_attention_bhsd(
        qb, kb, vb, qpos.astype(jnp.int32), kpos.astype(jnp.int32),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=_auto_interpret(interpret))
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def bucket_rank_hist(digits, *, chunk=1024,
                     interpret: Optional[bool] = None):
    m = digits.shape[0]
    pad = (-m) % chunk
    if pad:
        digits = jnp.concatenate(
            [digits, jnp.full((pad,), 255, digits.dtype)])
    rank, hist = _brh(digits.astype(jnp.int32), chunk=chunk,
                      interpret=_auto_interpret(interpret))
    if pad:
        hist = hist.at[255].add(-pad)
        rank = rank[:m]
    return rank, hist


def radix_argsort_u32(keys, *, chunk=1024,
                      interpret: Optional[bool] = None):
    """Stable ascending argsort via 4 byte passes of the Pallas kernel."""
    m = keys.shape[0]
    perm = jnp.arange(m, dtype=jnp.int32)
    for shift in (0, 8, 16, 24):
        cur = keys[perm]
        digits = ((cur >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
        rank, hist = bucket_rank_hist(digits, chunk=chunk,
                                      interpret=interpret)
        offsets = jnp.cumsum(hist) - hist
        pos = offsets[digits] + rank
        perm = jnp.zeros((m,), jnp.int32).at[pos].set(perm)
    return perm


def _round_up(x: int, k: int) -> int:
    return -(-int(x) // k) * k


def _check_vmem(kernel: str, nbytes: int, what: str):
    """Trace-time guard: refuse a kernel call whose resident blocks
    cannot fit VMEM, instead of handing Mosaic a doomed compile."""
    if nbytes > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"{kernel}: {what} needs {nbytes} bytes of VMEM, over the "
            f"{VMEM_BUDGET_BYTES}-byte budget; this size is outside the "
            f"kernel's regime — use the default XLA path")


def _node_chunk(n: int) -> tuple:
    """(n_chunk, n_pad): the in-kernel node-axis slice and the padded
    node count (lane-aligned, a whole number of slices)."""
    n_chunk = min(NODE_CHUNK, _round_up(max(n, 1), 128))
    return n_chunk, _round_up(max(n, 1), n_chunk)


def laplacian_spmv_edges(u, v, w, x, *, block=512,
                         interpret: Optional[bool] = None):
    """y = L x via the gather-scatter spmv kernel. u/v/w: (M,) edge
    list (w == 0.0 marks padding / masked slots); x: (n, P) float32
    probe block. Edges are padded to a block multiple with zero-weight
    self loops, which contribute exactly nothing; nodes are padded to
    whole lane-aligned slices and sliced away."""
    m = u.shape[0]
    if m == 0:
        return jnp.zeros_like(x)
    n, p = x.shape
    n_chunk, n_pad = _node_chunk(n)
    block = min(_round_up(block, 128), _round_up(m, 128))
    # double-buffered x and output blocks + the accumulator, plus the
    # per-step incidence slabs
    _check_vmem("laplacian_spmv_edges",
                5 * p * n_pad * 4 + 3 * n_chunk * block * 4,
                f"an ({n}, {p}) probe block")
    pad = _round_up(m, block) - m
    u = jnp.pad(u.astype(jnp.int32), (0, pad))[None]
    v = jnp.pad(v.astype(jnp.int32), (0, pad))[None]
    w = jnp.pad(w.astype(jnp.float32), (0, pad))[None]
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, 0), (0, n_pad - n)))
    out = _spmv(u, v, w, xt, block=block, n_chunk=n_chunk,
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
                interpret=_auto_interpret(interpret))
    return out[:, :n].T


def bitmap_intersect_any(m1, m2, *, block=1024,
                         interpret: Optional[bool] = None):
    l, w = m1.shape
    pad = (-l) % block
    if pad:
        z = jnp.zeros((pad, w), m1.dtype)
        m1 = jnp.concatenate([m1, z])
        m2 = jnp.concatenate([m2, z])
    out = _bitmap(m1, m2, block=block, interpret=_auto_interpret(interpret))
    return out[:l]
