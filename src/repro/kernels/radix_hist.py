"""Radix histogram / rank kernel (Pallas TPU) — LGRASS §3.3 on the MXU.

The CPU radix sort keeps 256 scalar bucket counters in one cache page.
The TPU adaptation turns bucket counting into dense linear algebra:

    one_hot  = (digits[:, None] == iota(256))          (C, 256) on the VPU
    hist    += one_hot^T @ 1                            column sum
    rank     = one_hot @ carry + row-prefix(one_hot)    MXU matmul + cumsum

The grid walks chunks sequentially ("arbitrary"); the running per-bucket
carry lives in VMEM scratch, so one kernel pass yields every element's
stable rank *within its bucket* plus the global histogram — exactly the
two quantities a counting-sort pass needs. ops.py composes 4 passes of
this into the full uint32 radix argsort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NB = 256


def _hist_kernel(d_ref, rank_ref, hist_ref, carry_ref, *, n_chunks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    d = d_ref[...]                                    # (C,) int32
    c = d.shape[0]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (c, NB), 1)
    onehot = (d[:, None] == buckets).astype(jnp.int32)      # (C, NB)
    # sum/cumsum dtypes pinned: x64 promotion would widen to int64 and
    # the stores into the int32 refs fail
    within = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - onehot
    carry = carry_ref[...]                                  # (NB,)
    # rank = carry[digit] + row prefix, both as dense contractions
    rank = (jnp.sum(onehot * carry[None, :], axis=1, dtype=jnp.int32) +
            jnp.sum(within * onehot, axis=1, dtype=jnp.int32))
    rank_ref[...] = rank
    carry_ref[...] = carry + jnp.sum(onehot, axis=0, dtype=jnp.int32)

    @pl.when(i == n_chunks - 1)
    def _flush():
        hist_ref[...] = carry_ref[...]


def bucket_rank_hist(digits: jax.Array, *, chunk: int = 1024,
                     interpret: bool = False):
    """digits: (L,) int32 in [0, 256). Returns (rank_in_bucket, hist)."""
    m = digits.shape[0]
    assert m % chunk == 0, "pad digits to a chunk multiple"
    n_chunks = m // chunk
    kernel = functools.partial(_hist_kernel, n_chunks=n_chunks)
    rank, hist = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((chunk,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((NB,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((NB,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((NB,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(digits)
    return rank, hist
