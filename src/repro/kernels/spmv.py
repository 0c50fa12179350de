"""Gather-scatter Laplacian spmv kernel (Pallas TPU) — the probe
estimator's inner loop as dense MXU contractions.

y = L x with L = Σ_e w_e (e_u − e_v)(e_u − e_v)ᵀ. Per grid step a block
of C edges builds the signed incidence slab Sᵀ = onehot(u) − onehot(v)
((n, C), VPU compares), and two MXU matmuls do the gather AND the
scatter: dᵀ = xᵀ Sᵀ pulls both endpoints' probe rows in one contraction,
and accᵀ += (w ⊙ dᵀ) S pushes the weighted differences back — no
data-dependent addressing anywhere.
The (P, n) accumulator lives in VMEM scratch across the sequential grid
and flushes once on the last block. Zero-weight columns (edge padding,
masked batch slots) contribute exactly nothing, so the caller only has
to zero w.

Layout (what the chip's compiler accepts): every operand is rank 2 and
lane-dense. Edges are (1, M) rows, and the probe block is transposed to
(P, n) so nodes sit on lanes; the node axis is walked in `n_chunk`
slices by loops inside the kernel, which keeps the compiled code small
for any n. The contractions run at HIGHEST precision: the MXU's default
single bf16 pass would round x to 8 bits of mantissa.

VMEM bound: xᵀ, the accumulator and the output block must fit — the
kernel targets the serving regime (n up to a few thousand), and ops.py
checks the bound at trace time. core/spectral_probe.py keeps the
pure-XLA segment-sum spmv as the default path; this kernel is the
TPU-native swap-in behind `use_spmv_kernel=True` (ops.py pads edges and
nodes and picks interpret mode per backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _spmv_kernel(u_ref, v_ref, w_ref, xt_ref, out_ref, acc_ref, *,
                 n_blocks: int, n_chunk: int, n_chunks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    u = u_ref[...]                                    # (1, C) int32
    v = v_ref[...]
    w = w_ref[...]                                    # (1, C) float32
    p, c = xt_ref.shape[0], u.shape[1]

    def slab(start):
        # signed incidence: +1 at u, −1 at v, 0 elsewhere (a self-loop
        # padding column u == v cancels to all-zero on its own)
        nodes = jax.lax.broadcasted_iota(jnp.int32, (n_chunk, c), 0) + start
        return (jnp.where(nodes == u, 1.0, 0.0)
                - jnp.where(nodes == v, 1.0, 0.0))

    def gather(j, dt):
        start = pl.multiple_of(j * n_chunk, n_chunk)
        xt = xt_ref[:, pl.ds(start, n_chunk)]
        return dt + jnp.dot(xt, slab(start), precision=_HI,
                            preferred_element_type=jnp.float32)

    dt = jax.lax.fori_loop(0, n_chunks, gather,
                           jnp.zeros((p, c), jnp.float32))
    wd = w * dt                                       # (P, C)

    def scatter(j, carry):
        start = pl.multiple_of(j * n_chunk, n_chunk)
        acc_ref[:, pl.ds(start, n_chunk)] += jax.lax.dot_general(
            wd, slab(start), (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_chunks, scatter, 0)

    @pl.when(i == n_blocks - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def laplacian_spmv(u: jax.Array, v: jax.Array, w: jax.Array,
                   xt: jax.Array, *, block: int, n_chunk: int,
                   vmem_limit_bytes: int, interpret: bool = False
                   ) -> jax.Array:
    """u, v: (1, M) int32; w: (1, M) float32 (0.0 on padding slots);
    xt: (P, n_pad) float32 transposed probe block, n_pad a multiple of
    n_chunk. Returns (P, n_pad) float32 (L x)ᵀ."""
    m = u.shape[1]
    p, n_pad = xt.shape
    assert m % block == 0, "pad edges to a block multiple"
    assert n_pad % n_chunk == 0, "pad nodes to a chunk multiple"
    n_blocks = m // block
    kernel = functools.partial(_spmv_kernel, n_blocks=n_blocks,
                               n_chunk=n_chunk, n_chunks=n_pad // n_chunk)
    espec = pl.BlockSpec((1, block), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[espec, espec, espec,
                  pl.BlockSpec((p, n_pad), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((p, n_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="laplacian_spmv",
    )(u, v, w, xt)
