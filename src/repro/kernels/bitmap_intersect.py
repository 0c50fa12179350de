"""Bitmap set-intersection kernel (Pallas TPU) — LGRASS Alg. 5's
"M_{lca,u} ∩ M_{lca,v} is not empty" test.

The paper accelerates mark-set intersection with bitmaps + SIMD (FESIA
style). The TPU analogue is a VPU kernel over (block, W) uint32 lanes:
AND + any-reduce per edge row, with the edge dimension tiled through VMEM.
One memory pass, no MXU involvement — this is the paper's "classic
acceleration technique for set operations" mapped onto the vector unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _bitmap_kernel(m1_ref, m2_ref, out_ref):
    inter = jnp.bitwise_and(m1_ref[...], m2_ref[...])   # (block, W)
    out_ref[...] = jnp.any(inter != 0, axis=1)


def bitmap_intersect_any(m1: jax.Array, m2: jax.Array, *,
                         block: int = 1024,
                         interpret: bool = False) -> jax.Array:
    """m1, m2: (L, W) uint32 bitmaps. Returns (L,) bool non-empty flags."""
    l, w = m1.shape
    assert m1.shape == m2.shape
    assert l % block == 0, "pad rows to a block multiple"
    return pl.pallas_call(
        _bitmap_kernel,
        grid=(l // block,),
        in_specs=[
            pl.BlockSpec((block, w), lambda i: (i, 0)),
            pl.BlockSpec((block, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((l,), jnp.bool_),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(m1, m2)
