"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, qpos, kpos, *, causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """q/k/v: (BH, S, d); qpos/kpos: (S,) with -1 = padding."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (kpos >= 0)[None, None, :]
    if causal:
        mask = mask & (kpos[None, None, :] <= qpos[None, :, None])
    if window is not None:
        mask = mask & (kpos[None, None, :] > qpos[None, :, None] - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def bucket_rank_hist_ref(digits: jax.Array):
    """Stable rank within bucket + histogram, O(L * 256) dense."""
    nb = 256
    # dtypes pinned: under x64 a bare arange / unpinned sum would widen
    # to int64 and diverge from the int32 kernel outputs
    onehot = (digits[:, None] == jnp.arange(nb, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)
    within = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - onehot
    rank = jnp.sum(within * onehot, axis=1, dtype=jnp.int32)
    hist = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    return rank, hist


def bitmap_intersect_any_ref(m1: jax.Array, m2: jax.Array) -> jax.Array:
    return jnp.any(jnp.bitwise_and(m1, m2) != 0, axis=1)


def laplacian_spmv_ref(u: jax.Array, v: jax.Array, w: jax.Array,
                       x: jax.Array) -> jax.Array:
    """y = L x via segment scatter-adds — the production formulation
    (core/spectral_probe.laplacian_spmv), so the Pallas kernel is
    validated against the exact code the estimator runs by default."""
    d = x[u] - x[v]
    c = w.astype(x.dtype)[:, None] * d
    return jnp.zeros_like(x).at[u].add(c).at[v].add(-c)
