"""Flash attention forward kernel (Pallas TPU).

Tiling: grid = (B*H, S_q/block_q, S_k/block_k) with the k dimension
innermost and sequential ("arbitrary"); online-softmax statistics (m, l)
and the output accumulator live in VMEM scratch and persist across the k
iterations of one q block — the TPU-native version of flash attention's
SRAM tiling (HBM -> VMEM -> MXU instead of HBM -> shared mem -> tensor
cores). Causal and sliding-window masks come in as position vectors, so
the same kernel serves train, prefill and windowed (hymba) layers.

Block shapes default to (128, 128): MXU-aligned on both matmul dims.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, scale, causal,
               window: Optional[int], n_kblocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # (bq, d)
    k = k_ref[0]                      # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qp = qpos_ref[...]                # (bq,)
    kp = kpos_ref[...]                # (bk,)
    mask = (kp >= 0)[None, :]
    if causal:
        mask = mask & (kp[None, :] <= qp[:, None])
    if window is not None:
        mask = mask & (kp[None, :] > qp[:, None] - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                    jax.lax.dot_general(
                        p.astype(v_ref.dtype), v_ref[0],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_cur

    @pl.when(ki == n_kblocks - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,        # (BH, Sq, d)
    k: jax.Array,        # (BH, Sk, d)
    v: jax.Array,        # (BH, Sk, d)
    qpos: jax.Array,     # (Sq,) int32, -1 = padding
    kpos: jax.Array,     # (Sk,) int32, -1 = padding
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window, n_kblocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((block_q,), lambda b, qi, ki: (qi,)),
            pl.BlockSpec((block_k,), lambda b, qi, ki: (ki,)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qpos, kpos, q, k, v)
