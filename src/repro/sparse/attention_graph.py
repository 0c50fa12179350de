"""Beyond-paper integration: LGRASS as a long-context attention
sparsifier.

Long-context attention over S tokens is a dense graph over S/B blocks.
We build a weighted block graph (sliding-window locality edges + content
similarity chords from mean-pooled block embeddings), run the *exact same*
LGRASS pipeline the power-grid task uses, and keep the sparsifier's edges
as the block-sparse attention mask. The spanning tree guarantees every
block can attend along a connected backbone (information can flow
anywhere), and the spectrally-critical chords keep the long-range links
that matter most — the graph-spectral analogue of landmark/global tokens.

This makes the paper's contribution a first-class *framework feature*
(an attention-mask planner in the data/serving plane), not just a
standalone solver.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.core.sparsify import lgrass_sparsify


@dataclasses.dataclass
class BlockMaskPlan:
    n_blocks: int
    mask: np.ndarray        # (n_blocks, n_blocks) bool, causal, incl. diag
    kept_edges: int
    total_edges: int


def build_block_graph(block_feats: np.ndarray, window: int = 2,
                      n_chords_per_block: int = 4,
                      seed: int = 0) -> Graph:
    """block_feats: (NB, d) mean-pooled block embeddings (host numpy)."""
    nb, d = block_feats.shape
    f = block_feats / (np.linalg.norm(block_feats, axis=1, keepdims=True)
                       + 1e-6)
    sim = f @ f.T  # (NB, NB) cosine
    edges = {}
    # locality edges (always candidates, strongly weighted)
    for i in range(nb):
        for j in range(max(0, i - window), i):
            edges[(j, i)] = 2.0 + max(sim[i, j], 0.0)
    # content chords: top-k similar earlier blocks
    for i in range(nb):
        if i <= window:
            continue
        cand = sim[i, : max(i - window, 0)]
        top = np.argsort(-cand)[:n_chords_per_block]
        for j in top:
            key = (min(int(j), i), max(int(j), i))
            edges.setdefault(key, 1.0 + max(float(cand[j]), 0.0))
    u = np.array([a for a, _ in edges], np.int32)
    v = np.array([b for _, b in edges], np.int32)
    w = np.array(list(edges.values()), np.float32)
    g = Graph(n=nb, u=u, v=v, w=w)
    g.validate()
    return g


def plan_block_mask(block_feats: np.ndarray, keep_frac: float = 0.15,
                    window: int = 2) -> BlockMaskPlan:
    """LGRASS-sparsified causal block mask."""
    g = build_block_graph(block_feats, window=window)
    budget = max(1, int(keep_frac * g.n))
    res = lgrass_sparsify(g, budget=budget)
    nb = g.n
    mask = np.zeros((nb, nb), bool)
    np.fill_diagonal(mask, True)
    for eid in np.where(res.edge_mask)[0]:
        a, b = int(g.u[eid]), int(g.v[eid])
        lo, hi = min(a, b), max(a, b)
        mask[hi, lo] = True  # causal: later block attends to earlier
    return BlockMaskPlan(n_blocks=nb, mask=mask,
                         kept_edges=int(res.edge_mask.sum()),
                         total_edges=g.m)


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask_blocks: jax.Array,
                           block: int) -> jax.Array:
    """Exact attention restricted to allowed (q-block, k-block) pairs.

    q/k/v: (B, S, H, D); mask_blocks: (S/block, S/block) bool (causal).
    Reference implementation (dense with mask); the Pallas flash kernel
    consumes the same mask per (qi, ki) tile on real hardware by skipping
    masked tiles.
    """
    b, s, h, d = q.shape
    nb = s // block
    scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    tok_mask = jnp.repeat(jnp.repeat(mask_blocks, block, 0), block, 1)
    causal = jnp.tril(jnp.ones((s, s), bool))
    full = tok_mask & causal
    scores = jnp.where(full[None, None], scores, -1e9)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
