"""Fig. 5 analogue: LGRASS runtime vs graph size on random test cases.

The paper's claim is strict linearity as size scales. We time the device
pipeline (phase 1, fully-jitted) over a geometric size ladder and report
the least-squares exponent of log(time) vs log(edges) — linear means
exponent ~1. (Host recovery excluded: it is output-sensitive and tiny.)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import random_connected_graph
from repro.core.sparsify import phase1_device


def _time_phase1(g, reps=2):
    u = jnp.asarray(g.u, jnp.int32)
    v = jnp.asarray(g.v, jnp.int32)
    w = jnp.asarray(g.w, jnp.float32)

    def call():
        return phase1_device(u, v, w, g.n, 8)

    jax.block_until_ready(call())  # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run(quick: bool = False):
    sizes = [2_000, 4_000, 8_000] if quick else [4_000, 8_000, 16_000,
                                                 32_000]
    rows = []
    logs = []
    for n in sizes:
        g = random_connected_graph(n, 2 * n, seed=n)
        t = _time_phase1(g, reps=1 if n >= 32_000 else 2)
        rows.append((f"fig5.lgrass_n{n}", t * 1e6, g.m))
        logs.append((np.log(g.m), np.log(t)))
    x = np.array([a for a, _ in logs])
    y = np.array([b for _, b in logs])
    slope = float(np.polyfit(x, y, 1)[0])
    rows.append(("fig5.scaling_exponent", 0.0, round(slope, 3)))
    return rows
