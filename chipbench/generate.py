"""The benchmark's graph generators and its one traffic generator.

The graph family is a copy of `powergrid_like_graph` (with
`powergrid_shape`) as it stands in `src/repro/core/graph.py`, kept here
so that a change to the program cannot change what the benchmark feeds
it. `test_chipbench_copies.py` checks that it still agrees with the
program's own generator.

`make_calls(config, traffic, seed)` is the traffic generator: it reads a
configuration file (the deployment: graph family, case sizes, budget
rule) and a traffic file (which case, graphs per call, pool size, the
seed of the pool's graphs) and returns the pool of calls the
closed-loop caller sends in order. A graph's own structure sets its loop
rounds, so graphs of one size differ in call time, by up to a third at
16K nodes; the pool is therefore the same graphs in every run, sent in
the same order, and the run's `seed` shuffles each graph's edge list.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    """Undirected weighted edge list: int32 endpoints, float32 weights."""

    n: int
    u: np.ndarray
    v: np.ndarray
    w: np.ndarray

    @property
    def m(self) -> int:
        return int(self.u.shape[0])


def _finish(n, u, v, w, rng) -> Graph:
    perm = rng.permutation(len(u))
    return Graph(n=n, u=u[perm].astype(np.int32), v=v[perm].astype(np.int32),
                 w=w[perm].astype(np.float32))


def powergrid_shape(n_side: int, chord_frac: float = 0.25):
    """(nodes, edges) of `powergrid_like_graph(n_side, chord_frac)`."""
    n = n_side * n_side
    return n, 2 * n_side * (n_side - 1) + int(chord_frac * n)


def powergrid_like_graph(n_side: int, chord_frac: float = 0.25,
                         seed: int = 0) -> Graph:
    """2-D grid plus random chords, lognormal(0, 0.5) weights."""
    rng = np.random.default_rng(seed)
    n, m = powergrid_shape(n_side, chord_frac)
    idx = np.arange(n).reshape(n_side, n_side)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    existing = set(zip(np.minimum(u, v).tolist(), np.maximum(u, v).tolist()))
    n_chords = m - len(u)
    cu, cv = [], []
    while len(cu) < n_chords:
        x, y = int(rng.integers(0, n)), int(rng.integers(0, n))
        if x == y:
            continue
        key = (min(x, y), max(x, y))
        if key in existing:
            continue
        existing.add(key)
        cu.append(x)
        cv.append(y)
    u = np.concatenate([u, np.array(cu, dtype=np.int64)])
    v = np.concatenate([v, np.array(cv, dtype=np.int64)])
    w = rng.lognormal(0.0, 0.5, size=len(u))
    return _finish(n, u, v, w, rng)


def budget(config: dict, n: int) -> int:
    """The configuration's budget rule: a share of n off-tree edges,
    rounded as Python rounds, at least 1 (the baseline's default)."""
    return max(1, int(round(config["budget_share"] * n)))


def shuffled(g: Graph, rng) -> Graph:
    """The same graph with its edge list in another order."""
    p = rng.permutation(g.m)
    return Graph(n=g.n, u=g.u[p], v=g.v[p], w=g.w[p])


def make_calls(config: dict, traffic: dict, seed: int):
    """The pool of calls for one run: `traffic["pool_calls"]` calls of
    `traffic["graphs_per_call"]` graphs each, as (graphs, budgets).

    The graphs come from `traffic["graph_seed"]`, the same in every run;
    `seed` shuffles each graph's edge list."""
    if config["family"] != "powergrid":
        raise ValueError(f"unknown graph family {config['family']!r}")
    case = config["cases"][traffic["case"]]
    rng = np.random.default_rng(int(traffic["graph_seed"]))
    order = np.random.default_rng(int(seed))
    calls = []
    for _ in range(traffic["pool_calls"]):
        graphs = [shuffled(powergrid_like_graph(
            case["n_side"], case["chord_frac"],
            seed=int(rng.integers(0, 2**63 - 1))), order)
            for _ in range(traffic["graphs_per_call"])]
        calls.append((graphs, [budget(config, g.n) for g in graphs]))
    return calls
