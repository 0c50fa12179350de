"""Drive `SparsifyService.sparsify` with the batch axis sharded over the
configuration's chips: `SparsifyService(mesh=batch_mesh(chips))`,
otherwise at its default settings, one batch of graphs per call, every
result's edge mask on the host before the call returns."""
from __future__ import annotations


class Entry:
    def __init__(self, config: dict, calls: list):
        from repro.core.distributed import batch_mesh
        from repro.core.graph import Graph
        from repro.serve.sparsify_service import SparsifyService

        self.svc = SparsifyService(mesh=batch_mesh(config["chips"]))
        self.calls = [([Graph(n=g.n, u=g.u, v=g.v, w=g.w) for g in graphs],
                       budgets) for graphs, budgets in calls]

    def warm(self):
        """Compile (or load) every bucket program the pool needs at its
        batch sizes, then serve one batch so the host path is warm."""
        sizes = sorted({(g.n, g.m) for graphs, _ in self.calls
                        for g in graphs})
        batch_sizes = sorted({len(graphs) for graphs, _ in self.calls})
        budgets = sorted({b for _, bs in self.calls for b in bs})
        self.svc.warmup(sizes, batch_sizes=batch_sizes, budgets=budgets)
        self.call(0)

    def call(self, i: int) -> list:
        graphs, budgets = self.calls[i]
        return [r.edge_mask for r in self.svc.sparsify(graphs, budget=budgets)]

    def start_window(self):
        """Count the service's padding, compiles and loop rounds over the
        window only."""
        self.svc.stats = type(self.svc.stats)()

    def counters(self) -> dict:
        """The service's counters; a program without a counter leaves it
        out."""
        s = self.svc.stats
        out = {"padding_overhead": s.padding_overhead,
               "n_dispatches": s.n_dispatches,
               "n_on_path_compiles": s.n_on_path_compiles}
        for name in ("loop_rounds", "loop_lane_rounds", "loop_chip_rounds"):
            value = getattr(s, name, None)
            if value is not None:
                out[name] = dict(value)
        return out

    def close(self):
        self.svc = None
        self.calls = None
