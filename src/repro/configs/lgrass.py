"""The paper's own workload: LGRASS graph sparsification cases.

Each "shape" is a graph size; the dry-run lowers the distributed phase-1
(repro.core.distributed) over the production mesh for each case. Sizes
come from the generators' own shape functions, so they are what
`official_case` and `powergrid_like_graph` actually produce.
"""
import dataclasses

from repro.core.graph import OFFICIAL_CASE_SHAPES, powergrid_shape


@dataclasses.dataclass(frozen=True)
class GraphCase:
    name: str
    n_nodes: int
    n_edges: int


def _grid_case(name: str, n_side: int, chord_frac: float,
               seed: int = 0) -> GraphCase:
    return GraphCase(name, *powergrid_shape(n_side, chord_frac))


CASES = {
    "case1_4k": _grid_case("case1_4k", **OFFICIAL_CASE_SHAPES["case1"]),
    "case2_7k": _grid_case("case2_7k", **OFFICIAL_CASE_SHAPES["case2"]),
    "case3_16k": _grid_case("case3_16k", **OFFICIAL_CASE_SHAPES["case3"]),
    # powergrid_like_graph(1024, 0.25): the 10^6-node grid
    "grid_1m": _grid_case("grid_1m", n_side=1024, chord_frac=0.25),
}
