"""graph_ms: the window's seconds over the graphs completed in it, in ms.
A graph is complete when its edge mask is on the host."""
from chipbench import readers


def read(run):
    n = readers.graphs(run.calls)
    return 1e3 * run.window_s / n if n else None
