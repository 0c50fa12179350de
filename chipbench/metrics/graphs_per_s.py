"""graphs_per_s: graphs completed in the window over its seconds."""
from chipbench import readers


def read(run):
    n = readers.graphs(run.calls)
    return n / run.window_s if n else None
