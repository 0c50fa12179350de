"""mst_ms.single: device ms per graph of the MST stage's operations
(effective-weight rank sort and Borůvka spanning tree) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "MST", per_graph=True)
