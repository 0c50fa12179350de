"""lca_ms.single: device ms per graph of the LCA stage's operations
(tree rooting, lifting tables and edge LCAs) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "LCA", per_graph=True)
