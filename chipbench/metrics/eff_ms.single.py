"""eff_ms.single: device ms per graph of the EFF stage's operations
(root choice, graph BFS and effective weights) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "EFF", per_graph=True)
