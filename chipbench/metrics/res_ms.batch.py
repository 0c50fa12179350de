"""res_ms.batch: device ms per batch call of the RES stage's operations
(root-path resistance sums, criticality and ball radii) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "RES", per_graph=False)
