"""sort_ms.single: device ms per graph of the SORT stage's operations
(group keys, group layout and the global criticality order) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "SORT", per_graph=True)
