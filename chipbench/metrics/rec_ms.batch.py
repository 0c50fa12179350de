"""rec_ms.batch: device ms per batch call of the REC stage's operations
(recovery replay and the output reductions) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "REC", per_graph=False)
