"""mark_ms.single: device ms per graph of the MARK stage's operations
(phase-1 marking and its per-edge views) in the traced window, read through the
program's stage scopes (stages.py)."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    return stages.stage_ms(run, "MARK", per_graph=True)
