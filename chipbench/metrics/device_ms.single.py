"""device_ms.single: device busy ms in the traced window per graph
completed in it (the fused program, one dispatch per graph)."""
from chipbench import readers


def read(run):
    return readers.busy_ms_per(run, per_graph=True)
