"""device_ms.batch: device busy ms in the traced window per batch call
(one dispatch per call in a one-bucket cell)."""
from chipbench import readers


def read(run):
    return readers.busy_ms_per(run, per_graph=False)
