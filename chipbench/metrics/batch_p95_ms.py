"""batch_p95_ms: 95th percentile over every batch call of the window,
from the call to the last edge mask on the host."""
from chipbench import readers


def read(run):
    return readers.latency_pct_ms(run, 95)
