"""xfer_ms.single: device-idle ms per graph inside the program's
`lgrass.upload` and `lgrass.fetch` host spans, in the traced window:
the time the chip waits on the arguments' upload and the results'
transfer to the host."""
from chipbench import devtrace, readers

SPANS = ("lgrass.upload", "lgrass.fetch")


def read(run):
    if readers.traced_busy_s(run) is None:
        return None
    spans = [(s, e) for n, s, e in run.trace.main if n in SPANS]
    if not spans:
        return None
    lo, hi = run.trace.window
    busy = [(s, e) for _, s, e in run.trace.chips[0]]
    idle = sum(devtrace.covered(spans, gs, ge)
               for gs, ge in devtrace.gaps(busy, lo, hi))
    return 1e3 * idle * 1e-9 / readers.graphs(run.traced)
