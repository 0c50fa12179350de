"""shard_ms.x4: device-idle ms per batch call inside the service's
`svc.shard` host span (the move of the staged batch onto the mesh), in
the traced window, read on the first chip as `xfer_ms.single` reads its
spans. A program without the span reads nothing."""
from chipbench import devtrace, readers

SPANS = ("svc.shard",)


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
    return 1e3 * idle * 1e-9 / len(run.traced)
