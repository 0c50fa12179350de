"""lockstep_ms.batch: device ms per batch call that the vmapped while
loops spend on lanes already done: over the counted loops, the ms of
the loop's `while` operation per call (stages.py) times the share of
its lane rounds that no graph needed, 1 - loop_rounds / loop_lane_rounds
of the service's `ServiceStats` over the window."""
from chipbench import stages

prepare = stages.prepare


def read(run):
    stats = getattr(getattr(run.entry, "svc", None), "stats", None)
    rounds = getattr(stats, "loop_rounds", None)
    lanes = getattr(stats, "loop_lane_rounds", None)
    times = stages.loop_ms(run)
    if times is None or not rounds or not lanes:
        return None
    return sum(ms * (1.0 - rounds[lp] / lanes[lp])
               for lp, ms in times.items() if lanes.get(lp))
