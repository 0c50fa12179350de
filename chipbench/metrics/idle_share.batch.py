"""idle_share.batch: % of the traced window in which no operation ran
on the device."""
from chipbench import readers


def read(run):
    return readers.idle_pct(run)
