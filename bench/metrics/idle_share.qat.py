"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced window)."""
from bench.lib import readers


def read(rec):
    return readers.idle_share_pct(rec)
