"""Device time of one QAT step: the compiled train step's time in the
trace over its number of calls there."""
from bench.lib import readers


def read(rec):
    t = rec["trace"]["programs"].get(readers.TRAIN, 0.0)
    n = rec["trace"]["calls"].get(readers.TRAIN, 0)
    return 1e3 * t / n if n else None
