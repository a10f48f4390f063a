"""Device time of the decode program per decode step (the engine's own
step counter over the traced interval)."""
from bench.lib import readers


def read(rec):
    steps = readers.delta(rec, "decode_steps")
    t = rec["trace"]["programs"].get(readers.DECODE, 0.0)
    return 1e3 * t / steps if steps > 0 and t > 0 else None
