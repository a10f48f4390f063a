"""Device time per call of the QAT step outside the operations of the
``layers``, ``vocab`` and ``optimizer`` phases: operations under none of
their scopes and the time between operations, so that the four add up to
``train_step_ms``; a large value says the other three miss part of the
step (``bench/lib/phases.py``)."""
from bench.lib import phases

PHASE = phases.UNSCOPED


def read(rec):
    ms = phases.run_ms(rec)
    return ms[PHASE] if ms else None
