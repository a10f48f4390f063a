"""Device time per call of the QAT step of its operations under the
``optimizer`` scope (``launch.steps.make_train_step`` around the global
norm clip, the learning-rate schedule and AdamW) (``bench/lib/phases.py``).
"""
from bench.lib import phases

PHASE = "optimizer"


def read(rec):
    ms = phases.run_ms(rec)
    return ms[PHASE] if ms else None
