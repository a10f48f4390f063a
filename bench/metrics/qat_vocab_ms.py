"""Device time per call of the QAT step of its operations under the
``embed``, ``head`` or ``kd_loss`` scopes: the embedding gather and its
gradient, the tied head forward and backward, and the KD softmax over the
vocabulary (``bench/lib/phases.py``)."""
from bench.lib import phases

PHASE = "vocab"


def read(rec):
    ms = phases.run_ms(rec)
    return ms[PHASE] if ms else None
