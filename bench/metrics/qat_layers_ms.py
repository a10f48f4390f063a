"""Device time per call of the QAT step of its operations under the
``layers`` scope (``models.model.forward`` around the block stack): the
teacher's and the student's blocks, forward and backward, fake-quant
included (``bench/lib/phases.py``)."""
from bench.lib import phases

PHASE = "layers"


def read(rec):
    ms = phases.run_ms(rec)
    return ms[PHASE] if ms else None
