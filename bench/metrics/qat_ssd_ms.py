"""Device time per call of the QAT step of its operations under the
``ssd`` scope (``models/recurrent.mamba2_fwd``, around the chunked scan),
forward and backward, teacher and student (``bench/lib/scopes.py``)."""
from bench.lib import scopes

SCOPE = "ssd"


def read(rec):
    ms = scopes.run_ms(rec)
    return ms.get(SCOPE) if ms else None
