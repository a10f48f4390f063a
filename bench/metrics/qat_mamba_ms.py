"""Device time per call of the QAT step of its operations under the
``mamba`` scope (``models/model.py``, around each Mamba-2 mixer): in_proj
to out_proj, the SSD included, forward and backward, teacher and student
(``bench/lib/scopes.py``)."""
from bench.lib import scopes

SCOPE = "mamba"


def read(rec):
    ms = scopes.run_ms(rec)
    return ms.get(SCOPE) if ms else None
