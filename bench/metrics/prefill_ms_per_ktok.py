"""Device time of the prefill programs (batched admission and tail waves)
per 1,000 prompt tokens the engine prefilled in the traced interval."""
from bench.lib import readers


def read(rec):
    toks = readers.delta(rec, "prompt_tokens_prefilled")
    t = readers.seconds_of(rec["trace"]["programs"], *readers.PREFILL)
    return 1e3 * t / (toks / 1e3) if toks > 0 and t > 0 else None
