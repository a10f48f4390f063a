"""Model FLOP utilization of the QAT step of a Mamba-2 + attention hybrid:
the model operations per trained token (``bench/lib/costs_hybrid.py``:
linears, head, causal attention, the chunked SSD; student forward and
backward, teacher forward; recomputation not counted) times the window's
trained tokens per second, over the chip's bf16 peak."""
from bench.lib import costs_hybrid, peaks


def read(rec):
    pk = peaks.peaks(rec["device"]["kind"])
    per_tok = costs_hybrid.qat_flops_per_token(rec["config"],
                                               rec["traffic"]["seq_len"])
    return 100.0 * per_tok * rec["e2e"]["train_tok_s"] / pk["bf16_flops"]
