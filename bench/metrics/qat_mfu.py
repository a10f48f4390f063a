"""Model FLOP utilization of the QAT step: the model operations per
trained token (student forward and backward, teacher forward, attention;
recomputation not counted) times the window's trained tokens per second,
over the chip's bf16 peak."""
from bench.lib import costs, peaks


def read(rec):
    pk = peaks.peaks(rec["device"]["kind"])
    per_tok = costs.qat_flops_per_token(rec["config"],
                                        rec["traffic"]["seq_len"])
    return 100.0 * per_tok * rec["e2e"]["train_tok_s"] / pk["bf16_flops"]
