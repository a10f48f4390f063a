"""The whole serve step's share of the chip's peak: the least time for
the traced interval's model work over the interval. The least time is
the larger of the model's operations at the int8 peak (2 x matmul
weights per prompt or decoded token, plus decode attention over each
decoded token's context) and its bytes at HBM peak (the packed weights
once per decode step and per tail wave, the K/V of every decoded token's
context read, and every new token's K/V written). Attention over the
prompts is not counted, so this is a lower bound."""
from bench.lib import costs, peaks, readers


def read(rec):
    c = rec["config"]
    pk = peaks.peaks(rec["device"]["kind"])
    t0, t1 = rec["trace_host"]
    ctx = readers.decode_contexts(rec)
    prompt = readers.delta(rec, "prompt_tokens_prefilled")
    waves = readers.delta(rec, "decode_steps") + len(readers.tail_waves(rec))
    if t1 <= t0 or waves <= 0:
        return None
    a_ops, a_bytes = costs.decode_attention(c, ctx)
    ops = 2 * costs.matmul_params(c) * (prompt + len(ctx)) + a_ops
    nbytes = (costs.packed_weight_bytes(c) * waves + a_bytes
              + costs.kv_bytes_per_token(c) * (prompt + len(ctx)))
    t, _ = costs.least_time(ops, nbytes, pk["int8_ops"],
                            pk["hbm_bytes_per_s"])
    return 100.0 * t / (t1 - t0)
