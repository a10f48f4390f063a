"""Share of its roofline that the paged decode attention kernel reaches:
the least time of the decode attention the traced interval needed (for
each decoded token, every layer reads the visible int8 K/V and their
scales, and computes QK^T and PV over them) over the kernel's device time
in the decode program."""
from bench.lib import costs, peaks, readers


def read(rec):
    pk = peaks.peaks(rec["device"]["kind"])
    kt = readers.kernel_seconds(rec, readers.DECODE, readers.is_paged_attn)
    ctx = readers.decode_contexts(rec)
    if kt <= 0 or not ctx:
        return None
    ops, nbytes = costs.decode_attention(rec["config"], ctx)
    t, _ = costs.least_time(ops, nbytes, pk["bf16_flops"],
                            pk["hbm_bytes_per_s"])
    return 100.0 * t / kt
