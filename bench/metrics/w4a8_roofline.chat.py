"""Share of its roofline that the packed-int4 x int8 matmul kernel
reaches: the least time of every call in the traced interval (the larger
of its int8 operations at the chip's int8 peak and its bytes at HBM peak)
over the kernel's device time. Decode calls carry the engine's slots as
rows; a tail wave's body calls its prompt tokens, its head call its rows.
"""
from bench.lib import costs, peaks, readers


def read(rec):
    c = rec["config"]
    pk = peaks.peaks(rec["device"]["kind"])
    kt = (readers.kernel_seconds(rec, readers.DECODE, readers.is_w4a8)
          + sum(readers.kernel_seconds(rec, p, readers.is_w4a8)
                for p in readers.PREFILL))
    if kt <= 0:
        return None
    m = costs.dims(c)
    least = 0.0
    steps = readers.delta(rec, "decode_steps")
    per_step = sum(costs.least_time(*costs.w4a8_call(
        rec["engine"]["slots"], K, N, b), pk["int8_ops"],
        pk["hbm_bytes_per_s"])[0] for K, N, b in costs.linears(c))
    head = costs.least_time(*costs.w4a8_call(
        rec["engine"]["slots"], m["d"], m["V"], False), pk["int8_ops"],
        pk["hbm_bytes_per_s"])[0]
    least += steps * (m["L"] * per_step + head)
    for rows, toks in readers.tail_waves(rec):
        least += m["L"] * sum(costs.least_time(*costs.w4a8_call(
            toks, K, N, b), pk["int8_ops"], pk["hbm_bytes_per_s"])[0]
            for K, N, b in costs.linears(c))
        least += costs.least_time(*costs.w4a8_call(
            rows, m["d"], m["V"], False), pk["int8_ops"],
            pk["hbm_bytes_per_s"])[0]
    return 100.0 * least / kt
