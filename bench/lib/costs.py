"""Operations and bytes the algorithms need, from the configuration's
sizes alone: what the roofline shares and the MFU figures divide by the
chip's peaks (``bench/lib/peaks.py``)."""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    hd = c.get("head_dim") or d // H
    return {"d": d, "H": H, "Hkv": c["num_key_value_heads"], "hd": hd,
            "f": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def linears(c: Dict) -> Tuple[Tuple[int, int, bool], ...]:
    """(d_in, d_out, bias) of one layer's linears."""
    m = dims(c)
    d, qd, kvd, f = m["d"], m["H"] * m["hd"], m["Hkv"] * m["hd"], m["f"]
    return ((d, qd, True), (d, kvd, True), (d, kvd, True), (qd, d, False),
            (d, f, False), (d, f, False), (f, d, False))


def matmul_params(c: Dict) -> int:
    """Weights that take part in a matrix product per token: every
    layer's linears and the vocabulary head."""
    m = dims(c)
    return m["L"] * sum(i * o for i, o, _ in linears(c)) + m["d"] * m["V"]


def attn_flops_per_ctx(c: Dict) -> int:
    """Forward attention operations per token per position of context
    (QK^T and PV, all layers)."""
    m = dims(c)
    return 4 * m["L"] * m["H"] * m["hd"]


def kv_bytes_per_token(c: Dict) -> int:
    """Int8 K and V plus their f32 per-token scales, all layers."""
    m = dims(c)
    return m["L"] * 2 * m["Hkv"] * (m["hd"] + 4)


def packed_weight_bytes(c: Dict) -> int:
    """Bytes of the w4a8 layout a forward streams: int4 weights, f32
    per-channel scales and biases, every layer and the head."""
    m = dims(c)
    per_layer = sum(i * o // 2 + 4 * o + (4 * o if b else 0)
                    for i, o, b in linears(c))
    return m["L"] * per_layer + m["d"] * m["V"] // 2 + 4 * m["V"]


def w4a8_call(M: int, K: int, N: int, bias: bool) -> Tuple[int, int]:
    """(int8 operations, bytes) of one packed-int4 x int8 matmul of M rows:
    the packed weights, their scales (and bias), the int8 activations and
    their scales, the bf16 output."""
    ops = 2 * M * K * N
    nbytes = (N * K // 2 + 4 * N + (4 * N if bias else 0)
              + M * K + 4 * M + 2 * M * N)
    return ops, nbytes


def w4a8_forward(c: Dict, rows: int) -> Tuple[int, int]:
    """(operations, bytes) of every w4a8 call of one forward over
    ``rows`` tokens: each layer's linears, then the head."""
    m = dims(c)
    ops = nbytes = 0
    for K, N, b in linears(c):
        o, n = w4a8_call(rows, K, N, b)
        ops += m["L"] * o
        nbytes += m["L"] * n
    o, n = w4a8_call(rows, m["d"], m["V"], False)
    return ops + o, nbytes + n


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def qat_flops_per_token(c: Dict, seq: int) -> float:
    """Model operations per trained token of a KD step: the student's
    forward and backward (3 forwards) and the teacher's forward, each
    2 x matmul weights plus causal attention over (seq + 1) / 2 positions
    on average. Recomputation is not counted."""
    fwd = 2 * matmul_params(c) + attn_flops_per_ctx(c) * (seq + 1) / 2
    return 4 * fwd


def decode_attention(c: Dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of decode attention at the given contexts (one
    per decoded token): the visible int8 K/V and their scales are read."""
    ops = nbytes = 0
    for ctx in contexts:
        ops += attn_flops_per_ctx(c) * ctx
        nbytes += kv_bytes_per_token(c) * ctx
    return ops, nbytes
