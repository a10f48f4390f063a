"""Model operations per trained token of the QAT step of a Granite 4.0-H
hybrid configuration (Mamba-2 and attention layers by ``layer_types``),
from the configuration's sizes alone, for ``qat_mfu.hybrid``."""
from __future__ import annotations

from typing import Dict


def _sizes(c: Dict) -> Dict[str, int]:
    d, H = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // H
    Hs, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    return {"d": d, "qd": H * hd, "kvd": c["num_key_value_heads"] * hd,
            "H": H, "hd": hd, "f": c["shared_intermediate_size"],
            "Hs": Hs, "P": P, "N": N, "inner": Hs * P,
            "V": c["vocab_size"], "chunk": c["mamba_chunk_size"]}


def matmul_params(c: Dict) -> int:
    """Weights that take part in a matrix product per token: each kept
    layer's linears (in_proj and out_proj, or q, k, v and o; the SwiGLU
    MLP) and the tied head."""
    m = _sizes(c)
    d, inner, N, Hs = m["d"], m["inner"], m["N"], m["Hs"]
    mamba = d * (2 * inner + 2 * N + Hs) + inner * d
    attn = 2 * d * m["qd"] + 2 * d * m["kvd"]
    mlp = 3 * d * m["f"]
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return sum((attn if k == "attention" else mamba) + mlp
               for k in kinds) + d * m["V"]


def ssd_flops_per_token(c: Dict, seq: int) -> int:
    """Forward operations per token of one layer's chunked SSD at the
    configuration's chunk length: its row of C B^T, the masked product
    with x dt over the chunk, its share of the chunk state, and the state
    read out through C."""
    m = _sizes(c)
    L = min(m["chunk"], seq)
    hp = m["Hs"] * m["P"]
    return 2 * L * m["N"] + 2 * L * hp + 2 * hp * m["N"] + 2 * hp * m["N"]


def qat_flops_per_token(c: Dict, seq: int) -> float:
    """Student forward and backward (3 forwards) and the teacher's forward:
    2 x matmul weights, causal attention over (seq + 1) / 2 positions on
    average in each attention layer, and each Mamba-2 layer's SSD.
    Recomputation is not counted."""
    m = _sizes(c)
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    n_attn = sum(k == "attention" for k in kinds)
    fwd = (2 * matmul_params(c)
           + n_attn * 4 * m["H"] * m["hd"] * (seq + 1) / 2
           + (len(kinds) - n_attn) * ssd_flops_per_token(c, seq))
    return 4 * fwd
