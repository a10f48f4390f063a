"""Hands a Granite 4.0-H hybrid configuration and its reference weights to
the program under test: the program's ``ModelConfig`` from the config
file's keys (the kept layers of ``layer_types`` as one block pattern, so
one scanned segment of one repeat), and the program's parameter tree from
the neutral weight layout of ``bench/reference/granite_hybrid.py``.
Nothing here computes what is compared."""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from bench.reference import granite_hybrid as ref

KINDS = {"mamba": "mamba2", "attention": "attn"}


def model_config(c: Dict):
    from repro.configs.base import ModelConfig
    m = ref.dims(c)
    return ModelConfig(
        name=c["name"], family="hybrid", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], d_ff=m["f"], vocab_size=m["V"],
        head_dim=m["hd"], tie_embeddings=True, rope_theta=0.0,
        norm_eps=m["eps"], block_pattern=tuple(KINDS[k] for k in m["kinds"]),
        ssm_heads=m["Hs"], ssm_head_dim=m["P"], ssm_state=m["N"],
        ssm_chunk=m["chunk"], conv1d_width=m["K"],
        embedding_multiplier=m["emb_mult"],
        residual_multiplier=m["res_mult"],
        attention_multiplier=m["attn_mult"],
        logits_scaling=m["logits_scaling"])


def program_params(c: Dict, w: Dict, scales: Optional[Dict] = None) -> Dict:
    """The program's tree over the weights ``w``: block ``i`` of the one
    segment is layer ``i``, each leaf with the segment's leading repeat
    axis of 1. ``scales`` gives each linear's weight step size (the
    reference's ``<layer>.s_<linear>`` and ``s_head``); without it every
    step size is the program's placeholder 1.0, for the program's own
    calibration."""
    m = ref.dims(c)
    one = jnp.ones((1,), jnp.float32)

    def lin(i, name):
        wt = w[f"{i}.{name}"]
        s = (scales[f"{i}.s_{name}"] if scales is not None
             else jnp.ones((1, wt.shape[-1]), jnp.float32))
        return {"w": wt[None], "s_in": one, "s_w": s[None]}

    def block(i, kind):
        g = lambda n: w[f"{i}.{n}"][None]      # noqa: E731
        b = {"ln1": {"w": g("ln1")}, "ln2": {"w": g("ln2")},
             "mlp": {n: lin(i, n) for n in ref.MLP_LINEARS}}
        if kind == "attention":
            b["attn"] = {**{n: lin(i, n) for n in ref.ATTN_LINEARS},
                         "s_q": one, "s_k": one, "s_v": one}
        else:
            b["mamba"] = {"in_proj": lin(i, "in_proj"),
                          "out_proj": lin(i, "out_proj"),
                          "conv_w": g("conv_w"), "conv_b": g("conv_b"),
                          "A_log": g("A_log"), "D": g("D"),
                          "dt_bias": g("dt_bias"), "norm": {"w": g("norm")}}
        return b

    head_s = (scales["s_head"] if scales is not None
              else jnp.ones((1, m["V"]), jnp.float32))
    return {"embed": {"w": w["embed"]}, "final_norm": {"w": w["final_norm"]},
            "segments": [{str(i): block(i, k)
                          for i, k in enumerate(m["kinds"])}],
            "head": {"s_w": head_s, "s_in": jnp.float32(1.0)}}


def neutral_name(path: str) -> Optional[str]:
    """Neutral name of a program leaf given as a dotted path, or None for
    a leaf the reference does not hold (the unused activation scales)."""
    parts = path.split(".")
    if parts[0] in ("embed", "final_norm"):
        return parts[0]
    if parts[0] == "head":
        return "s_head" if parts[-1] == "s_w" else None
    i, leaf = parts[2], parts[-1]          # segments.0.<layer>. ... .<leaf>
    owner = parts[-2]
    if owner in ref.LINEARS:
        return {"w": f"{i}.{owner}", "s_w": f"{i}.s_{owner}"}.get(leaf)
    if owner in ("ln1", "ln2", "norm"):
        return f"{i}.{owner}"
    if leaf in ("conv_w", "conv_b", "A_log", "D", "dt_bias"):
        return f"{i}.{leaf}"
    return None
