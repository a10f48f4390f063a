"""Hands a Qwen2-family configuration and its reference weights to the
program under test: the program's ``ModelConfig`` from the config file's
keys, and the program's parameter tree from the neutral weight layout of
``bench/reference/qwen2.py``. Nothing here computes what is compared."""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from bench.reference import qwen2 as ref


def model_config(c: Dict):
    from repro.configs.base import BLOCK_ATTN, ModelConfig
    m = ref.dims(c)
    return ModelConfig(
        name=c["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], d_ff=m["f"], vocab_size=m["V"],
        head_dim=m["hd"], qkv_bias=True, tie_embeddings=m["tied"],
        rope_theta=m["theta"], norm_eps=m["eps"], block_pattern=(BLOCK_ATTN,))


def program_params(c: Dict, w: Dict, scales: Optional[Dict] = None) -> Dict:
    """The program's tree (one scanned segment of attention blocks) over
    the weights ``w``. ``scales`` gives each linear's weight step size
    (key per linear, plus ``head``); without it every step size is the
    program's placeholder 1.0, for the program's own calibration."""
    m = ref.dims(c)
    L, V = m["L"], m["V"]
    one = jnp.ones((L,), jnp.float32)

    def lin(name, bias=None):
        out = w[name].shape[-1]
        p = {"w": w[name], "s_in": one,
             "s_w": (scales[name] if scales is not None
                     else jnp.ones((L, 1, out), jnp.float32))}
        if bias is not None:
            p["b"] = w[bias]
        return p

    block = {"ln1": {"w": w["ln1"]}, "ln2": {"w": w["ln2"]},
             "attn": {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
                      "wv": lin("wv", "bv"), "wo": lin("wo"),
                      "s_q": one, "s_k": one, "s_v": one},
             "mlp": {"wg": lin("wg"), "wu": lin("wu"), "wd": lin("wd")}}
    head_s = (scales["head"] if scales is not None
              else jnp.ones((1, V), jnp.float32))
    head = {"s_w": head_s, "s_in": jnp.float32(1.0)}
    if not m["tied"]:
        head["w"] = w["head"]
    return {"embed": {"w": w["embed"]}, "final_norm": {"w": w["final_norm"]},
            "segments": [{"0": block}], "head": head}


def neutral_name(path: str) -> Optional[str]:
    """Neutral name of a program leaf given as a dotted path, or None for
    a leaf the reference does not hold (the unused activation scales)."""
    parts = path.split(".")
    if parts[:1] == ["embed"]:
        return "embed"
    if parts[:1] == ["final_norm"]:
        return "final_norm"
    if parts[:1] == ["head"]:
        return {"s_w": "s_head", "w": "head"}.get(parts[-1])
    leaf = parts[-1]
    if parts[-2] in ("ln1", "ln2"):
        return parts[-2]
    lin = parts[-2]
    if lin not in ref.LINEARS:
        return None
    if leaf == "w":
        return lin
    if leaf == "s_w":
        return "s_" + lin
    if leaf == "b":
        return "b" + lin[1]
    return None
