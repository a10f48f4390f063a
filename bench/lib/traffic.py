"""The one generator of traffic: reads a mix's parameters from its data
file under ``bench/traffic/`` and draws the requests from ``--seed``.

Every seed gets the same multiset of sizes and of inter-arrival gaps, in
another order: lengths and gaps are the stratified quantiles of their
distributions (fixed by the mix), and the seed permutes them and draws
the token ids. So two seeds differ in order and content, not in work. An
open loop's schedule is cut into the fill, the window and the drain; each
part gets its own multiset, and its gaps are scaled to span it exactly,
so every seed sends the window the same requests at the same mean rate.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional, Tuple

import numpy as np

KINDS = ("open_loop", "train")


@dataclass
class Req:
    """One request as the benchmark sends it."""
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    temperature: float          # <= 0: greedy
    top_k: int
    seed: int
    due: Optional[float] = None  # seconds from the schedule's origin


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped length distribution:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def segments(mix: Dict, seconds: float) -> List[Tuple[float, int]]:
    """(duration, requests) of an open loop's fill, window and drain."""
    rate = mix["rate_per_s"]
    return [(d, max(1, int(round(rate * d)))) for d in
            (mix["fill_seconds"], seconds, mix["drain_seconds"])]


def _segmented(mix: Dict, seconds: float, rng, what):
    """Concatenate, part by part, a permutation of ``what(duration, n)``."""
    return np.concatenate([rng.permutation(what(d, n))
                           for d, n in segments(mix, seconds)])


def _gaps(d: float, n: int) -> np.ndarray:
    """``n`` stratified exponential gaps scaled to sum to ``d``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (d / g.sum())


def requests(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """An open loop's requests, in the order they are sent."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"{mix['kind']!r} mixes send no requests")
    rng = np.random.default_rng(int(seed))
    share = mix["greedy_share"]
    plen = _segmented(mix, seconds, rng,
                      lambda d, k: quantiles(mix["prompt"], k))
    olen = _segmented(mix, seconds, rng,
                      lambda d, k: quantiles(mix["output"], k))
    greedy = _segmented(mix, seconds, rng,
                        lambda d, k: np.arange(k) < round(k * share))
    gaps = _segmented(mix, seconds, rng, _gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    s = mix.get("sampled", {})
    out = []
    for i in range(len(due)):
        out.append(Req(
            index=i,
            prompt=rng.integers(0, vocab, int(plen[i])).astype(np.int32),
            max_new=int(olen[i]),
            temperature=0.0 if greedy[i] else float(s["temperature"]),
            top_k=0 if greedy[i] else int(s["top_k"]),
            seed=int(rng.integers(0, 2 ** 31 - 1)),
            due=float(due[i])))
    return out
