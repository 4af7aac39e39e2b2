"""The general traffic generator: a mix file of parameters in, the
requests of one run out.

The run's seed picks the order and the token ids, never the sizes: a
window of ``seconds`` gets ``rate_per_s x seconds`` requests whose
lengths and gaps between arrivals are stratified draws from the mix's
distributions (the (i + 1/2)/n quantiles, fixed by the file), so runs of
different seeds do the same work in another order.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.harness import rng

# ids of the requests that set-up puts part-way into slots
WARM_ID = 1 << 20


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, as whole numbers
    (multiples of ``round_to`` where given)."""
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    from scipy.stats import norm
    u = (np.arange(n) + 0.5) / n
    x = dist["median"] * np.exp(dist["sigma"] * norm.ppf(u))
    step = dist.get("round_to", 1)
    x = np.rint(x / step) * step
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def _gaps(arrivals: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` stratified gaps between arrivals, scaled to sum to
    ``seconds``: gamma with the given coefficient of variation (1 is a
    Poisson process, more is burstier)."""
    if arrivals["kind"] != "gamma":
        raise ValueError(f"unknown arrival process {arrivals['kind']!r}")
    from scipy.stats import gamma
    u = (np.arange(n) + 0.5) / n
    g = gamma.ppf(u, 1.0 / arrivals["cv"] ** 2)
    return g * (seconds / g.sum())


def _lengths(mix: dict, n: int):
    """Prompt and output lengths of ``n`` requests, paired by a fixed
    shuffle; an answer ends where the cache does."""
    prompts = _quantiles(mix["prompt_len"], n)
    outputs = _quantiles(mix["output_len"], n)
    outputs = outputs[np.random.default_rng(0).permutation(n)]
    return prompts, np.minimum(outputs, mix["cache_len"] - prompts)


def serve_requests(mix: dict, seed: int, seconds: float) -> List[Dict]:
    """The requests due in a window of ``seconds``, in the order they
    fall due: each ``{"id", "due", "prompt_len", "output_len"}``, with
    ``due`` in seconds from the window's start."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    prompts, outputs = _lengths(mix, n)
    gaps = _gaps(mix["arrivals"], n, seconds)
    r = rng(seed, 0)
    order = r.permutation(n)
    gaps = gaps[r.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [{"id": i, "due": float(due[i]), "prompt_len": int(prompts[j]),
             "output_len": int(outputs[j])}
            for i, j in enumerate(order)]


def warm_requests(mix: dict, seed: int) -> List[Dict]:
    """The ``warm_slots`` requests that set-up puts into slots, each left
    with a stratified share of its answer still to come."""
    k = mix["warm_slots"]
    if not k:
        return []
    prompts, outputs = _lengths(mix, k)
    left = (np.arange(k) + 0.5) / k
    left = left[np.random.default_rng(1).permutation(k)]
    order = rng(seed, 2).permutation(k)
    return [{"id": WARM_ID + i, "prompt_len": int(prompts[j]),
             "output_len": max(2, int(np.ceil(left[j] * outputs[j])))}
            for i, j in enumerate(order)]


def prompt_lengths(dist: dict) -> List[int]:
    """Every prompt length the distribution can give."""
    step = dist.get("round_to", 1)
    lo = int(np.ceil(dist["min"] / step) * step)
    return sorted({dist["min"], dist["max"],
                   *range(lo, dist["max"] + 1, step)})


def prompt_tokens(seed: int, req: dict, vocab: int) -> np.ndarray:
    return rng(seed, 1, req["id"]).integers(
        0, vocab, req["prompt_len"], dtype=np.int32)
