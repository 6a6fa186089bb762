"""The one traffic generator. A mix is a data file
(``bench/traffic/<mix>.json``) of parameters; this module turns
(mix, seed, seconds) into exactly what the program receives.

Every seed gets the same set of sizes and arrival gaps -- quantiles of the
mix's distributions -- in the one order the mix's ``order_seed`` draws,
and token contents the seed draws. The order is the mix's, not the
seed's: where a window holds only a few waves, the order decides which
requests share a wave, and so how much work the window holds. So seeds
change the data, never the amount of work.
"""

from __future__ import annotations

import numpy as np

from benchlib.common import rng


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` output lengths at the distribution's quantiles, clipped."""
    u = _quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif kind == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(int)


def train_tokens(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """One training batch: ``(batch, seq + 1)`` int32 token ids, distinct
    for every (seed, step). ``tokens: zipf`` draws a Zipf unigram of the
    mix's exponent (folded into the vocabulary), as text has, so the loss
    falls as a model learns it; ``uniform`` draws every id alike."""
    g = rng(seed, 1, step)
    shape = (mix["batch"], mix["seq"] + 1)
    if mix["tokens"] == "zipf":
        return ((g.zipf(mix["zipf_a"], shape) - 1) % vocab).astype(np.int32)
    return g.integers(0, vocab, shape, dtype=np.int32)


def serve_schedule(mix: dict, vocab: int, seed: int, seconds: float):
    """Open-loop arrivals for a window of ``seconds``: a list of
    ``(due_s, prompt, max_new)`` in due order. The gaps are the quantiles
    of an exponential of the mix's rate (Poisson arrivals), so
    ``rate * seconds`` requests fall due within the window; they and the
    output lengths take the order that the mix's ``order_seed`` draws."""
    n = expected_requests(mix, seconds)
    g = rng(seed, 2)
    order = rng(mix["order_seed"], 2)
    gaps = -np.log1p(-_quantiles(n)) / mix["rate_per_s"]
    gaps = gaps[order.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    outs = _lengths(mix["output"], n)[order.permutation(n)]
    plen = mix["prompt_len"]
    prompts = g.integers(0, vocab, (n, plen), dtype=np.int32)
    return [(float(due[i]), prompts[i], int(outs[i])) for i in range(n)]


def max_new_tokens(mix: dict) -> int:
    return int(mix["output"].get("max", mix["output"].get("value", 1)))


def expected_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))

