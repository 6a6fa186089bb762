"""Paths, file lookup by name, seeds and small statistics shared by the
benchmark's drivers."""

from __future__ import annotations

import json
import math
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# run-time output (traces) inside the checkout, at a fixed path
OUT = ROOT / ".bench_out"


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, traffic mix or cell."""
    path = BENCH / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two 32-bit words from any non-negative seed (seeds pass 2**32)."""
    return np.random.SeedSequence([int(seed), salt]).generate_state(2)


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def jax_key(seed: int, salt: int = 0):
    """A raw threefry key made from the seed."""
    import jax.numpy as jnp

    return jnp.asarray(seed_words(seed, salt), jnp.uint32)


def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` % of the sample at or below it. ``inf`` marks a missing
    sample (a request that failed counts as missing every limit)."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device, as the backend reports it
    (0 where it reports nothing, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
