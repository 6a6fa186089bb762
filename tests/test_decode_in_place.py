"""The decode step donates its position-indexed caches and writes one
position of them in place, and the runtime's retry from committed state
stays exact over the buffers a failed attempt wrote into."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TINY_ARCHS
from repro.launch.serve import GuardedEngine
from repro.models.model import is_position_indexed, split_caches
from repro.runtime import Completion, Request, ServingRuntime

BACKEND = "xla"

# (arch, prompt_len): recurrentgemma's prompt runs past its window of 16,
# so its ring caches wrap and the written slot evicts the oldest key
CASES = {
    "olmo-1b": 8,             # full K/V
    "recurrentgemma-9b": 20,  # ring K/V beside RG-LRU states
    "minicpm3-4b": 8,         # MLA latent
    "deepseek-v2-lite": 8,    # MLA latent, a leading dense layer's too
    "mamba2-780m": 8,         # SSM state, nothing indexed by position
}
MAX_NEW = 4


def _engine(arch, slots=2):
    cfg = TINY_ARCHS[arch]
    plen = CASES[arch]
    eng = GuardedEngine(cfg, plen + MAX_NEW + 1, slots)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(plen,)).astype(np.int32)
               for _ in range(slots)]
    return eng, prompts


def _decode_args(eng, state, scales):
    indexed, rest = split_caches(state["caches"])
    return (eng.params, indexed, rest, state["tok"],
            jnp.asarray(state["pos"] + state["t"], jnp.int32),
            jnp.asarray(scales, jnp.float32))


@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-9b",
                                  "deepseek-v2-lite"])
def test_guarded_decode_donates_position_indexed_caches(arch):
    eng, prompts = _engine(arch)
    ones = [1.0] * eng.slots
    state, _, _ = eng.start_wave(prompts, ones, BACKEND)

    # every donated leaf is aliased to an output of the lowered step
    args = _decode_args(eng, state, ones)
    text = eng._decode_fn(BACKEND).lower(*args).as_text()
    n_indexed = len(jax.tree.leaves(args[1]))
    assert n_indexed > 0
    assert text.count("tf.aliasing_output") == n_indexed

    calls = 3
    for _ in range(calls):
        indexed, rest = split_caches(state["caches"])
        state, _, census = eng.decode(state, ones, BACKEND)
        assert float(census[-1]) == 0.0
        assert all(x.is_deleted() for x in jax.tree.leaves(indexed))
        # recurrent states are replaced, not donated: the committed state
        # keeps the ones its step started from
        assert not any(x.is_deleted() for x in jax.tree.leaves(rest))
    assert eng.decode_in_place == calls


class _PoisonOnce:
    """The engine protocol, passed through, except at decode call ``at``:
    a live slot gets the logit scale NaN, and every position-indexed cache
    of the committed state gets NaN at the position that attempt wrote --
    as an attempt with a non-finite hidden state would leave it. The
    runtime then retries from that state."""

    def __init__(self, engine, at=1, slot=0):
        self.engine, self.slots = engine, engine.slots
        self.at, self.slot, self.calls = at, slot, 0

    @property
    def decode_in_place(self):
        return self.engine.decode_in_place

    def validate(self, prompt, max_new):
        return self.engine.validate(prompt, max_new)

    def start_wave(self, prompts, scales, backend):
        return self.engine.start_wave(prompts, scales, backend)

    def decode(self, state, scales, backend):
        self.calls += 1
        if self.calls != self.at:
            return self.engine.decode(state, scales, backend)
        scales = list(scales)
        scales[self.slot] = float("nan")
        out = self.engine.decode(state, scales, backend)
        state["caches"] = _nan_at(state["caches"], state["pos"] + state["t"])
        return out


def _nan_at(caches, pos):
    def block(cache):
        if not is_position_indexed(cache):
            return cache
        slot = pos % cache["slot_pos"].shape[-1]
        out = dict(cache)
        for key, c in cache.items():
            if key != "slot_pos":
                a = np.array(c)
                a[..., slot, :] = np.nan
                out[key] = jnp.asarray(a)
        return out

    return {grp: {k: block(c) for k, c in blocks.items()}
            for grp, blocks in caches.items()}


@pytest.mark.parametrize("arch", sorted(CASES))
def test_quarantine_retry_over_donated_cache_is_bitwise(arch):
    eng, prompts = _engine(arch)
    reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    clean = ServingRuntime(eng, quarantine_planner=False).serve(reqs)
    assert all(isinstance(r, Completion) for r in clean)
    clean_calls = eng.decode_in_place

    poison = _PoisonOnce(eng)
    rt = ServingRuntime(poison, quarantine_planner=False)
    out = rt.serve(reqs)
    assert [r.tokens for r in out] == [r.tokens for r in clean]
    snap = rt.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["retries"] == 1
    # MAX_NEW - 1 decode steps, one of them attempted twice
    assert poison.calls == (MAX_NEW - 1) + 1
    assert snap["decode_in_place"] == clean_calls + poison.calls
