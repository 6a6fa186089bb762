"""DeepSeek-V2-Lite's mechanisms against the plain reference
(``bench/benchlib/reference_mla_moe.py``), at the TINY size on seeded
random weights: MLA without q-LoRA under YaRN, the dense first layer,
softmax top-k routing over every routed expert with only the held experts
computed, the shared experts, the dropless serving dispatch and its
counters, and the step programs' scopes."""

from __future__ import annotations

import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import reference_mla_moe as REF  # noqa: E402
from benchlib import weights  # noqa: E402

from repro.configs import ARCHS, TINY_ARCHS  # noqa: E402
from repro.launch.serve import GuardedEngine  # noqa: E402
from repro.launch.steps import make_decode_step, make_prefill_step  # noqa: E402
from repro.models import mla as MLA  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.model import split_caches  # noqa: E402

ARCH = "deepseek-v2-lite"
CFG = TINY_ARCHS[ARCH]
BACKEND = "xla"
KEY = jnp.asarray([7, 11], jnp.uint32)
# of the logit scale. The program scores attention in bfloat16 (prefill's
# flash attention, decode's latent scores), which moves TINY's logits by
# ~0.4% of their scale, and a router near-tie that this rounding flips
# moves a position by up to ~3%; a missing YaRN factor moves them by 30-47%.
TOL = 5e-2


def _m(cfg=CFG) -> dict:
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def params():
    return weights.make_params(CFG, KEY)


@pytest.fixture(scope="module")
def ref():
    return REF.ServeReference(_m(), KEY)


def _prompts(n, plen, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=(plen,)).astype(np.int32)
            for _ in range(n)]


# ---- (a) prefill and decode through the engine against the full forward ----

def test_prefill_and_decode_logits_match_reference(params, ref):
    """Prefill then decode steps (the guarded engine's own step functions)
    against the reference's full forward at every position they produce,
    within ``TOL`` of the logit scale."""
    plen, steps = 8, 5
    toks = np.stack(_prompts(2, plen + steps, seed=1))
    want = np.asarray(ref.logits_fn()(toks))
    prefill = jax.jit(make_prefill_step(CFG, plen + steps + 1))
    decode = jax.jit(make_decode_step(CFG, greedy=False))
    logits, caches = prefill(params, jnp.asarray(toks[:, :plen]))
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, plen - 1],
                               atol=TOL * scale)
    for t in range(steps):
        logits, caches = decode(params, caches,
                                jnp.asarray(toks[:, plen + t:plen + t + 1]),
                                jnp.asarray(plen + t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[:, 0],
                                   want[:, plen + t], atol=TOL * scale)


def test_guarded_engine_serves_the_reference_greedy_tokens(params, ref):
    """Through ``GuardedEngine`` (prefill, then decode on the donated
    caches): every served token's reference logit lies within ``TOL`` of
    the logit scale of the reference's best there (bfloat16 scores)."""
    plen, max_new, slots = 8, 6, 3
    eng = GuardedEngine(CFG, plen + max_new + 1, slots)
    eng.params = params
    prompts = _prompts(slots, plen, seed=2)
    ones = [1.0] * slots
    state, tok, _ = eng.start_wave(prompts, ones, BACKEND)
    served = [tok]
    for _ in range(max_new - 1):
        state, tok, _ = eng.decode(state, ones, BACKEND)
        served.append(tok)
    served = np.stack(served, 1)                        # (slots, max_new)
    seqs = np.concatenate([np.stack(prompts), served], 1)
    want = np.asarray(ref.logits_fn()(seqs))
    at = want[:, plen - 1:plen - 1 + max_new]
    picked = np.take_along_axis(at, served[..., None], -1)[..., 0]
    gap = at.max(-1) - picked
    assert gap.max() <= TOL * np.abs(want).max(), gap


# ---- (b) the held shares add up to the whole layer --------------------------

def test_held_shares_sum_to_the_uncut_layer(rng):
    """Two disjoint shares of the routed experts (4 each of 8), each with
    the shared experts: their sum, with the shared experts counted once,
    is the layer that holds all 8 (float32, 1e-5), and that is the
    reference's uncut layer within 2e-3 of its scale: the router's softmax
    sums through the reduction engine, whose row sum is good to ~5e-4
    relative (it scales each token's gates alike and picks the same
    experts)."""
    whole = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, held_start=0, held_count=None))
    p, _ = MOE.moe_init(jax.random.PRNGKey(3), whole)
    x = jnp.asarray(rng.randn(2, 12, CFG.d_model).astype(np.float32))
    total = 0.0
    for start in (0, 4):
        share = dataclasses.replace(CFG, moe=dataclasses.replace(
            CFG.moe, held_start=start, held_count=4))
        ps = dict(p, **{k: p[k][start:start + 4]
                        for k in ("gate", "up", "down")})
        y, _ = MOE.moe_apply_dropless(ps, x, share)
        total = total + y
    shared = MOE._shared(p, x, whole)
    uncut, _ = MOE.moe_apply_dropless(p, x, whole)
    np.testing.assert_allclose(np.asarray(total - shared), np.asarray(uncut),
                               atol=1e-5)
    m = _m(whole)
    rp = {"router": p["router"], "e_gate": p["gate"], "e_up": p["up"],
          "e_down": p["down"], "s_gate": p["shared"]["gate"]["w"],
          "s_up": p["shared"]["up"]["w"], "s_down": p["shared"]["down"]["w"]}
    with jax.default_matmul_precision("highest"):
        want = REF.moe(m, REF.make_ein("f32"), rp, x)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want),
                               atol=2e-3 * np.abs(np.asarray(want)).max())


# ---- (c) dropless -----------------------------------------------------------

def test_dropless_logits_do_not_depend_on_wave_mates(params, ref):
    """A prompt whose own tokens all route alike (one token repeated),
    served alone and in a wave whose other prompts route unevenly too:
    the same prefill logits (1e-5 of the logit scale: rows are computed
    apart), and the reference's (``TOL``). A capacity dispatch drops the
    repeated token's pairs past its capacity and misses the reference."""
    plen = 16
    prefill = jax.jit(make_prefill_step(CFG, plen + 1))
    mine = np.full((plen,), 5, np.int32)
    mates = [np.full((plen,), 9, np.int32), _prompts(1, plen, seed=4)[0],
             np.full((plen,), 5, np.int32)]
    alone, _ = prefill(params, jnp.asarray(mine[None]))
    wave, _ = prefill(params, jnp.asarray(np.stack([mine] + mates)))
    want = np.asarray(ref.logits_fn()(mine[None]))[0, -1]
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(wave)[0, 0], np.asarray(alone)[0, 0],
                               atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(alone)[0, 0], want,
                               atol=TOL * scale)


def test_dropless_chunks_match_one_pass(rng, monkeypatch):
    """Tokens past ``DROPLESS_CHUNK`` go through in chunks; the result is
    the one-pass result (float32, 1e-6)."""
    p, _ = MOE.moe_init(jax.random.PRNGKey(5), CFG)
    x = jnp.asarray(rng.randn(3, 10, CFG.d_model).astype(np.float32))
    one, n1 = MOE.moe_apply_dropless(p, x, CFG)
    monkeypatch.setattr(MOE, "DROPLESS_CHUNK", 8)
    chunked, n2 = MOE.moe_apply_dropless(p, x, CFG)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(one),
                               atol=1e-6)
    assert int(n1["moe_held_pairs"]) == int(n2["moe_held_pairs"])


# ---- (d) YaRN and the softmax scale in closed form --------------------------

def test_yarn_inverse_frequencies_and_softmax_scale_closed_form():
    """At the published sizes: rope dim 64, theta 1e4, factor 40 over 4,096
    positions, beta_fast 32, beta_slow 1. The correction range is
    floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10 to
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23: dims below 10 keep
    theta ** (-2i/64), dims from 23 on take it / 40, and between the ramp
    (i - 10) / 13 blends them. mscale = mscale_all_dim, so cos/sin keep
    scale 1, and the softmax scale is 192 ** -0.5 * (0.0707 ln 40 + 1) ** 2."""
    cfg = ARCHS[ARCH]
    inv, cs = MLA.rope_inv_freq(cfg)
    i = np.arange(32, dtype=np.float64)
    base = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert cs == 1.0
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert MLA.softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert MLA.softmax_scale(cfg) / 192 ** -0.5 == pytest.approx(1.5897,
                                                                  abs=1e-4)
    rinv, rcs = REF.inv_freq(_m(cfg))
    np.testing.assert_allclose(inv, rinv, rtol=1e-6)
    assert rcs == cs and REF.softmax_scale(_m(cfg)) == pytest.approx(scale)


# ---- (e) the scopes ---------------------------------------------------------

SCOPES = ("mla", "moe_router", "moe_experts", "moe_shared")


def test_new_scopes_in_compiled_prefill_and_decode(params):
    plen, slots = 8, 2
    eng = GuardedEngine(CFG, plen + 4, slots)
    eng.params = params
    ones = jnp.ones((slots,), jnp.float32)
    prompts = jnp.asarray(np.stack(_prompts(slots, plen)))
    pre = eng._prefill_fn(BACKEND).lower(params, prompts, ones)
    state, _, _ = eng.start_wave(_prompts(slots, plen), [1.0] * slots,
                                 BACKEND)
    indexed, rest = split_caches(state["caches"])
    dec = eng._decode_fn(BACKEND).lower(
        params, indexed, rest, state["tok"], jnp.asarray(plen, jnp.int32),
        ones)
    for lowered in (pre, dec):
        text = lowered.compile().as_text()
        for scope in SCOPES:
            assert f"/{scope}/" in text, scope


def test_dense_decode_program_has_no_moe_scope_or_counter():
    cfg = TINY_ARCHS["internlm2-1.8b"]
    eng = GuardedEngine(cfg, 12, 2)
    state, _, census = eng.start_wave(_prompts(2, 8), [1.0, 1.0], BACKEND)
    state, _, census = eng.decode(state, [1.0, 1.0], BACKEND)
    assert census.shape == (3,)
    indexed, rest = split_caches(state["caches"])
    text = eng._decode_fn(BACKEND).lower(
        eng.params, indexed, rest, state["tok"], jnp.asarray(9, jnp.int32),
        jnp.ones((2,), jnp.float32)).as_text()
    for scope in SCOPES:
        assert scope not in text
    assert eng.moe_held_pairs == eng.moe_decode_calls == 0


# ---- (f) the counters -------------------------------------------------------

def test_counters_equal_hand_counted_pairs(params, ref):
    """Each decode call computes every slot's routed pairs on the held
    experts: counted by hand from the reference's own routing at the
    positions the calls fed, over the two MoE layers."""
    plen, max_new, slots = 8, 5, 3
    eng = GuardedEngine(CFG, plen + max_new + 1, slots)
    eng.params = params
    prompts = _prompts(slots, plen, seed=6)
    ones = [1.0] * slots
    state, tok, census = eng.start_wave(prompts, ones, BACKEND)
    assert census.shape == (slots + 1,)
    served = [tok]
    for _ in range(max_new - 1):
        state, tok, census = eng.decode(state, ones, BACKEND)
        assert census.shape == (slots + 1,)
        served.append(tok)
    seqs = np.concatenate([np.stack(prompts), np.stack(served, 1)], 1)
    fed = range(plen, plen + max_new - 1)          # each decode call's input
    held = 0
    for experts in ref.routes(seqs):                # (B, T, k) a MoE layer
        e = experts[:, list(fed)]
        start = CFG.moe.held_start
        held += int(np.sum((e >= start) & (e < start + CFG.moe.n_held)))
    assert eng.moe_decode_calls == max_new - 1
    assert eng.moe_held_pairs == held
    assert 0 < held < slots * (max_new - 1) * CFG.moe.top_k * 2
