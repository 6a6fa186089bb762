"""The comparison that decides ``correct``, shown to fail: the rest of a
run, past the harness's look for a chip, at a tiny size on the CPU, with
the timed path broken underneath -- a step that returns its state
unchanged, half of the batch left out, a token altered where it is
produced, a census that never flags a slot -- and with the control (the
plain reference in float8 in the program's place)."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

import _tiny
import run
from benchlib import serve_cell, train_cell

SEED = 2**32 + 11


def _train():
    cell, cfg, mix = _tiny.train_cell()
    res = train_cell.run(cell, cfg, mix, SEED, 0.2, None,
                         time.perf_counter(), jax.devices())
    return cell, res


def _serve():
    cell, cfg, mix = _tiny.serve_cell()
    res = serve_cell.run(cell, cfg, mix, SEED, 0.5, None,
                         time.perf_counter(), jax.devices())
    return cell, res


@pytest.fixture(autouse=True)
def _default_backend():
    yield
    from repro import reduce as R

    R.set_default_backend(None)


def test_sound_train_run_reads_small_gaps():
    cell, res = _train()
    n = res["numbers"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert n["loss_gap"] < 1e-2 and n["grad_gap"] < 0.1
    assert n["grad_gap_median"] <= n["grad_gap"] and n["norm_gap"] < 0.05
    assert n["change_gap"] < 0.5


def _wrap_step(monkeypatch, body):
    """Replace the program's one-chip guarded step by ``body(step, params,
    opt, guard, batch)`` around the real step, compiled without
    donation."""
    from repro.launch import steps

    def make(cfg, tcfg, *a, **k):
        real = jax.jit(steps.make_guarded_train_step(cfg, tcfg, *a, **k))
        return lambda p, o, g, b: body(real, p, o, g, b)

    monkeypatch.setattr(steps, "make_jitted_guarded_train_step", make)


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    def unchanged(real, p, o, g, b):
        metrics = real(p, o, g, b)[3]
        return p, o, g, metrics

    _wrap_step(monkeypatch, unchanged)
    cell, res = _train()
    assert res["numbers"]["change_gap"] == pytest.approx(1.0)
    assert run.verdict(cell, res)[0] is False


def test_half_the_batch_left_out_fails(monkeypatch):
    def half(real, p, o, g, b):
        rows = b["tokens"].shape[0] // 2
        return real(p, o, g, {"tokens": b["tokens"][:rows]})

    _wrap_step(monkeypatch, half)
    cell, res = _train()
    assert run.verdict(cell, res)[0] is False


def test_train_control_separates():
    """At this size the control's readings lie far above a sound run's on
    the same seed, by the first gradient; at the cell's own size the
    cell's limits reject it (PERF.md gives the chip readings)."""
    cell, res = _train()
    cell, cfg, mix = _tiny.train_cell()
    ref = train_cell.reference_readings(cfg, cell, mix, SEED)
    low = train_cell.reference_readings(cfg, cell, mix, SEED, "fp8")
    ctrl = train_cell.compare(low, ref)
    assert ctrl["grad_gap"] >= 5 * res["numbers"]["grad_gap"]
    assert ctrl["grad_gap_median"] >= 5 * res["numbers"]["grad_gap_median"]


def test_sound_serve_run_answers_every_request():
    cell, res = _serve()
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["numbers"]["tokens_checked"] > 0
    assert res["numbers"]["token_gap"] < 0.1
    assert res["numbers"]["census_misses"] == 0
    assert run.verdict(cell, res)[0] is True


def test_census_that_reads_clean_fails(monkeypatch):
    """A census that never flags a slot misses the canary that the
    adapter plants in the window."""
    from repro.launch.serve import GuardedEngine

    real = GuardedEngine.decode

    def clean(self, state, scales, backend):
        new, toks, census = real(self, state, scales, backend)
        return new, toks, np.zeros_like(np.asarray(census))

    monkeypatch.setattr(GuardedEngine, "decode", clean)
    cell, res = _serve()
    assert res["numbers"]["census_misses"] == 1
    assert run.verdict(cell, res)[0] is False


def test_token_altered_where_produced_fails(monkeypatch):
    from repro.launch.serve import GuardedEngine

    real = GuardedEngine.decode
    calls = {"n": 0}

    def altered(self, state, scales, backend):
        new, toks, census = real(self, state, scales, backend)
        calls["n"] += 1
        if calls["n"] % 2 == 0:
            toks = (np.asarray(toks) + 1) % self.cfg.vocab_size
        return new, toks, census

    monkeypatch.setattr(GuardedEngine, "decode", altered)
    cell, res = _serve()
    assert res["failed"] == 0
    assert run.verdict(cell, res)[0] is False


def test_serve_control_fails():
    """The control reads the gap of its own first token at every position
    of eight requests of 24 served tokens each (the cell checks eight)."""
    cell, cfg, mix = _tiny.serve_cell()
    sched = serve_cell.traffic.serve_schedule(mix, cfg["token_vocab"], SEED,
                                              1.0)
    picks = list(range(8))
    ref = serve_cell.reference.ServeReference(
        cfg["model"], serve_cell.common.jax_key(SEED))
    # served tokens: the reference's own greedy continuation
    total = mix["prompt_len"] + serve_cell.traffic.max_new_tokens(mix)
    tokens = [None] * len(sched)
    for rid in picks:
        seq = list(sched[rid][1])
        for _ in range(24):
            x = np.zeros((1, total), np.int32)
            x[0, :len(seq)] = seq
            _, _, top = ref.read(x, np.zeros_like(x))
            seq.append(int(top[0, len(seq) - 1]))
        tokens[rid] = seq[len(sched[rid][1]):]
    seqs, mask, served = serve_cell.check_batch(sched, tokens, picks, total)
    sound = serve_cell.widest_gap(cfg, SEED, seqs, mask, served)
    assert sound["token_gap"] == pytest.approx(0.0, abs=1e-4)
    low = serve_cell.widest_gap(cfg, SEED, seqs, mask, served, "fp8")
    res = {"numbers": dict(low, failed_requests=0, census_misses=0)}
    assert run.verdict(cell, res)[0] is False
