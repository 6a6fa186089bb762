"""The serving adapter's times, on the tiny engine on the CPU: time to
first token counts from when a request was due, a refused request counts
as missing, and the census canary is caught without a token lost or
timed twice."""

from __future__ import annotations

import math

import numpy as np
import pytest

import _tiny
from benchlib import serve_cell


class Clock:
    """Advances a little on every read, a second on every engine call."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3
        return self.t

    def sleep(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def engine():
    cell, cfg, mix = _tiny.serve_cell()
    from repro import reduce as R

    R.set_default_backend(cell["reduce_backend"])
    try:
        yield cell, cfg, mix, serve_cell.build_engine(cell, cfg, mix, 5)
    finally:
        R.set_default_backend(None)


def _slow(eng, clock):
    calls = {"n": 0}
    for name in ("start_wave", "decode"):
        real = getattr(eng, name)

        def timed(*a, _real=real, **k):
            clock.t += 1.0
            calls["n"] += 1
            return _real(*a, **k)

        setattr(eng, name, timed)
    return calls


def test_ttft_counts_from_the_due_time(engine):
    cell, cfg, mix, eng = engine
    clock = Clock()
    _slow(eng, clock)
    rng = np.random.default_rng(0)
    prompt = lambda: rng.integers(0, 256, 16).astype(np.int32)
    sched = [(0.0, prompt(), 3), (0.5, prompt(), 2), (30.0, prompt(), 4)]
    runtime, timed, due, t0, t1 = serve_cell.serve_window(
        eng, cell, sched, clock=clock, sleep=clock.sleep)
    t = serve_cell.timings(runtime, timed, due, sched)
    assert t["failed"] == 0
    assert [len(x) for x in t["tokens"]] == [3, 2, 4]
    assert due[2] - due[0] == pytest.approx(30.0)
    # request 1 was due while the first wave ran: it waited for it
    w0, w1 = timed.waves[0], timed.waves[1]
    assert w0["rids"][:1] == [0] and 1 in w1["rids"] and 2 not in w1["rids"]
    for rid, wave in ((0, w0), (1, w1)):
        assert t["ttft"][rid] == pytest.approx(wave["ends"][0] - due[rid])
        assert t["wait"][rid] == pytest.approx(wave["launch"] - due[rid])
        assert t["ttft"][rid] > t["wait"][rid] >= 0.0
    assert t["wait"][1] > 2.0          # a whole wave of 3 steps
    assert t["ttft"][2] == pytest.approx(1.0, abs=0.05)
    # one gap per later token, each a step long
    assert len(t["itl"]) == 2 + 1 + 3
    assert all(g == pytest.approx(1.0, abs=0.05) for g in t["itl"])


def test_refused_request_is_missing(engine):
    cell, cfg, mix, eng = engine
    clock = Clock()
    rng = np.random.default_rng(1)
    ok = [(0.01 * i, rng.integers(0, 256, 16).astype(np.int32), 2)
          for i in range(9)]
    too_long = (0.1, rng.integers(0, 256, 16).astype(np.int32), 10_000)
    sched = ok + [too_long]
    runtime, timed, due, t0, t1 = serve_cell.serve_window(
        eng, cell, sched, clock=clock, sleep=clock.sleep)
    t = serve_cell.timings(runtime, timed, due, sched)
    assert t["failed"] == 1
    assert math.isinf(t["ttft"][-1]) and t["tokens"][-1] is None
    import run

    # the missing request ranks above every served one: of 10 requests,
    # the 83rd percentile is the slowest served, and with 2 of 10
    # missing, it is missing too
    r = {"counters": {"ttft_s": t["ttft"]}}
    served = max(x for x in t["ttft"] if not math.isinf(x))
    assert run.read_metric("ttft_p83_ms", r) == pytest.approx(served * 1e3)
    r = {"counters": {"ttft_s": t["ttft"][1:] + [math.inf]}}
    assert run.read_metric("ttft_p83_ms", r) is None
    res = {"numbers": {"token_gap": 0.0, "failed_requests": t["failed"],
                       "census_misses": 0}}
    assert run.verdict(cell, res)[0] is False


def test_census_canary_is_caught(engine):
    """The canary goes into a slot no request uses: the census names it,
    the runtime retries nothing, and every token is timed once."""
    cell, cfg, mix, eng = engine
    clock = Clock()
    calls = _slow(eng, clock)
    rng = np.random.default_rng(2)
    sched = [(0.0, rng.integers(0, 256, 16).astype(np.int32), 5),
             (0.0, rng.integers(0, 256, 16).astype(np.int32), 5),
             (0.0, rng.integers(0, 256, 16).astype(np.int32), 5),
             (0.0, rng.integers(0, 256, 16).astype(np.int32), 3)]
    real = eng.decode
    seen = []

    def spy(state, scales, backend):
        seen.append(list(scales))
        return real(state, scales, backend)

    eng.decode = spy
    try:
        runtime, timed, due, t0, t1 = serve_cell.serve_window(
            eng, cell, sched, clock=clock, sleep=clock.sleep, canary_at=1)
    finally:
        eng.decode = real
    t = serve_cell.timings(runtime, timed, due, sched)
    assert timed.planted == 1 and timed.census_misses == 0
    assert runtime.metrics.snapshot()["retries"] == 0
    assert t["failed"] == 0 and [len(x) for x in t["tokens"]] == [5, 5, 5, 3]
    # all four slots are busy until the short request is done: the canary
    # waits for decode 3, and goes into that request's slot
    poisoned = [i for i, sc in enumerate(seen) if any(np.isnan(sc))]
    assert poisoned == [2] and np.isnan(seen[2][3])
    assert calls["n"] == 5
    assert len(timed.waves) == 1 and len(timed.waves[0]["ends"]) == 5
    assert len(t["itl"]) == 3 * 4 + 2
    assert all(g == pytest.approx(1.0, abs=0.05) for g in t["itl"])
