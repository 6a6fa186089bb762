"""Guarded serving: admission/shedding, deadlines, census-guarded decode
with quarantine+retry, the per-backend circuit breaker, and the planner's
quarantine re-route.

Most of the file drives ``ServingRuntime`` with a jax-free FakeEngine and
an injectable FakeClock -- every schedule (deadlines, cooldowns, retry
counts) is asserted deterministically, no wall-clock waits. The last
section runs the REAL ``GuardedEngine`` (tiny olmo) end to end under a
chaos schedule and checks the exported status JSON against the injection
schedule, plus greedy-token equivalence across the degradation chain."""

import json
import math

import numpy as np
import pytest

from repro.runtime import (
    AdmissionQueue,
    ChaosMonkey,
    CircuitBreaker,
    Completion,
    DeadlineExceeded,
    Preemption,
    Request,
    RequestRejected,
    ServingRuntime,
    TransientFault,
)

# ----------------------------- fakes ---------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeEngine:
    """Protocol-conforming, jax-free, bitwise-deterministic engine.

    Slot i's token stream is ``(base[i] + t) % 997`` where ``base`` is the
    prompt sum -- multiplying by the chaos scale (NaN/Inf) makes the value
    non-finite, which the fake census reports per slot exactly like
    ``guarded_logit_stat`` (counts per slot, total appended).
    ``poison_slots`` marks slots whose census NEVER comes clean (the
    persistent-poison path); ``step_cost`` advances ``clock`` per step so
    deadline schedules are exact."""

    def __init__(self, slots=4, *, clock=None, step_cost=0.0,
                 poison_slots=()):
        self.slots = slots
        self.clock = clock
        self.step_cost = float(step_cost)
        self.poison_slots = set(poison_slots)
        self.step_calls = 0
        self.backends_used = []

    def validate(self, prompt, max_new):
        return None

    def _step(self, base, t, scales, backend):
        self.step_calls += 1
        self.backends_used.append(backend)
        if self.clock is not None and self.step_cost:
            self.clock.advance(self.step_cost)
        toks, census = [], []
        for i in range(self.slots):
            if base[i] is None:
                toks.append(0)
                census.append(0.0)
                continue
            v = float(base[i] + t) * float(scales[i])
            bad = (not math.isfinite(v)) or i in self.poison_slots
            census.append(1.0 if bad else 0.0)
            toks.append(-1 if bad else int(v) % 997)
        census.append(sum(census))
        return toks, census

    def start_wave(self, prompts, scales, backend):
        base = [
            int(np.sum(np.asarray(p))) if p is not None else None
            for p in prompts
        ]
        toks, census = self._step(base, 0, scales, backend)
        return {"base": base, "t": 0}, toks, census

    def decode(self, state, scales, backend):
        t = state["t"] + 1
        toks, census = self._step(state["base"], t, scales, backend)
        return {"base": state["base"], "t": t}, toks, census


def _reqs(n, max_new=4, deadline_s=None, plen=8):
    rng = np.random.default_rng(0)
    return [
        Request(rid=i, prompt=rng.integers(0, 100, size=(plen,)),
                max_new=max_new, deadline_s=deadline_s)
        for i in range(n)
    ]


# -------------------------- AdmissionQueue ---------------------------------


def test_queue_sheds_oldest_expired_first():
    q = AdmissionQueue(capacity=2)
    a = Request(0, None, 1, deadline_s=1.0)
    b = Request(1, None, 1, deadline_s=5.0)
    assert q.submit(a, now=0.0) == (True, [])
    assert q.submit(b, now=0.0) == (True, [])
    # a is past-deadline at t=2: the full queue sheds it to admit c
    c = Request(2, None, 1, deadline_s=9.0)
    admitted, shed = q.submit(c, now=2.0)
    assert admitted and [r.rid for r in shed] == [0]
    assert len(q) == 2


def test_queue_refuses_when_nobody_sheddable():
    q = AdmissionQueue(capacity=1)
    assert q.submit(Request(0, None, 1, deadline_s=None), 0.0) == (True, [])
    admitted, shed = q.submit(Request(1, None, 1), 0.0)
    assert not admitted and shed == []
    with pytest.raises(ValueError):
        AdmissionQueue(0)


def test_queue_pop_drops_expired():
    q = AdmissionQueue(capacity=8)
    q.submit(Request(0, None, 1, deadline_s=1.0), 0.0)
    q.submit(Request(1, None, 1, deadline_s=9.0), 0.0)
    wave, expired = q.pop(4, now=2.0)
    assert [r.rid for r in wave] == [1]
    assert [r.rid for r in expired] == [0]


# -------------------------- CircuitBreaker ---------------------------------


def test_breaker_trips_after_threshold_and_degrades():
    clk = FakeClock()
    trips, closes = [], []
    br = CircuitBreaker(chain=("a", "b", "c"), fail_threshold=2,
                        cooldown_s=1.0, clock=clk,
                        on_trip=trips.append, on_close=closes.append)
    assert br.backend() == "a"
    br.record_failure("a")
    assert br.state("a") == "closed"  # below threshold
    br.record_success("a")
    br.record_failure("a")
    assert br.state("a") == "closed"  # success reset the streak
    br.record_failure("a")
    br.record_failure("a")
    assert br.state("a") == "open" and trips == ["a"]
    assert br.backend() == "b" and br.total_trips == 1
    assert closes == []


def test_breaker_half_open_probe_cycle_with_bounded_backoff():
    clk = FakeClock()
    trips, closes = [], []
    br = CircuitBreaker(chain=("a", "b"), fail_threshold=1, cooldown_s=1.0,
                        cooldown_cap_s=3.0, probe_successes=2, clock=clk,
                        on_trip=trips.append, on_close=closes.append)
    br.record_failure("a")
    assert br.backend() == "b"
    clk.advance(1.0)
    assert br.backend() == "a" and br.state("a") == "half_open"
    # failed probe: re-open with cooldown DOUBLED
    br.record_failure("a")
    assert br.state("a") == "open" and trips == ["a", "a"]
    clk.advance(1.0)
    assert br.backend() == "b"  # 1.0 < doubled cooldown 2.0
    clk.advance(1.0)
    assert br.backend() == "a" and br.state("a") == "half_open"
    # another failed probe: 2.0 * 2 capped at 3.0
    br.record_failure("a")
    clk.advance(2.5)
    assert br.backend() == "b"
    clk.advance(0.5)
    assert br.backend() == "a"
    br.record_success("a")
    assert br.state("a") == "half_open" and closes == []
    br.record_success("a")
    assert br.state("a") == "closed" and closes == ["a"]
    assert br.backend() == "a"


def test_breaker_terminal_backend_always_served():
    clk = FakeClock()
    br = CircuitBreaker(chain=("a", "b"), fail_threshold=1, clock=clk)
    br.record_failure("a")
    br.record_failure("b")
    # the terminal backend trips like any other but is still served --
    # something must answer
    assert br.states() == {"a": "open", "b": "open"}
    assert br.backend() == "b"


# ------------------------- ChaosMonkey hooks -------------------------------


def test_chaos_from_seed_deterministic_and_disjoint():
    kw = dict(n_steps=64, nan_rate=0.1, inf_rate=0.05, fail_rate=0.1,
              preempt_rate=0.1)
    c1 = ChaosMonkey.from_seed(7, **kw)
    c2 = ChaosMonkey.from_seed(7, **kw)
    assert (c1.nan_steps, c1.inf_steps, c1.fail_steps, c1.preempt_steps) == \
        (c2.nan_steps, c2.inf_steps, c2.fail_steps, c2.preempt_steps)
    assert ChaosMonkey.from_seed(8, **kw).nan_steps != c1.nan_steps or \
        ChaosMonkey.from_seed(8, **kw).fail_steps != c1.fail_steps
    all_sets = [c1.nan_steps, c1.inf_steps, c1.fail_steps, c1.preempt_steps]
    assert sum(len(s) for s in all_sets) == len(frozenset().union(*all_sets))
    assert all(0 not in s for s in all_sets)  # anchor id stays clean
    assert any(all_sets)


def test_chaos_scale_for_fires_once():
    c = ChaosMonkey(nan_steps=[3], inf_steps=[4])
    assert math.isnan(c.scale_for(3))
    assert c.scale_for(3) == 1.0  # fire-once: the retry sees identity
    assert math.isinf(c.scale_for(4))
    assert c.scale_for(4) == 1.0
    assert c.scale_for(1) == 1.0


def test_chaos_on_request_preempt_vs_fault():
    c = ChaosMonkey(fail_steps=[2], preempt_steps=[5])
    with pytest.raises(Preemption):
        c.on_request(5)
    c.on_request(5)  # fired
    with pytest.raises(TransientFault):
        c.on_request(2)
    c.on_request(2)
    assert issubclass(Preemption, TransientFault)
    assert c.calls == 4


# --------------------- ServingRuntime + FakeEngine -------------------------


def test_clean_serve_returns_completions_in_request_order():
    eng = FakeEngine(slots=3)
    rt = ServingRuntime(eng, clock=FakeClock(), quarantine_planner=False)
    reqs = _reqs(7, max_new=4)
    out = rt.serve(reqs)
    assert [r.rid for r in out] == [r.rid for r in reqs]
    assert all(isinstance(r, Completion) and r.ok for r in out)
    assert all(len(r.tokens) == 4 for r in out)
    snap = rt.metrics.snapshot()
    assert snap["admitted"] == 7 and snap["completed"] == 7
    assert snap["tokens_out"] == 28 and snap["quarantined"] == 0


def test_serve_empty_is_empty():
    rt = ServingRuntime(FakeEngine(), clock=FakeClock(),
                        quarantine_planner=False)
    assert rt.serve([]) == []


def test_chaos_quarantine_retry_reproduces_clean_run_bitwise():
    reqs = _reqs(6, max_new=5)
    clean = ServingRuntime(FakeEngine(slots=3), clock=FakeClock(),
                           quarantine_planner=False).serve(reqs)

    clk = FakeClock()
    chaos = ChaosMonkey(nan_steps=[1], fail_steps=[3], preempt_steps=[4])
    br = CircuitBreaker(chain=("fakeA", "fakeB"), fail_threshold=1,
                        clock=clk)
    eng = FakeEngine(slots=3)
    rt = ServingRuntime(eng, chaos=chaos, breaker=br, clock=clk,
                        quarantine_planner=False)
    out = rt.serve(reqs)

    # the guarded retries reproduce the clean tokens BITWISE: the NaN'd
    # slot's state never committed, the faulted/preempted steps re-ran
    assert [r.tokens for r in out] == [r.tokens for r in clean]
    snap = rt.metrics.snapshot()
    assert snap["quarantined"] == 1  # rid 1's one poisoned attempt
    assert snap["retries"] == 3      # nan + fault + preemption
    assert snap["breaker_trips"] == 1
    assert snap["breaker_states"] == {"fakeA": "open", "fakeB": "closed"}
    assert chaos.fired == {("nan", 1), ("fail", 3), ("preempt", 4)}
    # the faulted wave finished on the degraded backend
    assert "fakeB" in eng.backends_used


def test_seeded_chaos_schedule_reproduces_clean_run_bitwise():
    """The from_seed flavor: a randomly drawn (but deterministic)
    per-request schedule, counters derived from the schedule itself."""
    n = 12
    reqs = _reqs(n, max_new=4)
    clean = ServingRuntime(FakeEngine(slots=4), clock=FakeClock(),
                           quarantine_planner=False).serve(reqs)

    chaos = ChaosMonkey.from_seed(12, n_steps=n, nan_rate=0.2,
                                  fail_rate=0.2, preempt_rate=0.15)
    # seed 12 draws all three kinds: nan {4,5}, fail {6,7}, preempt {1}
    assert chaos.nan_steps and chaos.fail_steps and chaos.preempt_steps
    clk = FakeClock()
    rt = ServingRuntime(
        FakeEngine(slots=4), chaos=chaos, clock=clk,
        breaker=CircuitBreaker(chain=("fakeA", "fakeB"), clock=clk),
        quarantine_planner=False)
    out = rt.serve(reqs)

    assert [r.tokens for r in out] == [r.tokens for r in clean]
    snap = rt.metrics.snapshot()
    assert snap["quarantined"] == len(chaos.nan_steps)
    # every configured injection fired exactly once
    assert chaos.fired == (
        {("nan", s) for s in chaos.nan_steps}
        | {("fail", s) for s in chaos.fail_steps}
        | {("preempt", s) for s in chaos.preempt_steps}
    )
    assert snap["retries"] >= len(chaos.fail_steps) + len(chaos.preempt_steps)


def test_persistently_poisoned_slot_fails_structured_batch_proceeds():
    clk = FakeClock()
    eng = FakeEngine(slots=3, poison_slots={1})
    rt = ServingRuntime(eng, clock=clk, max_step_retries=2,
                        quarantine_planner=False)
    out = rt.serve(_reqs(3, max_new=4))
    assert isinstance(out[1], RequestRejected) and not out[1].ok
    assert "poisoned" in out[1].reason and out[1].tokens == ()
    assert isinstance(out[0], Completion) and len(out[0].tokens) == 4
    assert isinstance(out[2], Completion) and len(out[2].tokens) == 4
    snap = rt.metrics.snapshot()
    # 3 attempts of the first step, each quarantining slot 1 once
    assert snap["quarantined"] == 3
    assert snap["rejected_poisoned"] == 1


def test_deadline_expiry_returns_partial_tokens_and_sheds_queue():
    clk = FakeClock()
    eng = FakeEngine(slots=1, clock=clk, step_cost=0.01)
    rt = ServingRuntime(eng, clock=clk, quarantine_planner=False)
    reqs = [
        Request(rid=i, prompt=np.arange(4), max_new=5, deadline_s=0.035)
        for i in range(2)
    ]
    out = rt.serve(reqs)
    # wave 1 decodes until the clock passes the deadline: partial tokens
    assert isinstance(out[0], DeadlineExceeded)
    assert len(out[0].tokens) == 4
    # wave 2 was still queued when its deadline passed: zero tokens
    assert isinstance(out[1], DeadlineExceeded) and out[1].tokens == ()
    assert rt.metrics.snapshot()["deadline_missed"] == 2


def test_infeasible_deadline_refused_with_estimate():
    clk = FakeClock()
    eng = FakeEngine(slots=2, clock=clk, step_cost=0.01)
    rt = ServingRuntime(eng, clock=clk, quarantine_planner=False)
    rt.serve(_reqs(2, max_new=4))  # primes the EWMA with real step times
    assert rt._step_ewma is not None
    late = Request(rid=99, prompt=np.arange(4), max_new=50,
                   deadline_s=clk() + 0.05)
    assert not rt.submit(late)
    res = rt._results[99]
    assert isinstance(res, RequestRejected) and "infeasible" in res.reason
    assert rt.metrics.snapshot()["shed_infeasible"] == 1


def test_queue_full_sheds_structured():
    rt = ServingRuntime(FakeEngine(slots=2), clock=FakeClock(),
                        queue_capacity=2, quarantine_planner=False)
    reqs = _reqs(4, max_new=2)
    admits = [rt.submit(r) for r in reqs]
    assert admits == [True, True, False, False]
    for rid in (2, 3):
        res = rt._results[rid]
        assert isinstance(res, RequestRejected) and "queue full" in res.reason
    rt.drain()
    out = [rt._results[r.rid] for r in reqs]
    assert [r.ok for r in out] == [True, True, False, False]
    snap = rt.metrics.snapshot()
    assert snap["shed_queue_full"] == 2 and snap["admitted"] == 2


def test_validate_rejects_before_admission():
    class PickyEngine(FakeEngine):
        def validate(self, prompt, max_new):
            return "prompt too long" if len(prompt) > 4 else None

    rt = ServingRuntime(PickyEngine(slots=2), clock=FakeClock(),
                        quarantine_planner=False)
    good = Request(0, np.arange(3), 2)
    bad = Request(1, np.arange(9), 2)
    out = rt.serve([good, bad])
    assert isinstance(out[0], Completion)
    assert isinstance(out[1], RequestRejected)
    assert out[1].reason == "prompt too long"


def test_status_json_counters_match_injection_schedule(tmp_path):
    path = tmp_path / "serve_status.json"
    clk = FakeClock()
    chaos = ChaosMonkey(nan_steps=[1], fail_steps=[3], preempt_steps=[4])
    br = CircuitBreaker(chain=("fakeA", "fakeB"), fail_threshold=1,
                        clock=clk)
    rt = ServingRuntime(FakeEngine(slots=3, clock=clk, step_cost=0.01),
                        chaos=chaos, breaker=br, clock=clk,
                        status_path=path, quarantine_planner=False)
    out = rt.serve(_reqs(6, max_new=3))
    assert all(r.ok for r in out)
    snap = json.loads(path.read_text())
    assert snap["admitted"] == 6 and snap["completed"] == 6
    assert snap["tokens_out"] == 18
    assert snap["quarantined"] == 1 and snap["retries"] == 3
    assert snap["breaker_trips"] == 1
    assert snap["breaker_states"]["fakeA"] == "open"
    assert snap["deadline_missed"] == 0
    assert snap["latency_requests"] == 6
    assert snap["ttft_p99_s"] >= snap["ttft_p50_s"] > 0
    assert snap["itl_p99_s"] >= snap["itl_p50_s"] > 0


# ------------------- planner quarantine (breaker re-route) -----------------


@pytest.fixture
def clean_quarantine():
    from repro import reduce as R

    yield
    for name in R.quarantined_backends():
        R.reinstate_backend(name)


def test_plan_cache_serves_no_stale_quarantined_plans(clean_quarantine):
    """The breaker-trip regression: a memoized auto ReducePlan carrying a
    quarantined backend must be invalidated, not served."""
    import jax.numpy as jnp

    from repro import reduce as R

    R.plan_cache_clear()
    shape, dtype = (4096,), jnp.float32
    b0 = R.plan_for(shape, dtype).backend
    before = R.plan_cache_info()
    assert R.plan_for(shape, dtype).backend == b0
    assert R.plan_cache_info().hits == before.hits + 1  # memo is live

    R.quarantine_backend(b0)
    assert b0 in R.quarantined_backends()
    b1 = R.plan_for(shape, dtype).backend
    if b0 != "xla":
        assert b1 != b0  # the stale memo would have returned b0
    else:
        assert b1 == "xla"  # terminal: serves even quarantined
    # an explicit pin bypasses quarantine -- the half-open probe path
    assert R.plan_for(shape, dtype, backend=b0).backend == b0
    # the re-routed plan still computes correctly
    x = jnp.arange(float(shape[0]), dtype=dtype)
    assert float(R.reduce(x, kind="sum")) == pytest.approx(
        shape[0] * (shape[0] - 1) / 2, rel=1e-6)

    R.reinstate_backend(b0)
    assert R.plan_for(shape, dtype).backend == b0  # reinstated immediately


def test_quarantine_walks_whole_chain_to_terminal(clean_quarantine):
    import jax.numpy as jnp

    from repro import reduce as R

    for name in ("pallas_fused", "pallas_hier", "mma_jnp"):
        R.quarantine_backend(name)
    assert R.plan_for((4096,), jnp.float32).backend == "xla"
    x = jnp.ones((64,), jnp.float32)
    assert float(R.reduce(x, kind="sum")) == 64.0


def test_scan_plan_cache_serves_no_stale_quarantined_plans(clean_quarantine):
    """The scan twin of the breaker-trip regression: quarantining a backend
    must reroute AUTO ScanPlans and invalidate the memoized scan-plan
    cache -- a stale memo would keep dispatching prefix sums onto the
    quarantined backend for every already-seen shape."""
    import jax.numpy as jnp
    import numpy as np

    from repro import reduce as R

    R.plan_cache_clear()
    shape, dtype = (200_000,), jnp.float32
    b0 = R.scan_plan_for(shape, dtype).backend
    assert b0 != "xla"  # a large float operand auto-routes onto an MMA path
    before = R.scan_plan_cache_info()
    assert R.scan_plan_for(shape, dtype).backend == b0
    assert R.scan_plan_cache_info().hits == before.hits + 1  # memo is live

    R.quarantine_backend(b0)
    assert R.scan_plan_cache_info().currsize == 0  # memo invalidated
    b1 = R.scan_plan_for(shape, dtype).backend
    assert b1 != b0  # the stale memo would have returned b0
    # an explicit pin bypasses quarantine -- the half-open probe path
    assert R.scan_plan_for(shape, dtype, backend=b0).backend == b0
    # the re-routed scan still computes correctly
    x = jnp.ones((256,), dtype)
    np.testing.assert_array_equal(
        np.asarray(R.scan(x)), np.arange(1, 257, dtype=np.float32)
    )

    R.reinstate_backend(b0)
    assert R.scan_plan_for(shape, dtype).backend == b0  # back immediately


def test_scan_quarantine_walks_chain_to_terminal(clean_quarantine):
    import jax.numpy as jnp
    import numpy as np

    from repro import reduce as R

    for name in ("pallas_fused", "mma_jnp"):
        R.quarantine_backend(name)
    assert R.scan_plan_for((200_000,), jnp.float32).backend == "xla"
    x = jnp.ones((64,), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(R.scan(x)), np.arange(1, 65, dtype=np.float32)
    )


# --------------------- real-engine end to end ------------------------------


def _tiny_engine(cls, slots, prompt_len=8, max_new=4):
    from repro.configs import TINY_ARCHS

    cfg = TINY_ARCHS["olmo-1b"]
    return cls(cfg, prompt_len + max_new + 1, slots), cfg


def _tiny_prompts(cfg, n, prompt_len=8):
    rng = np.random.default_rng(3)
    return [
        rng.integers(0, cfg.vocab_size, size=(prompt_len,)).astype(np.int32)
        for _ in range(n)
    ]


def test_engine_serve_empty_and_cache_overflow_guard():
    from repro.launch.serve import Engine

    eng, cfg = _tiny_engine(Engine, slots=2)
    assert eng.serve([], max_new=4) == []
    with pytest.raises(ValueError, match="s_max"):
        eng.check_fits(prompt_len=10, max_new=4)  # 10 + 4 + 1 > 13
    with pytest.raises(ValueError, match="s_max"):
        eng.serve(_tiny_prompts(cfg, 1, prompt_len=12), max_new=4)


def test_engine_padded_wave_masks_dummy_not_duplicate():
    from repro.launch.serve import Engine

    eng, cfg = _tiny_engine(Engine, slots=2)
    prompts = _tiny_prompts(cfg, 3)
    batched = eng.serve(prompts, max_new=4)
    assert len(batched) == 3  # a 2-slot engine serves 3 via a padded wave
    # the padded wave's live slot must decode exactly as a full wave would
    solo = eng.serve(prompts[2:], max_new=4)
    assert batched[2] == solo[0]


def test_guarded_serving_end_to_end_chaos_status(tmp_path):
    """The acceptance test: real model, per-request chaos, quarantine +
    breaker degradation, and the status JSON matching the injection
    schedule -- with tokens bitwise-identical to the clean run."""
    from repro.launch.serve import GuardedEngine

    eng, cfg = _tiny_engine(GuardedEngine, slots=2)
    prompts = _tiny_prompts(cfg, 4)
    reqs = [Request(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(prompts)]

    clean = ServingRuntime(eng, quarantine_planner=False).serve(reqs)
    assert all(isinstance(r, Completion) for r in clean)

    path = tmp_path / "status.json"
    chaos = ChaosMonkey(nan_steps=[1], fail_steps=[2])
    # default chain, no planner hooks; a frozen clock keeps the tripped
    # breaker OPEN through the run (real step times would otherwise let
    # the half-open probe close it again -- good behavior, bad fixture)
    br = CircuitBreaker(fail_threshold=1, clock=FakeClock())
    rt = ServingRuntime(eng, chaos=chaos, breaker=br, status_path=path,
                        quarantine_planner=False)
    out = rt.serve(reqs)

    # greedy tokens identical under chaos: the NaN'd slot was quarantined
    # and retried from committed state; the tripped breaker degraded the
    # census backend pallas_fused -> mma_jnp without touching the tokens
    assert [r.tokens for r in out] == [r.tokens for r in clean]
    snap = json.loads(path.read_text())
    assert snap["admitted"] == 4 and snap["completed"] == 4
    assert snap["quarantined"] == 1
    assert snap["retries"] == 2  # one census retry + one fault retry
    assert snap["breaker_trips"] == 1
    assert snap["breaker_states"]["pallas_fused"] == "open"
    assert chaos.fired == {("nan", 1), ("fail", 2)}


def test_guarded_tokens_equivalent_across_backend_chain():
    """Pin the census statistic to each backend in the degradation chain
    explicitly: greedy tokens must be identical -- the guard observes the
    logits, it never alters them."""
    from repro.launch.serve import GuardedEngine
    from repro.runtime.serving import DEFAULT_BACKEND_CHAIN

    eng, cfg = _tiny_engine(GuardedEngine, slots=2)
    prompts = _tiny_prompts(cfg, 2)
    scales = np.ones((2,), np.float32)
    per_backend = []
    for backend in DEFAULT_BACKEND_CHAIN:
        state, toks, census = eng.start_wave(list(prompts), scales, backend)
        seq = [list(toks)]
        for _ in range(3):
            state, toks, census = eng.decode(state, scales, backend)
            seq.append(list(toks))
            assert float(census[-1]) == 0.0
        per_backend.append(seq)
    assert per_backend[0] == per_backend[1] == per_backend[2]
