"""What the serving runtime records about itself: the per-request record
(``ServingRuntime.requests()``) on an injected clock, the slot-step
counters and latency percentiles of ``ServeMetrics``, the profiler host
spans of the serving path, and the named scopes that the compiled train
and serve steps carry into their op names."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model import split_caches
from repro.runtime import (
    ChaosMonkey,
    Completion,
    DeadlineExceeded,
    Request,
    RequestRejected,
    ServeMetrics,
    ServingRuntime,
)
from test_serving_guard import FakeClock, FakeEngine, _tiny_engine, _tiny_prompts

STEP = 0.01  # the fake engine's cost of one step, on the fake clock


def _runtime(slots, **kw):
    clk = FakeClock()
    eng = FakeEngine(slots=slots, clock=clk, step_cost=STEP)
    return ServingRuntime(eng, clock=clk, quarantine_planner=False, **kw), clk


# -------------------------- the per-request record -------------------------


def test_record_times_of_completed_requests():
    rt, clk = _runtime(slots=2)
    clk.advance(1.0)
    reqs = [Request(rid=i, prompt=np.arange(4), max_new=n)
            for i, n in enumerate((3, 2, 1))]
    for r in reqs:
        rt.submit(r)
        clk.advance(0.5)          # submitted at 1.0, 1.5, 2.0
    rt.drain()
    rec = rt.requests()
    assert list(rec) == [0, 1, 2]
    assert [rec[i].submitted for i in range(3)] == [1.0, 1.5, 2.0]
    # wave 1 (rids 0, 1) launches at 2.5; prefill and two decodes
    assert rec[0].launched == rec[1].launched == pytest.approx(2.5)
    assert rec[0].token_times == pytest.approx((2.51, 2.52, 2.53))
    assert rec[1].token_times == pytest.approx((2.51, 2.52))
    assert rec[0].first_token == pytest.approx(2.51)
    assert rec[0].finished == pytest.approx(2.53)
    # rid 1 is done after its second token, decided before the third step
    assert rec[1].finished == pytest.approx(2.52)
    # wave 2 (rid 2) launches when wave 1 ends
    assert rec[2].launched == pytest.approx(2.53)
    assert rec[2].token_times == pytest.approx((2.54,))
    for i in range(3):
        assert isinstance(rec[i].outcome, Completion)
        assert rec[i].outcome.tokens == rt._results[i].tokens
        assert len(rec[i].token_times) == len(rec[i].outcome.tokens)


def test_record_outcomes_of_rejected_and_deadline_requests():
    class PickyEngine(FakeEngine):
        def validate(self, prompt, max_new):
            return "prompt too long" if len(prompt) > 4 else None

    clk = FakeClock()
    eng = PickyEngine(slots=1, clock=clk, step_cost=STEP)
    rt = ServingRuntime(eng, clock=clk, queue_capacity=2,
                        quarantine_planner=False)
    rt.submit(Request(0, np.arange(9), 2))                    # invalid
    rt.submit(Request(1, np.arange(4), 5, deadline_s=0.035))  # runs out
    rt.submit(Request(2, np.arange(4), 5, deadline_s=0.035))  # shed queued
    rt.submit(Request(3, np.arange(4), 5))                    # queue full
    rt.drain()
    rec = rt.requests()
    assert isinstance(rec[0].outcome, RequestRejected)
    assert rec[0].launched is None and rec[0].token_times == ()
    assert rec[0].finished == rec[0].submitted == 0.0
    assert "queue full" in rec[3].outcome.reason
    assert rec[3].first_token is None and rec[3].finished == 0.0
    # rid 1: decoded until the clock passed its deadline
    assert isinstance(rec[1].outcome, DeadlineExceeded)
    assert rec[1].launched == 0.0
    assert rec[1].token_times == pytest.approx((0.01, 0.02, 0.03, 0.04))
    assert rec[1].finished == pytest.approx(0.04)
    # rid 2: its deadline passed while queued; never launched
    assert isinstance(rec[2].outcome, DeadlineExceeded)
    assert rec[2].launched is None and rec[2].token_times == ()
    assert rec[2].finished == pytest.approx(0.04)


def test_record_of_poisoned_slot_and_retried_step():
    clk = FakeClock()
    eng = FakeEngine(slots=2, clock=clk, step_cost=STEP, poison_slots={1})
    rt = ServingRuntime(eng, clock=clk, max_step_retries=1,
                        quarantine_planner=False)
    rt.serve([Request(i, np.arange(4), 3) for i in range(2)])
    rec = rt.requests()
    # the prefill ran twice (one retry): its tokens came at the second end
    assert rec[0].token_times == pytest.approx((0.02, 0.03, 0.04))
    assert isinstance(rec[1].outcome, RequestRejected)
    assert rec[1].token_times == () and rec[1].finished == pytest.approx(0.02)


# ----------------------------- ServeMetrics --------------------------------


def test_slot_steps_count_slots_and_slots_given_a_token():
    rt, _ = _runtime(slots=4)
    rt.serve([Request(i, np.arange(4), n)
              for i, n in enumerate((4, 2, 1))])
    snap = rt.metrics.snapshot()
    # 4 steps of 4 slots; tokens 3 + 2 + 1 + 1 = the 7 tokens served
    assert snap["slot_steps"] == 16
    assert snap["live_slot_steps"] == 7 == snap["tokens_out"]


def test_snapshot_latency_percentiles_over_recent_requests():
    m = ServeMetrics(request_window=3)
    m.record_request(9.0, [9.0])          # pushed out of the window
    for ttft, gaps in ((1.0, [0.1, 0.3]), (2.0, []), (3.0, [0.2])):
        m.record_request(ttft, gaps)
    snap = m.snapshot()
    assert snap["latency_requests"] == 3
    assert snap["ttft_p50_s"] == 2.0 and snap["ttft_p99_s"] == 3.0
    assert snap["itl_p50_s"] == 0.2 and snap["itl_p99_s"] == 0.3
    assert "token_latency_p50_s" not in snap


def test_snapshot_percentiles_from_the_runtime_record():
    rt, clk = _runtime(slots=2)
    rt.serve([Request(i, np.arange(4), 3) for i in range(4)])
    rec = rt.requests()
    ttft = sorted(r.first_token - r.submitted for r in rec.values())
    snap = rt.metrics.snapshot()
    assert snap["latency_requests"] == 4
    assert snap["ttft_p50_s"] == pytest.approx(ttft[2])   # nearest rank
    assert snap["ttft_p99_s"] == pytest.approx(ttft[-1])
    assert snap["itl_p50_s"] == snap["itl_p99_s"] == pytest.approx(STEP)


def test_chaos_retries_leave_one_record_per_request():
    rt, _ = _runtime(slots=3, chaos=ChaosMonkey(nan_steps=[1]))
    out = rt.serve([Request(i, np.arange(4), 3) for i in range(3)])
    assert all(r.ok for r in out)
    rec = rt.requests()
    assert [len(r.token_times) for r in rec.values()] == [3, 3, 3]
    assert rt.metrics.snapshot()["live_slot_steps"] == 9


# ------------------------------- host spans --------------------------------


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_profiler_records_nested_serving_spans(tmp_path):
    from repro.launch.serve import GuardedEngine

    eng, cfg = _tiny_engine(GuardedEngine, slots=2)
    prompts = _tiny_prompts(cfg, 3)
    ServingRuntime(eng, quarantine_planner=False).serve(
        [Request(0, prompts[0], 2)])            # compiles outside the trace
    rt = ServingRuntime(eng, quarantine_planner=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = rt.serve([Request(i, p, 3) for i, p in enumerate(prompts)])
    finally:
        jax.profiler.stop_trace()
    assert all(r.ok for r in out)
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == {"serve.admit", "serve.wave", "serve.prefill",
                       "serve.step", "serve.dispatch", "serve.readback"}
    assert len(by["serve.admit"]) == 3
    # two waves (2 + 1 requests); each with its index and live slots
    assert sorted((s[3]["wave"], s[3]["live"]) for s in by["serve.wave"]) \
        == [(0, 2), (1, 1)]
    assert len(by["serve.prefill"]) == 2 and len(by["serve.step"]) == 4
    steps = by["serve.prefill"] + by["serve.step"]
    for s in steps:
        assert _inside(s, by["serve.wave"])
    for s in by["serve.dispatch"] + by["serve.readback"]:
        assert _inside(s, steps)
    assert len(by["serve.dispatch"]) == len(by["serve.readback"]) == 6


# ---------------------------- scopes in the HLO ----------------------------


def _op_names(lowered) -> list:
    """The op names the compiled program's instructions carry."""
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def _top_scopes(names) -> set:
    """The first component under the jit of each op name."""
    return {n.split("/")[1] for n in names
            if n.startswith("jit(") and n.count("/") > 1}


def test_guarded_train_step_carries_forward_backward_optimizer_scopes():
    from repro import optim
    from repro.configs import TINY_ARCHS, TrainConfig
    from repro.launch.steps import make_guarded_train_step
    from repro.models import init_params

    cfg = TINY_ARCHS["olmo-1b"]
    tcfg = TrainConfig(microbatches=1)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    opt = optim.init_state(params)
    guard = optim.init_guard_state(4)
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    step = make_guarded_train_step(cfg, tcfg, reduce_backend="xla")
    names = _op_names(jax.jit(step).lower(params, opt, guard, batch))
    tops = _top_scopes(names)
    assert {"jvp(forward)", "transpose(jvp(forward))", "optimizer"} <= tops


def test_guarded_serve_steps_carry_model_cache_census_scopes():
    from repro.launch.serve import GuardedEngine

    eng, cfg = _tiny_engine(GuardedEngine, slots=2)
    packed = jnp.zeros((2, 8), jnp.int32)
    scales = jnp.ones((2,), jnp.float32)
    pre = _op_names(eng._prefill_fn("xla").lower(eng.params, packed, scales))
    tok = jnp.zeros((2, 1), jnp.int32)
    caches = jax.eval_shape(
        lambda p, t, s: eng._prefill_fn("xla")(p, t, s)[1],
        eng.params, packed, scales)
    dec = _op_names(eng._decode_fn("xla").lower(
        eng.params, *split_caches(caches), tok, jnp.asarray(8, jnp.int32),
        scales))
    for names in (pre, dec):
        assert {"model", "census"} <= _top_scopes(names)
        cache = [n for n in names if "/kv_cache/" in n]
        # the cache's reads and writes, inside the model
        assert cache and all(n.startswith("jit(step)/model/") for n in cache)
    # decode writes one position of the cache and slices no layer out of it
    assert any(n.endswith("kv_cache/dynamic_update_slice")
               for n in dec if "/kv_cache/" in n)
    assert not any(n.endswith("kv_cache/dynamic_slice")
                   for n in dec if "/kv_cache/" in n)
