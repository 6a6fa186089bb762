"""The MoE serving cell's benchmark code on the CPU: the MLA and MoE scope
reduction on hand-made traces, the readers of the new per-layer metrics
(and ``None`` where a trace or a run has nothing for them), ``moe_counts``
against a hand count at the published sizes, the driver's configuration
building, and the driver end to end on a tiny configuration."""

from __future__ import annotations

import copy
import json
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

import run
from benchlib import common, moe_counts, moe_serve_cell, moe_trace
from benchlib import trace
from benchlib.peaks import PEAKS
from benchlib.program_trace import MODULE_LINE

MS = 1_000_000  # ns
ROOT = pathlib.Path(__file__).resolve().parents[2]


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS), stats={})


MLA_OP = "%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %p), kind=kLoop"
ROUTER_OP = "%sort.2 = s32[8]{0} sort(s32[8]{0} %k), dimensions={0}"
SHARED_OP = "%fusion.3 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %x)"
GMM_OP = ('%ragged-dot-none.1 = f32[8,16]{1,0} custom-call(s32[9]{0} %m), '
          'custom_call_target="tpu_custom_call"')
OTHER_OP = "%fusion.9 = bf16[2]{0} fusion(bf16[2]{0} %y), kind=kLoop"
SLICE_OP = ("%dynamic-slice_bitcast_fusion.6 = bf16[8,2048,1408]{2,1,0} "
            "fusion(bf16[26,8,2048,1408]{3,2,1,0} %w, s32[] %i), kind=kLoop")
WEIGHTS = ("bf16[8,2048,1408]", "bf16[8,1408,2048]")
OP_NAMES = {"jit_step(1)": {
    "fusion.1": "jit(step)/model/while/body/closed_call/mla/dot_general",
    "sort.2": "jit(step)/model/while/body/closed_call/moe_router/sort",
    "fusion.3": "jit(step)/model/while/body/closed_call/moe_shared/"
                "dot_general;jit(step)/model/moe_shared/mul",
    "fusion.9": "jit(step)/census/add",
    "dynamic-slice_bitcast_fusion.6": "jit(step)/model/while/body/squeeze"}}


def fake_trace(moe=True):
    """Window 0-100 ms; the harness's ``prefill`` span 0-40 and ``decode``
    spans 50-70 and 75-95. Device, all in program 1: the MLA fusion 5-15
    and 52-55, the router's sort 56-57, the grouped matmul 57-63 and
    76-82 and a layer's slice of the held experts' weights 84-86 (both
    left out without ``moe``), the shared experts 63-66, an unscoped
    census op 90-92."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(trace.WINDOW, 0, 100), ev("prefill", 0, 40), ev("decode", 50, 20),
        ev("decode", 75, 20)])])
    ops = [ev(MLA_OP, 5, 10), ev(MLA_OP, 52, 3), ev(ROUTER_OP, 56, 1),
           ev(GMM_OP, 57, 6), ev(GMM_OP, 76, 6), ev(SHARED_OP, 63, 3),
           ev(OTHER_OP, 90, 2), ev(SLICE_OP, 84, 2)]
    if not moe:
        ops = [o for o in ops if o.name not in (GMM_OP, SLICE_OP)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=MODULE_LINE, events=[ev("jit_step(1)", 0, 100)]),
        NS(name=trace.DEVICE_LINE, events=ops)])
    return NS(planes=[host, dev])


def test_reduce_scopes_hand_made_trace():
    red = moe_trace.reduce_scopes(fake_trace(), OP_NAMES, WEIGHTS)
    assert red["moe_scope_s"] == {
        "mla": pytest.approx(0.013), "moe_router": pytest.approx(0.001),
        "moe_experts": pytest.approx(0.014), "moe_shared":
        pytest.approx(0.003)}
    # the prefill's MLA fusion (5-15) is outside every decode span
    assert red["moe_scope_s_decode"] == {
        "mla": pytest.approx(0.003), "moe_router": pytest.approx(0.001),
        "moe_experts": pytest.approx(0.014), "moe_shared":
        pytest.approx(0.003)}
    # without the weights' shapes the slice has no scope
    plain = moe_trace.reduce_scopes(fake_trace(), OP_NAMES)
    assert plain["moe_scope_s"]["moe_experts"] == pytest.approx(0.012)


def test_held_weight_shapes_at_published_sizes():
    m = common.load("configs", "deepseek-v2-lite")["model"]
    assert moe_trace.held_weight_shapes(m) == WEIGHTS


@pytest.mark.parametrize("name,instruction,scope", [
    ("jit(step)/model/while/body/closed_call/mla/dot_general", "f.1", "mla"),
    ("jit(step)/model/moe_router/jit(_where)/select_n", "s.1", "moe_router"),
    ("jit(step)/model/while/body/closed_call/moe_experts/gather", "g",
     "moe_experts"),
    ("", "ragged-dot-none.3", "moe_experts"),
    ("", "ragged-dot-metadata", "moe_experts"),
    ("jit(step)/model/kv_cache/dynamic_update_slice", "d", ""),
])
def test_scope_of(name, instruction, scope):
    assert moe_trace.scope_of(name, instruction) == scope


CELL = "deepseek-v2-lite.serve.chat"


def _r(trace_keys, counters=None):
    cfg = common.load("configs", "deepseek-v2-lite")
    return {"trace": trace_keys, "counters": counters or {},
            "model": cfg["model"], "peaks": PEAKS["TPU v5 lite"],
            "cell": common.load("workloads", CELL),
            "mix": common.load("traffic", "chat-p512-moe")}


def test_serve_readers_on_the_hand_made_trace():
    red = dict(trace.reduce_trace(fake_trace(), {}),
               **moe_trace.reduce_scopes(fake_trace(), OP_NAMES, WEIGHTS))
    counters = {"moe_held_pairs": 2 * 2496, "moe_decode_calls": 2}
    r = _r(red, counters)
    # three engine steps: one prefill, two decodes
    assert run.read_metric("mla_ms.serve", r) == pytest.approx(13 / 3)
    assert run.read_metric("moe_ms.serve", r) == pytest.approx(18 / 3)
    t_min = moe_counts.expert_bytes(r["model"], 2496) / 819e9
    assert run.read_metric("moe_expert_roofline.serve", r) == \
        pytest.approx(100 * t_min / 0.007)
    # 2,496 pairs a call over 8 held experts and 26 MoE layers
    assert run.read_metric("held_expert_load.serve", r) == pytest.approx(12.0)


@pytest.mark.parametrize("name", ["mla_ms.serve", "moe_ms.serve",
                                  "moe_expert_roofline.serve"])
def test_serve_readers_find_nothing_without_the_scopes(name):
    red = trace.reduce_trace(fake_trace(), {})
    assert run.read_metric(name, _r(red, {"moe_held_pairs": 10,
                                          "moe_decode_calls": 1})) is None
    assert run.read_metric(name, _r(None)) is None
    # a program without the scopes or the grouped matmuls (a dense one)
    bare = dict(red, **moe_trace.reduce_scopes(fake_trace(moe=False), {},
                                               WEIGHTS))
    assert run.read_metric(name, _r(bare)) is None


def test_held_expert_load_needs_the_counters():
    assert run.read_metric("held_expert_load.serve", _r(None)) is None


def test_moe_counts_hand_count_at_published_sizes():
    m = common.load("configs", "deepseek-v2-lite")["model"]
    assert moe_counts.moe_layers(m) == 26 and moe_counts.held(m) == 8
    # 128 slots x 6 experts a token x 26 layers x 8 / 64 held
    pairs = 128 * 6 * 26 // 8
    assert pairs == 2496
    assert moe_counts.expert_flops(m, pairs) == 6 * 2048 * 1408 * 2496
    weights = 26 * 8 * 3 * 2048 * 1408 * 2            # 3.5998 GB
    rows = 2 * 2496 * 2048 * 2                        # 20.4 MB
    assert moe_counts.expert_bytes(m, pairs) == weights + rows
    p = PEAKS["TPU v5 lite"]
    assert moe_counts.expert_time_s(m, pairs, p) == pytest.approx(
        (weights + rows) / 819e9)                     # ~4.42 ms, HBM-bound


def test_model_config_builds_the_nested_settings():
    from benchlib import serve_cell
    from repro.configs import ARCHS, MLAConfig, MoEConfig, YaRNConfig

    cfg = common.load("configs", "deepseek-v2-lite")
    model = serve_cell.model_config(cfg)
    assert isinstance(model.mla, MLAConfig) and model.mla.q_lora_rank is None
    assert isinstance(model.moe, MoEConfig) and model.moe.n_held == 8
    assert isinstance(model.rope_scaling, YaRNConfig)
    # the published model but for the experts held here
    published = ARCHS["deepseek-v2-lite"]
    assert model.moe.n_experts == published.moe.n_experts == 64
    assert model.param_count() == pytest.approx(3.11e9, rel=1e-3)
    assert published.param_count() == pytest.approx(15.7e9, rel=1e-3)
    assert {k: v for k, v in cfg.items() if k in cfg["published"]
            and k not in cfg["reduced"]} == {
        k: v for k, v in cfg["published"].items() if k not in cfg["reduced"]}


def _tiny_cell():
    from repro.configs import TINY_ARCHS
    import dataclasses

    cell = dict(common.load("workloads", CELL), reduce_backend="xla",
                slots=4, check_requests=4)
    cfg = copy.deepcopy(common.load("configs", "deepseek-v2-lite"))
    cfg["model"] = json.loads(json.dumps(
        dataclasses.asdict(TINY_ARCHS["deepseek-v2-lite"])))
    cfg["token_vocab"] = 256
    base = common.load("traffic", "chat-p512-moe")
    mix = dict(base, prompt_len=16, rate_per_s=20.0,
               output=dict(base["output"], median=6, min=2, max=12))
    return cell, cfg, mix


def test_moe_serve_driver_end_to_end_on_the_cpu():
    """The driver over a tiny configuration: every request served, the
    census canary caught, the token gap small against the reference, and
    the routed-pair counters filled."""
    import jax
    from repro import reduce as R

    cell, cfg, mix = _tiny_cell()
    try:
        res = moe_serve_cell.run(cell, cfg, mix, 2**31 + 5, 0.5, None, 0.0,
                                 jax.devices())
    finally:
        R.set_default_backend(None)
    n = res["numbers"]
    assert n["failed_requests"] == 0 and n["census_misses"] == 0
    assert n["token_gap"] < 0.3 and n["tokens_checked"] > 0
    c = res["counters"]
    assert c["moe_decode_calls"] > 0 and c["moe_held_pairs"] > 0
    assert c["moe_held_pairs"] <= (c["moe_decode_calls"] * cell["slots"]
                                   * cfg["model"]["moe"]["top_k"] * 2)
    r = {"counters": c, "trace": None, "model": cfg["model"]}
    load = run.read_metric("held_expert_load.serve", r)
    assert 0 < load <= cell["slots"] * 3
    assert np.isfinite(run.read_metric("itl_p95_ms", r))
    # the serving cells' scheduler metrics read the driver's counters too
    assert 0 < run.read_metric("slot_occupancy.serve", r) <= 100
    assert run.read_metric("queue_wait_p83_ms.serve", r) >= 0
