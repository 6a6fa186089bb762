"""``benchlib.trace``: the reduction from a profiler trace to busy and
idle time, time by operation name, idle gaps named by the harness's
spans, the engine's launches told from other Mosaic kernels by their
kernel function, and the metrics that read them -- on a hand-made trace
whose numbers are worked out by hand, and on a small trace recorded on a
TPU v5e (``data/tiny_train.xplane.pb``, made by ``record_trace.py``)."""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

import run
from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data" / "tiny_train.xplane.pb"
MS = 1_000_000  # ns


FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KERNEL = ('%step.1 = f32[1,19]{1,0:T(1,128)S(1)} custom-call(bf16[4,8]'
          '{1,0:T(8,128)(2,1)} %a, f32[16]{0} %b), '
          'custom_call_target="tpu_custom_call"')
# another Mosaic kernel, which is not the engine's
ATTN = ('%attn.4 = bf16[2,8]{1,0} custom-call(bf16[2,8]{1,0} %q), '
        'custom_call_target="tpu_custom_call"')
ALL_REDUCE = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)"
# a loop op around the all-reduce and the attention kernel
LOOP = "%while.2 = (s32[]) while((s32[]) %t), body=%b"
KERNELS = {"step.1": {"parts_accumulate_kernel"}, "attn.4": {"_attn_kernel"}}


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def fake_trace():
    """Window 0-100 ms. Device 0 runs a fusion 10-25, an engine kernel
    25-40 and a loop 60-70 around an all-reduce 61-67 and an attention
    kernel 67-69; device 1 the same shifted by +10 ms. Host: step 0-50,
    sync 50-80, data 80-100."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(trace.WINDOW, 0, 100), ev("step", 0, 50), ev("sync", 50, 30),
        ev("data", 80, 20), ev("not_a_span", 0, 100)])])

    def dev(i, shift):
        return NS(name=f"/device:TPU:{i}", lines=[
            NS(name=trace.DEVICE_LINE, events=[
                ev(FUSION, 10 + shift, 15),
                ev(KERNEL, 25 + shift, 15),
                ev(ALL_REDUCE, 61 + shift, 6),
                ev(ATTN, 67 + shift, 2),
                ev(LOOP, 60 + shift, 10)]),
            NS(name="XLA Modules", events=[ev("jit_step", 0, 100)])])

    return NS(planes=[host, dev(0, 0), dev(1, 10)])


def test_reduce_hand_made_trace():
    red = trace.reduce_trace(fake_trace(), KERNELS)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["chips"] == 2
    # busy per device: 10-40 and 60-70 = 40 ms (device 1 the same, shifted)
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["ops"]["fusion.1"] == pytest.approx(0.015)
    assert red["ops"]["step.1"] == pytest.approx(0.015)
    assert red["ops"]["all-reduce.3"] == pytest.approx(0.006)
    assert red["ops"]["attn.4"] == pytest.approx(0.002)
    assert red["ops"]["while.2"] == pytest.approx(0.002)
    assert red["engine_s"] == pytest.approx(0.015)
    assert red["custom_calls"]["step.1"] == {
        "s": pytest.approx(0.015), "n": 2, "in_bytes": 4 * 8 * 2 + 16 * 4}
    assert red["other_kernels"] == {"attn.4": pytest.approx(0.002)}
    # device 0 idle: 0-10 step, 40-60 step 10 / sync 10 (the first wins),
    # 70-100 sync 10 / data 20; device 1: 0-20 step, 50-70 sync, 80-100 data
    idle = red["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(0.060)
    assert idle["step"] == pytest.approx((0.010 + 0.020 + 0.020) / 2)
    assert idle["sync"] == pytest.approx(0.020 / 2)
    assert idle["data"] == pytest.approx((0.030 + 0.020) / 2)
    assert red["span_counts"] == {"step": 1, "sync": 1, "data": 1}


def test_metrics_from_hand_made_trace():
    red = trace.reduce_trace(fake_trace(), KERNELS)
    r = {"trace": red}
    assert run.read_metric("idle_share.train", r) == pytest.approx(60.0)
    assert run.read_metric("engine_ms.train", r) == pytest.approx(15.0)
    b = trace.breakdown(red)
    assert [x[0] for x in b["device_ops"]] == ["fusion.1", "step.1",
                                               "all-reduce.3", "while.2",
                                               "attn.4"]
    assert {b["idle_gaps"][0][0], b["idle_gaps"][1][0]} == {"step", "data"}
    assert b["idle_gaps"][2] == ["sync", pytest.approx(0.010)]


def test_op_text_parsing():
    assert trace.op_name(KERNEL) == "step.1"
    assert trace.operand_bytes(KERNEL) == 4 * 8 * 2 + 16 * 4
    assert trace.operand_bytes(FUSION) == 32


def test_clip_statistic_roofline_from_hand_made_trace():
    from benchlib.peaks import peaks_for

    red = trace.reduce_trace(fake_trace(), KERNELS)
    model = {"n_layers": 0, "d_model": 4, "n_heads": 1, "n_kv_heads": 1,
             "d_head": 1, "d_ff": 1, "vocab_size": 12,
             "tie_embeddings": True, "norm": "layernorm_np"}
    # the tree: 4 x 12 = 48 elements, 96 bytes; the launch reads 128 bytes
    r = {"trace": red, "model": model, "peaks": peaks_for("TPU v5 lite")}
    want = 100.0 * (96 / 819e9) / 0.015
    assert run.read_metric("clip_stat_roofline.train", r) == pytest.approx(
        want)


def test_window_clips_device_time():
    t = fake_trace()
    t.planes[0].lines[0].events[0] = ev(trace.WINDOW, 20, 40)
    red = trace.reduce_trace(t, KERNELS)
    assert red["window_s"] == pytest.approx(0.040)
    # inside 20-60, device 0 runs 20-40 and device 1 runs 20-50
    assert red["busy_s"] == pytest.approx(0.025)


def test_no_window_or_device_is_an_error():
    t = fake_trace()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="window"):
        trace.reduce_trace(t, KERNELS)
    with pytest.raises(ValueError, match="device"):
        trace.reduce_trace(NS(planes=[fake_trace().planes[0]]), KERNELS)


@pytest.fixture(scope="module")
def chip_trace():
    if not DATA.exists():
        pytest.skip("no recorded chip trace")
    return trace.reduce_trace(trace.load(str(DATA)),
                              trace.mosaic_kernels(DATA.read_bytes()))


def test_recorded_chip_trace(chip_trace):
    red = chip_trace
    assert red["chips"] == 1
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["span_counts"]["step"] == 2
    assert red["span_counts"]["sync"] == 2
    assert red["engine_s"] > 0.0
    assert red["other_kernels"] == {}
    assert sum(red["ops"].values()) >= red["busy_s"] * 0.999
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(idle)


def test_engine_kernels_named_from_the_trace_hlo():
    """The recorded step runs two engine kernels: the clip statistic over
    the gradient tree (the launch XLA names after the step's jit) and a
    fused accumulation inside the layer loop."""
    assert trace.mosaic_kernels(DATA.read_bytes()) == {
        "guarded_step.1": {"parts_accumulate_kernel"},
        "closed_call.34": {"fused_accumulate_kernel"}}
    assert trace.is_engine({"scan_kernel"})
    assert not trace.is_engine({"parts_accumulate_kernel", "_ce_kernel"})
    assert not trace.is_engine(set())
