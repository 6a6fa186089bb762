"""Paper's analytic claims (section IV.B / V), validated exactly."""

import math

import pytest

from repro.core import cost_model as cm


def test_eq16_t_tc():
    assert cm.t_tensor_core(16**2, 16) == pytest.approx(5.0)
    assert cm.t_tensor_core((16**2) ** 3, 16) == pytest.approx(15.0)
    assert cm.t_tensor_core(2**20, 4) == pytest.approx(5 * math.log(2**20, 16))


def test_classic_4log2():
    assert cm.t_classic(2**10) == pytest.approx(40.0)


def test_eq17_speedup_closed_form():
    """S = (4/5) log2(m^2); paper section V: S(4) ~ 3.2, S(16) ~ 6.4,
    and S > 1 already at the minimum m = 2."""
    assert cm.speedup_model(4) == pytest.approx(3.2)
    assert cm.speedup_model(16) == pytest.approx(6.4)
    assert cm.speedup_model(2) == pytest.approx(1.6) and cm.speedup_model(2) > 1
    # TPU MXU tile: the model extrapolates to S ~ 11.2 at m = 128
    assert cm.speedup_model(128) == pytest.approx(11.2)


def test_ratio_equals_closed_form():
    """T_classic/T_tc == S independent of n (both are log n)."""
    for m in (2, 4, 16, 128):
        for n in (2**12, 2**24):
            ratio = cm.t_classic(n) / cm.t_tensor_core(n, m)
            assert ratio == pytest.approx(cm.speedup_model(m), rel=1e-9)


def test_multicore_mma_counts():
    """The striped-pipeline model: n/(m^2 c) + c MMAs on the critical path,
    recovering the serial fused count n/m^2 + 2 at c = 1."""
    n = 1 << 24  # 1024 tiles at m=128
    serial = cm.fused_mma_ops(n, num_cores=1)
    assert serial.lane == 1024 and serial.combine == 2
    assert serial.total == 1024 + 2 and serial.critical_path == 1026
    c4 = cm.fused_mma_ops(n, num_cores=4)
    assert c4.num_cores == 4 and c4.lane == 256 and c4.combine == 5
    assert c4.total == 4 * 256 + 5
    # striping cuts the critical path ~c-fold while total stays ~n/m^2
    assert c4.critical_path < serial.critical_path / 3
    # lanes never exceed the block count (tiny problems stay serial)
    tiny = cm.fused_mma_ops(100, num_cores=8)
    assert tiny.num_cores == 1 and tiny.lane == 1
    # monotone: more lanes never lengthens the critical path
    paths = [
        cm.fused_mma_ops(n, num_cores=c).critical_path for c in (1, 2, 4, 8)
    ]
    assert paths == sorted(paths, reverse=True)


def test_segmented_mma_counts():
    segments, tiles = 32, 4096
    serial = cm.segmented_mma_ops(
        tiles * 128 * 128, tiles=tiles, flushes=segments, num_cores=1
    )
    assert serial.total == tiles + segments  # n/m^2 + S
    c2 = cm.segmented_mma_ops(
        tiles * 128 * 128, tiles=tiles, flushes=40, num_cores=2
    )
    assert c2.lane == tiles // 2 and c2.combine == 40
    assert c2.critical_path < serial.critical_path
    # flushes run INSIDE their lanes concurrently: with the worst lane's
    # share known, only that share sits on the critical path (total MMAs
    # issued chip-wide are unchanged)
    c2b = cm.segmented_mma_ops(
        tiles * 128 * 128, tiles=tiles, flushes=40, num_cores=2,
        max_lane_flushes=22,
    )
    assert c2b.total == c2.total
    assert c2b.critical_path == tiles // 2 + 22


def test_tpu_roofline_terms():
    rl = cm.tpu_reduction_roofline(1 << 24, bytes_per_el=2)
    # cold reductions are HBM-bound: both compute paths fit under ~1.5x the
    # stream time at this size
    assert rl.hbm_s > 0 and rl.vpu_s > 0 and rl.mxu_s > 0
    assert rl.mxu_s < 1.5 * rl.hbm_s
    assert rl.cold_bound_s >= rl.hbm_s
    # monotonic in n
    rl2 = cm.tpu_reduction_roofline(1 << 26, bytes_per_el=2)
    assert rl2.hbm_s > rl.hbm_s and rl2.mxu_s > rl.mxu_s


def test_model_table_rows():
    rows = cm.model_table(ns=(2**16,), ms=(4, 16))
    assert len(rows) == 2
    for r in rows:
        assert r["speedup"] == pytest.approx(r["speedup_closed_form"], rel=1e-9)


def test_peaks_table_keyed_by_device_kind():
    v5e = cm.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (
        197e12, 819e9, 16 * 2**30
    )
    # the clock the bf16 peak implies: 4 MXUs x 128 x 128 MACs per cycle
    assert v5e.clock_hz == pytest.approx(1.503e9, rel=1e-3)
    with pytest.raises(ValueError, match="no published peaks"):
        cm.peaks_for("TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        cm.tpu_reduction_roofline(1 << 20, device_kind="TPU v99")


def test_planner_tpu_route_refuses_an_unknown_kind(monkeypatch):
    """On a TPU the auto route reads the peaks of the chip it runs on; a
    kind missing from the table is an error, not the v5e default."""
    import types

    import jax
    import jax.numpy as jnp

    from repro.reduce import plan

    n = 1 << 22
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(platform="tpu",
                                          device_kind="TPU v5 lite")],
    )
    assert plan._auto_backend((n,), jnp.float32, kind="sum", axis=None,
                              m=128) == "pallas_fused"
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(platform="tpu",
                                          device_kind="TPU v99")],
    )
    with pytest.raises(ValueError, match="TPU v99"):
        plan._auto_backend((n,), jnp.float32, kind="sum", axis=None, m=128)
