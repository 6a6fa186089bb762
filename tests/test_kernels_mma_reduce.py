"""Pallas mma_reduce backends vs pure-jnp oracle, driven through the unified
``repro.reduce`` engine (+ hypothesis property tests)."""

from _optional_hypothesis import hypothesis, st
import harness
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import reduce as R
from repro.kernels.mma_reduce import ref

SIZES = [1, 5, 127, 128, 16384, 16385, 100_000, 300_000]
DTYPES = [np.float32, np.float16]
PALLAS_BACKENDS = ["pallas_hier", "pallas_fused"]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("backend", PALLAS_BACKENDS)
def test_matches_sum_oracle(n, dtype, backend, rng):
    x = rng.randn(n).astype(dtype)
    got = float(R.reduce(jnp.asarray(x), backend=backend))
    want = float(ref.sum_ref(jnp.asarray(x)))
    tol = harness.mass_tol(x)  # bf16 multipliers; shared budget
    assert abs(got - want) <= tol, (got, want)


@pytest.mark.parametrize("n", [128 * 128, 3 * 128 * 128, 130_000])
def test_hierarchical_matches_eq13_oracle_exactly(n, rng):
    """The kernel's hierarchical mode must match the eq. (13) jnp emulation
    bit-for-bit (same tiling, same bf16 rounding)."""
    x = rng.randn(n).astype(np.float32)
    got = float(R.reduce(jnp.asarray(x), backend="pallas_hier"))
    want = float(ref.hierarchy_ref(jnp.asarray(x)))
    assert got == want


def test_two_mma_tile_algebra(rng):
    """Eq. (9)-(12): per-tile partials equal replicated row/col sums."""
    tiles = jnp.asarray(rng.randn(4, 16, 16).astype(np.float32))
    got = ref.two_mma_ref(tiles, compute_dtype=jnp.float32)
    want = jnp.sum(tiles, axis=(1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_fused_mode_more_accurate_than_hierarchical(rng):
    """The C-accumulator variant keeps partials in f32 -> strictly less
    rounding than the paper's write-back-and-relaunch hierarchy."""
    x = rng.randn(1 << 20).astype(np.float32)
    exact = x.astype(np.float64).sum()
    err_h = abs(float(R.reduce(jnp.asarray(x), backend="pallas_hier")) - exact)
    err_f = abs(float(R.reduce(jnp.asarray(x), backend="pallas_fused")) - exact)
    assert err_f <= err_h + 1e-6


def test_gradient():
    x = jnp.arange(300.0, dtype=jnp.float32)
    g = jax.grad(lambda y: R.reduce(y, backend="pallas_fused"))(x)
    np.testing.assert_allclose(np.asarray(g), 1.0)


def test_zero_size_input_is_additive_identity():
    """Regression: empty operands reduce to 0.0 on both kernel modes rather
    than erroring on a degenerate pad."""
    for backend in PALLAS_BACKENDS:
        assert float(R.reduce(jnp.zeros((0,)), backend=backend)) == 0.0


def test_segmented_kernel_matches_ref(rng):
    """The single-launch segmented kernel vs the per-segment oracle, across
    boundary-hostile layouts (boundaries inside and across tile blocks)."""
    from repro.kernels.mma_reduce import ops

    for sizes in (
        [100, 64, 1, 200],
        [5],
        [0, 3, 0],
        [16384, 1, 16385],          # exact tile, then straddling
        [7] * 19,                   # many boundaries inside one block
    ):
        offsets = np.concatenate([[0], np.cumsum(sizes)]).tolist()
        flat = jnp.asarray(rng.randn(int(offsets[-1])).astype(np.float32))
        for tpb in (1, 2, 8):
            got = ops.mma_sum_segments_pallas(
                flat, offsets, tiles_per_block=tpb,
                compute_dtype=jnp.float32,
            )
            want = ref.segmented_sum_ref(flat, offsets)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
                err_msg=f"sizes={sizes} tiles_per_block={tpb}",
            )


def test_segmented_kernel_empty_cases():
    from repro.kernels.mma_reduce import ops

    assert ops.mma_sum_segments_pallas(jnp.zeros((0,)), (0,)).shape == (0,)
    out = ops.mma_sum_segments_pallas(jnp.zeros((0,)), (0, 0, 0))
    np.testing.assert_array_equal(np.asarray(out), [0.0, 0.0])


def test_segment_tile_layout_static_maps():
    from repro.kernels.mma_reduce import ops

    tcounts, seg_of, flush = ops.segment_tile_layout((0, 5, 5, 40), 16)
    assert tcounts == (1, 0, 3)
    np.testing.assert_array_equal(seg_of, [0, 2, 2, 2])
    np.testing.assert_array_equal(flush, [1, 0, 0, 1])


def test_legacy_shim_still_works(rng):
    """The pre-engine entry points survive as deprecation shims."""
    import repro.kernels as K

    x = jnp.asarray(rng.randn(1000).astype(np.float32))
    with pytest.deprecated_call():
        got = float(K.mma_sum_pallas(x, mode="fused"))
    np.testing.assert_allclose(
        got, float(R.reduce(x, backend="pallas_fused")), rtol=1e-6
    )


@hypothesis.settings(max_examples=25, deadline=None)
@hypothesis.given(
    n=st.integers(1, 40_000),
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(0.01, 100.0),
)
def test_property_sum_equivalence(n, seed, scale):
    x = np.random.RandomState(seed).randn(n).astype(np.float32) * scale
    got = float(R.reduce(jnp.asarray(x), backend="pallas_fused"))
    want = float(x.astype(np.float64).sum())
    tol = harness.mass_tol(x, floor=1e-3)
    assert abs(got - want) <= tol


# ------------------- multi-core striped grid (tentpole) ----------------------


@pytest.mark.parametrize("num_cores", [1, 2, 3, 5])
@pytest.mark.parametrize("tpb", [1, 4, 8])
def test_multicore_lane_partials_bit_exact(num_cores, tpb, rng):
    """The striped kernel must match the op-for-op jnp emulation bit-for-bit
    for every lane geometry -- this pins striping, the masked-tail loads,
    and the per-lane carry, and (at num_cores=1) the pre-striping kernel's
    exact behavior. The kernel now ingests the FLAT buffer zero-copy; the
    emulation models the in-kernel masking as zero-padding (value-identical)."""
    from repro.kernels.mma_reduce import kernel as K

    x = jnp.asarray(rng.randn(100_000).astype(np.float32))
    got = np.asarray(
        K.reduce_fused(x, tiles_per_block=tpb, num_cores=num_cores)
    )
    want = np.asarray(
        ref.fused_lanes_ref(x, tiles_per_block=tpb, num_cores=num_cores)
    )
    assert got.shape == want.shape
    np.testing.assert_array_equal(
        got.view(np.uint32), want.view(np.uint32)
    )


@pytest.mark.parametrize("backend", PALLAS_BACKENDS)
@pytest.mark.parametrize("num_cores", [2, 4])
def test_multicore_matches_oracle(backend, num_cores, rng):
    """num_cores > 1 agrees with the xla oracle to the existing tolerances
    (pallas_hier ignores the knob -- its grid is already fully parallel)."""
    for n in (127, 16384, 100_000, 300_000):
        x = rng.randn(n).astype(np.float32)
        got = float(
            R.reduce(jnp.asarray(x), backend=backend, num_cores=num_cores)
        )
        want = float(x.astype(np.float64).sum())
        tol = harness.mass_tol(x)
        assert abs(got - want) <= tol, (n, got, want)


def test_multicore_exact_when_f32_and_integer_valued(rng):
    """With f32 multipliers and integer-valued data every partial is exact,
    so ANY lane count must give the exact per-segment sums -- this pins the
    lane-aware flush maps (no tile double-counted, none dropped)."""
    from repro.kernels.mma_reduce import ops

    for sizes in ([100, 64, 1, 200], [16384, 1, 16385], [7] * 19, [0, 3, 0]):
        offsets = np.concatenate([[0], np.cumsum(sizes)]).tolist()
        flat = jnp.asarray(
            rng.randint(-8, 8, size=int(offsets[-1])).astype(np.float32)
        )
        want = [
            float(np.asarray(flat[offsets[s] : offsets[s + 1]]).sum())
            for s in range(len(sizes))
        ]
        for c in (1, 2, 3, 4):
            for tpb in (1, 2, 8):
                got = ops.mma_sum_segments_pallas(
                    flat, offsets, tiles_per_block=tpb, num_cores=c,
                    compute_dtype=jnp.float32,
                )
                np.testing.assert_array_equal(
                    np.asarray(got), want,
                    err_msg=f"sizes={sizes} c={c} tpb={tpb}",
                )
        x = jnp.asarray(rng.randint(-8, 8, size=50_000).astype(np.float32))
        for c in (1, 2, 3):
            got = ops.mma_sum_pallas(
                x, mode="fused", num_cores=c, compute_dtype=jnp.float32
            )
            assert float(got) == float(np.asarray(x).sum()), c


@pytest.mark.parametrize("num_cores", [1, 2, 4])
def test_multicore_run_to_run_deterministic(num_cores, rng):
    """Two independent evaluations (fresh jit each) -> identical bits: the
    fixed-order lane combine must leave nothing schedule-dependent."""
    x = jnp.asarray(rng.randn(200_000).astype(np.float32))
    arrs = [x[:333], x[333:70_000], x[70_000:]]

    def full():
        return jax.jit(
            lambda a: R.reduce(a, backend="pallas_fused", num_cores=num_cores)
        )(x)

    def many():
        return jax.jit(
            lambda *a: R.reduce_many(
                a, backend="pallas_fused", num_cores=num_cores
            )
        )(*arrs)

    a, b = np.asarray(full()), np.asarray(full())
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    a, b = np.asarray(many()), np.asarray(many())
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_multicore_lane_flush_map():
    """Lane-aware boundary flags: every (lane, segment) group flushes exactly
    once, at its lane-maximal tile; C=1 reduces to the serial map."""
    from repro.kernels.mma_reduce import ops

    seg_of = np.asarray([0, 0, 0, 1, 1, 2, 2, 2], np.int32)
    serial = ops.lane_flush_map(seg_of, 1, 1)
    np.testing.assert_array_equal(serial, [0, 0, 1, 0, 1, 0, 0, 1])
    # r=1, c=2: lane 0 owns tiles 0,2,4,6; lane 1 owns 1,3,5,7
    striped = ops.lane_flush_map(seg_of, 1, 2)
    # lane 0 leaves seg0 after tile 2, seg1 after 4, seg2 after 6;
    # lane 1 leaves seg0 after tile 1, seg1 after 3, seg2 after 7
    np.testing.assert_array_equal(striped, [0, 1, 1, 1, 1, 0, 1, 1])
    for c in (1, 2, 3):
        f = ops.lane_flush_map(seg_of, 2, c)
        assert f.sum() >= 3  # every segment flushes at least once
        assert f.sum() <= 3 * c  # at most one flush per (lane, segment) visit


def test_segmented_kernel_pads_non_multiple_streams(rng):
    """Regression (carried over): ``reduce_segments`` pads the COVER MAPS
    itself when the tile count is not a multiple of the lane count -- pad
    tiles are fully-masked no-ops (lo == hi == 0), so a 3-tile cover on 2
    lanes reduces exactly."""
    from repro.kernels.mma_reduce import kernel as K
    from repro.kernels.mma_reduce import ops

    m = 128
    group = m * m
    flat = jnp.asarray(rng.randn(3 * group).astype(np.float32))
    offsets = (0, 2 * group, 3 * group)
    _, src, seg_of, lo, hi = ops.segment_cover_layout(offsets, group)
    flush = ops.lane_flush_map(seg_of, 1, 2)
    sub = K.reduce_segments(
        flat, src, seg_of, flush, lo, hi, 2, num_cores=2,
        compute_dtype=jnp.float32,
    )
    got = np.asarray(sub).sum(0)
    want = [float(jnp.sum(flat[: 2 * group])), float(jnp.sum(flat[2 * group :]))]
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_multicore_trace_counts_match_cost_model():
    """ops' static ReductionTrace split == cost_model.fused_mma_ops: the
    geometry the kernel runs and the model the planner trusts must agree."""
    from repro.core import cost_model
    from repro.kernels.mma_reduce import ops

    for n in (1, 130_000, 1 << 20, 1 << 24):
        for tpb in (2, 8):
            for c in (1, 2, 4, 16):
                tr = ops.fused_trace(n, tpb, c)
                mc = cost_model.fused_mma_ops(
                    n, num_cores=c, tiles_per_block=tpb
                )
                assert tr.num_cores == mc.num_cores
                assert tr.lane_mma_ops == mc.lane
                assert tr.combine_mma_ops == mc.combine
                assert tr.mma_ops == mc.total, (n, tpb, c)
    # num_cores=1 recovers the serial fused count: n/m^2 (+pad) + 2
    assert ops.fused_trace(1 << 20, 8, 1).mma_ops == 64 + 2
    # segmented: traced flush count == in-kernel collapse MMAs
    tr: list = []
    ops.mma_sum_segments_pallas(
        jnp.ones(40_000), (0, 20_000, 40_000), num_cores=2, trace=tr
    )
    (t,) = tr
    # each segment pads to whole tiles: 2 x ceil(20_000 / 128^2) = 4 tiles
    mc = cost_model.segmented_mma_ops(
        40_000, tiles=4, flushes=t.combine_mma_ops, num_cores=2
    )
    assert t.mma_ops == mc.total
    assert t.lane_mma_ops == mc.lane and t.num_cores == mc.num_cores


@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.given(
    n=st.integers(1, 40_000),
    seed=st.integers(0, 2**31 - 1),
    num_cores=st.integers(1, 5),
    tpb=st.sampled_from([1, 2, 4, 8]),
    dtype=st.sampled_from([np.float32, np.float16]),
)
def test_property_multicore_grid_vs_oracle(n, seed, num_cores, tpb, dtype):
    """Acceptance sweep: the grid-parallel kernel pinned to the xla oracle
    across ragged n x dtype x num_cores x tiles_per_block."""
    x = np.random.RandomState(seed).randn(n).astype(dtype)
    got = float(
        R.reduce(
            jnp.asarray(x),
            backend="pallas_fused",
            num_cores=num_cores,
            tiles_per_block=tpb,
        )
    )
    want = float(x.astype(np.float64).sum())
    tol = harness.mass_tol(x, floor=1e-3)
    assert abs(got - want) <= tol


@pytest.mark.parametrize("num_cores", [1, 2])
def test_multicore_kahan_single_launch_and_accurate(num_cores, rng):
    """precision="kahan" on pallas_fused carries the compensation in-kernel:
    still ONE pallas_call, and at least as accurate as the native carry."""
    x = jnp.asarray((rng.randn(300_000) * 100).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda v: R.reduce(
            v, backend="pallas_fused", precision="kahan", num_cores=num_cores
        )
    )(x)
    assert str(jaxpr).count("pallas_call") == 1
    exact = np.asarray(x).astype(np.float64).sum()
    e_native = abs(
        float(
            R.reduce(
                x, backend="pallas_fused", compute_dtype="float32",
                num_cores=num_cores,
            )
        )
        - exact
    )
    e_kahan = abs(
        float(
            R.reduce(
                x, backend="pallas_fused", compute_dtype="float32",
                precision="kahan", num_cores=num_cores,
            )
        )
        - exact
    )
    assert e_kahan <= e_native + 1e-9


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_resolve_interpret_from_the_default_backend(backend, want,
                                                    monkeypatch):
    """Interpreted on the CPU, compiled on a TPU, refused elsewhere: no
    backend silently falls back to the interpreter."""
    from repro.kernels import common

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            common.resolve_interpret(None)
    else:
        assert common.resolve_interpret(None) is want
    assert common.resolve_interpret(True) is True  # explicit always wins


F32_MMA_CASES = {
    "fused_f32": lambda x: R.reduce(x, backend="pallas_fused",
                                    compute_dtype="float32"),
    "fused_bf16": lambda x: R.reduce(x.astype(jnp.bfloat16),
                                     backend="pallas_fused"),
    "hier_f32": lambda x: R.reduce(x, backend="pallas_hier",
                                   compute_dtype="float32"),
    "kahan": lambda x: R.reduce(x, backend="pallas_fused", precision="kahan",
                                compute_dtype="float32"),
    "tree_census_fork": lambda x: R.reduce_tree(
        [x, x[:300]], "norm2", backend="pallas_fused", census=True,
        epilogue=[(), ("clip_coeff", 1.0)]),
    "many": lambda x: R.reduce_many(
        [x, x[:300].astype(jnp.bfloat16)], backend="pallas_fused",
        kind="sumsq"),
    "scan_f32": lambda x: R.scan(x, backend="pallas_fused"),
    "scan_bf16": lambda x: R.scan(x.astype(jnp.bfloat16),
                                  backend="pallas_fused"),
    "mma_jnp_norm2": lambda x: R.reduce(x, kind="norm2", backend="mma_jnp"),
    "mma_jnp_scan_f32": lambda x: R.scan(x[:4096].reshape(4, -1),
                                          backend="mma_jnp"),
}


@pytest.mark.parametrize("name", sorted(F32_MMA_CASES))
def test_f32_mma_operands_contract_at_highest_precision(name):
    """On the TPU a dot at default precision rounds f32 operands to bf16
    (interpret mode and the CPU cannot show it). Every engine MMA with an
    f32 operand asks for HIGHEST; 16-bit-only MMAs keep the native pass."""
    from repro.reduce import inspect as rinspect

    x = jnp.ones((3 * 128 * 128 + 5,), jnp.float32)
    jaxpr = jax.make_jaxpr(F32_MMA_CASES[name])(x)
    dots = [e for e, _ in rinspect.iter_eqns(jaxpr)
            if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        wide = any(v.aval.dtype == jnp.float32 for v in e.invars)
        prec = e.params["precision"]
        highest = prec is not None and all(
            p == jax.lax.Precision.HIGHEST for p in prec)
        assert highest == wide, (name, e)
