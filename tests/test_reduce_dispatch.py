"""The unified reduction engine: every backend must agree with the "xla"
oracle on every kind, across dtypes, shapes and plan overrides -- and stay
differentiable throughout."""

import harness
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import reduce as R

BACKENDS = ("xla", "mma_jnp", "pallas_hier", "pallas_fused")
MMA_BACKENDS = tuple(b for b in BACKENDS if b != "xla")
SEG_BACKENDS = BACKENDS + ("segmented",)

# (shape, axis) cases: scalar, tiny, ragged, multi-axis, > m^2 extents
FULL_CASES = [((), None), ((7,), None), ((1000,), None), ((20_000,), None)]
AXIS_CASES = [((33, 700), -1), ((6, 50, 40), (1, 2)), ((4, 130), 1),
              ((2, 3, 5), (0, 2))]


def _make(shape, dtype, rng):
    if np.issubdtype(dtype, np.integer):
        return jnp.asarray(rng.randint(-40, 40, size=shape or ()), dtype)
    return jnp.asarray(np.asarray(rng.randn(*shape), np.float32)).astype(dtype)


def _oracle_sum(x, axis):
    return np.asarray(x).astype(np.float64).sum(axis=axis)


def _tol(x):
    # bf16 multipliers: error scales with the mass of the operand
    # (the engine-wide budget; see tests/harness.py)
    return harness.mass_tol(x)


def test_registry_contains_all_four_backends():
    assert set(BACKENDS) <= set(R.available_backends())
    with pytest.raises(KeyError, match="unknown reduce backend"):
        R.get_backend("nope")
    with pytest.raises(ValueError, match="unknown kind"):
        R.reduce(jnp.ones(4), kind="max")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int32])
@pytest.mark.parametrize("shape,axis", FULL_CASES + AXIS_CASES)
def test_all_backends_agree_with_oracle(backend, dtype, shape, axis, rng):
    x = _make(shape, dtype, rng)
    ax = axis if not isinstance(axis, int) else (axis % max(x.ndim, 1),)
    ax_np = tuple(ax) if axis is not None else None
    got = R.reduce(x, axis=axis, backend=backend)
    want = _oracle_sum(x, ax_np)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, atol=_tol(x), rtol=1e-3
    )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", R.KINDS)
def test_every_kind_on_every_backend(backend, kind, rng):
    x = jnp.asarray(rng.randn(5000).astype(np.float32))
    xf = np.asarray(x).astype(np.float64)
    got = R.reduce(x, kind=kind, backend=backend)
    if kind == "moments":
        np.testing.assert_allclose(float(got[0]), xf.sum(), atol=_tol(x))
        np.testing.assert_allclose(float(got[1]), (xf**2).sum(), atol=_tol(x))
        return
    want = {
        "sum": xf.sum(),
        "mean": xf.mean(),
        "sumsq": (xf**2).sum(),
        "norm2": np.sqrt((xf**2).sum()),
    }[kind]
    np.testing.assert_allclose(float(got), want, atol=_tol(x), rtol=1e-3)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", ["sum", "mean", "sumsq", "norm2"])
def test_gradients_per_backend(backend, kind, rng):
    x = jnp.asarray((rng.rand(400) + 0.5).astype(np.float32))
    g = jax.grad(lambda y: R.reduce(y, kind=kind, backend=backend))(x)
    xf = np.asarray(x).astype(np.float64)
    want = {
        "sum": np.ones_like(xf),
        "mean": np.ones_like(xf) / xf.size,
        "sumsq": 2 * xf,
        "norm2": xf / np.sqrt((xf**2).sum()),
    }[kind]
    np.testing.assert_allclose(np.asarray(g), want, rtol=2e-3, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_moments_gradient(backend, rng):
    x = jnp.asarray(rng.randn(12, 300).astype(np.float32))

    def f(y):
        s, ss = R.reduce(y, axis=-1, kind="moments", backend=backend)
        return jnp.sum(s) + jnp.sum(ss)

    g = jax.grad(f)(x)
    want = 1.0 + 2 * np.asarray(x).astype(np.float64)
    np.testing.assert_allclose(np.asarray(g), want, rtol=2e-3, atol=1e-4)


def test_out_of_range_axis_raises(rng):
    """Bad axes must raise (numpy semantics), never silently wrap."""
    x = jnp.ones((3, 4))
    for bad in (2, 5, -3):
        with pytest.raises(ValueError, match="out of range"):
            R.reduce(x, axis=bad)
    # numpy convention: 0-d arrays accept axis 0 / -1, reject the rest
    assert float(R.reduce(jnp.asarray(3.0), axis=0)) == 3.0
    with pytest.raises(ValueError, match="out of range"):
        R.reduce(jnp.asarray(3.0), axis=1)
    # duplicate axes raise (numpy semantics), never silently dedup
    with pytest.raises(ValueError, match="duplicate axis"):
        R.reduce(x, axis=(0, -2))


def test_pallas_backends_reject_non_mxu_tile(rng):
    """The kernels implement the 128-wide MXU tile only; a pinned m != 128
    must raise rather than silently run the wrong configuration."""
    x = jnp.asarray(rng.randn(1000).astype(np.float32))
    with pytest.raises(ValueError, match="m=128 MXU tile"):
        R.reduce(x, backend="pallas_fused", m=16)
    # tile-size ablations go through the algorithmic backend
    assert np.isfinite(float(R.reduce(x, backend="mma_jnp", m=16)))


def test_empty_axis_tuple_is_identity(rng):
    """axis=() follows the numpy convention: reduce over NO axes."""
    x = jnp.asarray(rng.randn(8).astype(np.float32))
    out = R.reduce(x, axis=(), backend="mma_jnp")
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(R.reduce(x, axis=(), kind="sumsq")),
        np.asarray(x) ** 2,
        rtol=1e-6,
    )


def test_forward_mode_autodiff_on_native_backends(rng):
    """jvp/jacfwd/hessian must flow through the jnp-level backends, exactly
    as they did through the pre-engine jnp.sum / row_sum_mma call sites."""
    x = jnp.asarray(rng.randn(256).astype(np.float32))
    t = jnp.ones_like(x)
    for b in ("xla", "mma_jnp"):
        _, dy = jax.jvp(lambda v: R.reduce(v, backend=b), (x,), (t,))
        np.testing.assert_allclose(float(dy), x.size, rtol=1e-2)
        _, dy = jax.jvp(
            lambda v: R.reduce(v, axis=-1, backend=b), (x.reshape(8, 32),),
            (t.reshape(8, 32),),
        )
        np.testing.assert_allclose(np.asarray(dy), 32.0, rtol=1e-2)
    h = jax.hessian(lambda v: R.reduce(v, kind="sumsq", backend="xla"))(x[:8])
    np.testing.assert_allclose(np.asarray(h), 2 * np.eye(8), atol=1e-5)


def test_moments_axis_is_one_fused_dot():
    """Both moments must ride a single stacked all-ones dot (one MXU pass),
    like the row_moments_mma path this replaced."""
    x = jnp.ones((4, 300), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda v: R.reduce(v, axis=-1, kind="moments", backend="mma_jnp")
    )(x)
    ndots = sum(
        1 for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "dot_general"
    )
    assert ndots == 1, jaxpr


def test_pallas_row_reductions_use_batched_dot_not_kernel_loop():
    """A process-wide Pallas override must not serialize row reductions into
    per-row kernel launches: rows always take the eq. (9) batched dot."""
    x = jnp.ones((16, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda v: R.reduce(v, axis=-1, backend="pallas_fused")
    )(x)
    prims = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
    assert "dot_general" in prims
    assert not any("scan" in p or "while" in p for p in prims), prims


@pytest.mark.parametrize("backend", BACKENDS)
def test_zero_size_inputs(backend):
    assert float(R.reduce(jnp.zeros((0,)), backend=backend)) == 0.0
    assert R.reduce(jnp.zeros((4, 0)), axis=-1, backend=backend).shape == (4,)
    assert R.reduce(jnp.zeros((0, 4)), axis=-1, backend=backend).shape == (0,)


# ------------------------------ plan control ---------------------------------


def test_plan_overrides_respected(rng):
    x = jnp.asarray(rng.randn(10_000).astype(np.float32))
    want = np.asarray(x).astype(np.float64).sum()
    for m in (4, 16, 128):
        got = float(R.reduce(x, backend="mma_jnp", m=m, compute_dtype="float32"))
        np.testing.assert_allclose(got, want, rtol=1e-5)
    # an explicit plan object is honoured verbatim and replace() adjusts it
    plan = R.plan_for(x.shape, x.dtype, backend="pallas_fused", tiles_per_block=2)
    assert plan.backend == "pallas_fused" and plan.tiles_per_block == 2
    got = float(R.reduce(x, plan=plan))
    np.testing.assert_allclose(got, want, atol=_tol(x))
    got32 = float(R.reduce(x, plan=plan, compute_dtype="float32", backend="mma_jnp"))
    np.testing.assert_allclose(got32, want, rtol=1e-5)


def test_plan_rejects_bad_fields():
    with pytest.raises(ValueError, match="m must be >= 2"):
        R.ReducePlan(m=1)
    with pytest.raises(ValueError, match="precision"):
        R.ReducePlan(precision="exactly")
    with pytest.raises(ValueError, match="num_cores"):
        R.ReducePlan(num_cores=0)


def test_plan_num_cores_resolution(rng):
    """Off-TPU (this container) the planner's lane default is 1 -- interpret
    mode runs lanes sequentially; pinning the knob must stick on both the
    planner and the public reduce() override path."""
    assert R.plan_for((100_000,), jnp.float32).num_cores == 1
    p = R.plan_for((100_000,), jnp.float32, backend="pallas_fused", num_cores=4)
    assert p.num_cores == 4
    # replace() path: a pinned plan adjusted per call
    x = jnp.asarray(rng.randn(70_000).astype(np.float32))
    want = np.asarray(x).astype(np.float64).sum()
    got = float(R.reduce(x, plan=p.replace(num_cores=2)))
    np.testing.assert_allclose(got, want, atol=_tol(x))
    got = float(R.reduce(x, backend="pallas_fused", num_cores=3))
    np.testing.assert_allclose(got, want, atol=_tol(x))


def test_autotune_sweeps_num_cores():
    """autotune's tuned winner carries its lane count back through auto
    plan_for (the knob is swept alongside tiles_per_block)."""
    R.plan_cache_clear(clear_tuned=True)
    try:
        best = R.autotune(
            (40_000,), jnp.float32, backends=("pallas_fused",),
            tiles_per_block_candidates=(2,), num_cores_candidates=(2,),
            repeats=1,
        )
        assert best.backend == "pallas_fused" and best.num_cores == 2
        tuned = R.plan_for((40_000,), jnp.float32, backend="auto")
        assert tuned.num_cores == 2
        # explicit overrides still beat the tuned entry
        pinned = R.plan_for((40_000,), jnp.float32, backend="auto", num_cores=1)
        assert pinned.num_cores == 1
    finally:
        R.plan_cache_clear(clear_tuned=True)


def test_planner_heuristics():
    # integers take the exact path
    assert R.plan_for((1000,), jnp.int32, backend="auto").backend == "xla"
    # batched row reductions take the eq. (9) single-dot path
    assert (
        R.plan_for((32, 4096), jnp.float32, axis=(1,), backend="auto").backend
        == "mma_jnp"
    )
    # tiny full reductions are not worth any MMA plumbing
    assert R.plan_for((8,), jnp.float32, backend="auto").backend == "xla"
    # exact-sensitive kinds multiply at f32
    assert (
        R.plan_for((4096,), jnp.float32, kind="norm2").compute_dtype
        == "float32"
    )
    assert R.plan_for((4096,), jnp.float32).compute_dtype == "bfloat16"


def test_default_backend_resolution(monkeypatch):
    monkeypatch.delenv(R.BACKEND_ENV, raising=False)
    R.set_default_backend(None)
    assert R.default_backend() == "auto"
    monkeypatch.setenv(R.BACKEND_ENV, "xla")
    assert R.default_backend() == "xla"
    assert R.backend_for_flags(True) == "xla"  # env overrides legacy flags
    R.set_default_backend("pallas_hier")
    assert R.default_backend() == "pallas_hier"
    assert R.backend_for_flags(False) == "pallas_hier"
    R.set_default_backend(None)
    monkeypatch.delenv(R.BACKEND_ENV)
    assert R.backend_for_flags(True) == "mma_jnp"
    assert R.backend_for_flags(True, use_pallas=True) == "pallas_fused"
    assert R.backend_for_flags(False) == "xla"


def test_custom_backend_registration(rng):
    class Doubling(R.Backend):
        name = "doubling"

        def sum_all(self, x, plan):
            return 2.0 * jnp.sum(x.astype(plan.accum_jnp))

        def sum_axis(self, x, plan):
            return 2.0 * jnp.sum(x.astype(plan.accum_jnp), -1)

    try:
        R.register_backend(Doubling())
        x = jnp.ones(10)
        assert float(R.reduce(x, backend="doubling")) == 20.0
        # PRE-PROLOGUE compatibility: a legacy subclass whose sum_all has no
        # prologue parameter keeps serving every kind -- the engine degrades
        # to the host-side map it always used (regression: the in-kernel
        # prologue rewire must not break third-party backends).
        assert float(R.reduce(x, kind="sumsq", backend="doubling")) == 20.0
        s, ss = R.reduce(x, kind="moments", backend="doubling")
        assert float(s) == 20.0 and float(ss) == 20.0
        np.testing.assert_allclose(
            float(R.reduce(x, kind="norm2", backend="doubling")),
            np.sqrt(20.0), rtol=1e-6,
        )
    finally:
        from repro.reduce import backends as B

        B._REGISTRY.pop("doubling", None)


# ------------------------------ precision policy -----------------------------


def test_kahan_policy_is_orthogonal_to_backend():
    """An adversarial combine (one 2^25-mass block, seven 1.0-mass blocks)
    loses the small partials in a naive f32 accumulation; the compensated
    combine must recover them on every backend."""
    block = R.ReducePlan().kahan_block
    x = np.empty(8 * block, np.float32)
    x[:block] = 8192.0      # block sum 2^25
    x[block:] = 2.0**-12    # each remaining block sums to exactly 1.0
    xj = jnp.asarray(x)
    exact = x.astype(np.float64).sum()
    for backend in BACKENDS:
        e_native = abs(
            float(R.reduce(xj, backend=backend, compute_dtype="float32"))
            - exact
        )
        e_kahan = abs(
            float(
                R.reduce(
                    xj,
                    backend=backend,
                    compute_dtype="float32",
                    precision="kahan",
                )
            )
            - exact
        )
        assert e_kahan < e_native, backend
        assert e_kahan <= 1.0, backend  # only the final f32 rounding remains


# ------------------------------ pytree reductions ----------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_reduce_tree_matches_oracle(backend, rng):
    tree = {
        "w": jnp.asarray(rng.randn(37, 129).astype(np.float32)),
        "b": [
            jnp.asarray(rng.randn(1000).astype(np.float32)),
            jnp.asarray(np.float32(rng.randn())),  # scalar leaf
        ],
    }
    leaves = [np.asarray(v).astype(np.float64) for v in jax.tree.leaves(tree)]
    want_sq = sum((v**2).sum() for v in leaves)
    want_sum = sum(v.sum() for v in leaves)
    np.testing.assert_allclose(
        float(R.reduce_tree(tree, "sumsq", backend=backend)), want_sq, rtol=1e-5
    )
    np.testing.assert_allclose(
        float(R.reduce_tree(tree, "norm2", backend=backend)),
        np.sqrt(want_sq),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        float(R.reduce_tree(tree, "sum", backend=backend)), want_sum, rtol=1e-4
    )
    assert float(R.reduce_tree({}, "sumsq", backend=backend)) == 0.0


def test_reduce_tree_is_differentiable(rng):
    tree = {"a": jnp.asarray(rng.randn(64).astype(np.float32))}
    g = jax.grad(lambda t: R.reduce_tree(t, "sumsq", backend="mma_jnp"))(tree)
    np.testing.assert_allclose(
        np.asarray(g["a"]), 2 * np.asarray(tree["a"]), rtol=1e-5
    )


# ------------------------------ segmented multi-reduce -----------------------


# Adversarial segment layouts: empty segment list handled separately; here:
# single-element segments, exact-tile and non-tile-multiple sizes, empty
# segments in the middle, a > m^2 segment, mixed ranks.
SEG_SHAPES = [(1,), (127,), (), (128 * 128,), (0,), (40, 33), (16390,), (3, 1, 5)]


def _seg_arrays(rng, dtype=np.float32):
    return [
        jnp.asarray(np.asarray(rng.randn(*s), np.float64).astype(dtype))
        for s in SEG_SHAPES
    ]


@pytest.mark.parametrize("backend", SEG_BACKENDS)
@pytest.mark.parametrize("kind", R.KINDS)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_reduce_many_matches_per_array_oracle(backend, kind, dtype, rng):
    """reduce_many == [reduce(a) for a] on the xla oracle, every backend x
    kind x dtype, across single-element / empty / ragged / huge segments."""
    arrs = _seg_arrays(rng, dtype)
    got = R.reduce_many(arrs, kind=kind, backend=backend)
    # reduce_many defines the mean of an empty segment as 0 (the oracle's
    # 0/0 is nan); everything else must match the per-array engine calls.
    want = [
        jnp.zeros(()) if kind == "mean" and a.size == 0
        else R.reduce(a, kind=kind, backend="xla")
        for a in arrs
    ]
    tol = max(_tol(a) for a in arrs)
    if kind == "moments":
        gs, gss = got
        np.testing.assert_allclose(
            np.asarray(gs, np.float64), [float(w[0]) for w in want], atol=tol
        )
        np.testing.assert_allclose(
            np.asarray(gss, np.float64), [float(w[1]) for w in want], atol=tol
        )
        return
    assert got.shape == (len(arrs),)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), [float(w) for w in want],
        atol=tol, rtol=2e-2,
    )


@pytest.mark.parametrize("backend", SEG_BACKENDS)
def test_reduce_many_empty_segment_list(backend):
    out = R.reduce_many([], backend=backend)
    assert out.shape == (0,)
    s, ss = R.reduce_many([], kind="moments", backend=backend)
    assert s.shape == (0,) and ss.shape == (0,)
    assert R.reduce_many([], axis=-1, backend=backend) == []


@pytest.mark.parametrize("backend", SEG_BACKENDS)
def test_reduce_many_int_segments_exact(backend, rng):
    arrs = [jnp.asarray(rng.randint(-9, 9, size=s), jnp.int32) for s in [(3,), (400,)]]
    got = R.reduce_many(arrs, backend=backend)
    want = [int(np.asarray(a).sum()) for a in arrs]
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


@pytest.mark.parametrize("backend", SEG_BACKENDS)
def test_reduce_many_grads_match_per_array_reduce(backend, rng):
    """Per-segment cotangents: d(sum_s w_s * out_s)/dx must equal the
    per-array reduce gradients on every backend and kind."""
    arrs = [
        jnp.asarray((rng.rand(*s) + 0.5).astype(np.float32))
        for s in [(5,), (300,), (4, 33)]
    ]
    w = jnp.asarray([1.0, -2.0, 0.5])
    for kind in ("sum", "mean", "sumsq", "norm2"):
        g_many = jax.grad(
            lambda a: jnp.sum(R.reduce_many(a, kind=kind, backend=backend) * w)
        )(arrs)
        g_loop = jax.grad(
            lambda a: sum(
                wi * R.reduce(ai, kind=kind, backend="xla")
                for wi, ai in zip(w, a)
            )
        )(arrs)
        for gm, gl in zip(g_many, g_loop):
            np.testing.assert_allclose(
                np.asarray(gm), np.asarray(gl), rtol=2e-3, atol=1e-5
            )


@pytest.mark.parametrize("backend", SEG_BACKENDS)
def test_reduce_many_rows_ragged_widths(backend, rng):
    """axis=-1: per-array row reductions with differing widths ride one
    width-padded pass and match the per-array oracle."""
    arrs = [
        jnp.asarray(rng.randn(4, 300).astype(np.float32)),
        jnp.asarray(rng.randn(2, 3, 70).astype(np.float32)),
        jnp.asarray(rng.randn(5).astype(np.float32)),
    ]
    for kind in ("sum", "mean", "sumsq", "norm2"):
        outs = R.reduce_many(arrs, kind=kind, axis=-1, backend=backend)
        for o, a in zip(outs, arrs):
            want = R.reduce(a, kind=kind, axis=-1, backend="xla")
            assert o.shape == want.shape
            np.testing.assert_allclose(
                np.asarray(o, np.float64), np.asarray(want, np.float64),
                atol=_tol(a), rtol=2e-2,
            )
    s_l, ss_l = R.reduce_many(arrs, kind="moments", axis=-1, backend=backend)
    for s_, ss_, a in zip(s_l, ss_l, arrs):
        ws, wss = R.reduce(a, kind="moments", axis=-1, backend="xla")
        np.testing.assert_allclose(np.asarray(s_), np.asarray(ws), atol=_tol(a))
        np.testing.assert_allclose(np.asarray(ss_), np.asarray(wss), atol=_tol(a))


@pytest.mark.parametrize("backend", SEG_BACKENDS)
def test_reduce_many_rows_zero_size_leaves(backend, rng):
    """Regression: a zero-width or zero-batch leaf mixed with live leaves
    must come back as the identity, not crash the packing."""
    arrs = [
        jnp.zeros((5, 0), jnp.float32),
        jnp.asarray(rng.randn(3, 4).astype(np.float32)),
        jnp.zeros((0, 7), jnp.float32),
    ]
    outs = R.reduce_many(arrs, kind="sum", axis=-1, backend=backend)
    assert outs[0].shape == (5,) and not outs[0].any()
    assert outs[2].shape == (0,)
    np.testing.assert_allclose(
        np.asarray(outs[1]), np.asarray(arrs[1], np.float64).sum(-1),
        atol=1e-2,
    )


def test_reduce_many_rows_gradient(rng):
    arrs = [
        jnp.asarray(rng.randn(4, 30).astype(np.float32)),
        jnp.asarray(rng.randn(2, 50).astype(np.float32)),
    ]

    def f(a):
        outs = R.reduce_many(a, kind="sumsq", axis=-1, backend="mma_jnp",
                             compute_dtype="float32")
        return sum(jnp.sum(o) for o in outs)

    g = jax.grad(f)(arrs)
    for gi, ai in zip(g, arrs):
        np.testing.assert_allclose(
            np.asarray(gi), 2 * np.asarray(ai), rtol=1e-4, atol=1e-5
        )


def test_reduce_many_rejects_bad_args(rng):
    with pytest.raises(ValueError, match="unknown kind"):
        R.reduce_many([jnp.ones(3)], kind="max")
    with pytest.raises(ValueError, match="axis"):
        R.reduce_many([jnp.ones(3)], axis=0)
    with pytest.raises(ValueError, match="ndim >= 1"):
        R.reduce_many([jnp.asarray(1.0)], axis=-1)


@pytest.mark.parametrize("backend", ("mma_jnp", "pallas_fused", "segmented"))
def test_reduce_many_jit_and_pytree_input(backend, rng):
    """reduce_many accepts an arbitrary pytree and works under jit."""
    tree = {
        "a": jnp.asarray(rng.randn(129).astype(np.float32)),
        "b": (jnp.asarray(rng.randn(2, 40).astype(np.float32)),),
    }
    got = jax.jit(lambda t: R.reduce_many(t, backend=backend))(tree)
    want = [np.asarray(v, np.float64).sum() for v in jax.tree.leaves(tree)]
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=1e-2)


def test_global_norm_is_single_pallas_launch():
    """Acceptance: one jitted AdamW global_norm over a multi-leaf pytree on
    the Pallas backends lowers to a SINGLE pallas_call -- the per-leaf work
    is eq. (9) dots; only the packed segmented pass hits the kernel. The
    striped grid must preserve the property at every lane count: the lanes
    live INSIDE the one launch, never one launch per lane."""
    from repro.optim import adamw

    tree = {
        "w": jnp.ones((4, 256)),
        "b": [jnp.ones((300,)), jnp.ones(())],
        "e": jnp.ones((2, 3, 64)),
    }
    for backend in ("pallas_fused", "pallas_hier"):
        for num_cores in (None, 1, 2, 4):
            jaxpr = jax.make_jaxpr(
                lambda g: R.reduce_tree(
                    g, "norm2", backend=backend, num_cores=num_cores
                )
            )(tree)
            assert str(jaxpr).count("pallas_call") == 1, (backend, num_cores)
        lowered = jax.jit(
            lambda g: adamw.global_norm(g, backend=backend)
        ).lower(tree).as_text()
        assert lowered  # lowering succeeds end-to-end
    # and the statistic itself is right, at any lane count
    want = np.sqrt(4 * 256 + 300 + 1 + 2 * 3 * 64)
    got = float(jax.jit(
        lambda g: adamw.global_norm(g, backend="pallas_fused")
    )(tree))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    got2 = float(jax.jit(
        lambda g: R.reduce_tree(g, "norm2", backend="pallas_fused", num_cores=2)
    )(tree))
    np.testing.assert_allclose(got2, want, rtol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_reduce_tree_mixed_shape_pytree(backend, rng):
    """Mixed-rank / zero-size / scalar leaves through the segmented path."""
    tree = {
        "w": jnp.asarray(rng.randn(37, 129).astype(np.float32)),
        "z": jnp.zeros((0, 7), jnp.float32),
        "s": jnp.asarray(np.float32(rng.randn())),
        "t3": jnp.asarray(rng.randn(2, 3, 40).astype(np.float32)),
    }
    leaves = [np.asarray(v, np.float64) for v in jax.tree.leaves(tree)]
    want = sum((v**2).sum() for v in leaves)
    np.testing.assert_allclose(
        float(R.reduce_tree(tree, "sumsq", backend=backend)), want, rtol=1e-4
    )


def test_segmented_backend_route_and_registration():
    """The planner marks multi-reduce problems for the registered
    "segmented" auto-route; the route resolves a concrete executor."""
    assert "segmented" in R.available_backends()
    plan = R.plan_for((100_000,), jnp.float32, segments=16, backend="auto")
    assert plan.backend == "segmented"
    # non-segmented problems never route there
    assert R.plan_for((100_000,), jnp.float32).backend != "segmented"
    # concrete resolution: ints -> xla; floats off-TPU -> mma_jnp
    assert R.segmented_backend_for(1000, jnp.int32, 128) == "xla"
    assert R.segmented_backend_for(100_000, jnp.float32, 128) in (
        "mma_jnp", "pallas_fused"
    )


# ------------------------------ plan cache + autotune -------------------------


def test_plan_for_is_memoized():
    """Same args -> the SAME plan object, served from cache (no recompute)."""
    R.plan_cache_clear()
    args = dict(kind="sumsq", axis=(1,), tiles_per_block=4)
    p1 = R.plan_for((64, 4096), jnp.float32, **args)
    before = R.plan_cache_info()
    p2 = R.plan_for((64, 4096), jnp.float32, **args)
    after = R.plan_cache_info()
    assert p1 is p2
    assert after.hits == before.hits + 1 and after.misses == before.misses
    # a changed process default must MISS, never serve the stale auto plan
    try:
        R.set_default_backend("xla")
        assert R.plan_for((64, 4096), jnp.float32, **args).backend == "xla"
    finally:
        R.set_default_backend(None)


def test_plan_for_forwards_kahan_block():
    """Regression: plan_for used to drop the kahan_block knob entirely."""
    assert R.plan_for((100,), jnp.float32, kahan_block=512).kahan_block == 512
    assert R.plan_for((100,), jnp.float32).kahan_block == 4096
    with pytest.raises(ValueError, match="kahan_block"):
        R.ReducePlan(kahan_block=0)
    # and the public reduce() override reaches the compensated combine
    x = jnp.ones(2048, jnp.float32)
    got = float(
        R.reduce(x, backend="mma_jnp", precision="kahan", kahan_block=256)
    )
    np.testing.assert_allclose(got, 2048.0, rtol=1e-6)


def test_autotune_axis_key_matches_reduce_normalization():
    """Regression: autotune(axis=-1) winners must land on the same cache key
    reduce()'s normalized (non-negative) axis looks up."""
    R.plan_cache_clear(clear_tuned=True)
    try:
        best = R.autotune(
            (8, 64), jnp.float32, kind="sumsq", axis=-1,
            backends=("xla",), repeats=1,
        )
        assert best.backend == "xla"
        for ax in (-1, (1,), 1):
            assert R.plan_for(
                (8, 64), jnp.float32, kind="sumsq", axis=ax, backend="auto"
            ).backend == "xla", ax
    finally:
        R.plan_cache_clear(clear_tuned=True)


def test_autotune_feeds_plan_cache(rng):
    """Opt-in autotune records its winner; later auto plan_for returns it."""
    shape, dt = (4096,), jnp.float32
    R.plan_cache_clear(clear_tuned=True)
    try:
        best = R.autotune(
            shape, dt, backends=("xla", "mma_jnp"), repeats=1
        )
        assert best.backend in ("xla", "mma_jnp")
        tuned = R.plan_for(shape, dt, backend="auto")
        assert tuned is best or tuned == best
        # explicit overrides still beat the tuned entry
        pinned = R.plan_for(shape, dt, backend="pallas_fused")
        assert pinned.backend == "pallas_fused"
    finally:
        R.plan_cache_clear(clear_tuned=True)


def test_autotune_records_a_candidate_that_raises():
    """A backend that raises loses the race loudly: a warning, and its
    plan and error stay readable through autotune_failures."""

    class Refused(R.Backend):
        name = "refused"

        def sum_all(self, x, plan):
            raise RuntimeError("refused by the compiler")

        def sum_axis(self, x, plan):
            raise RuntimeError("refused by the compiler")

    from repro.reduce import backends as B

    shape, dt = (4096,), jnp.float32
    R.plan_cache_clear(clear_tuned=True)
    try:
        R.register_backend(Refused())
        with pytest.warns(UserWarning, match="refused by the compiler"):
            best = R.autotune(shape, dt, backends=("xla", "refused"),
                              repeats=1)
        assert best.backend == "xla"
        (failed,) = R.autotune_failures(shape, dt)
        assert failed[0].backend == "refused"
        assert "refused by the compiler" in failed[1]
    finally:
        B._REGISTRY.pop("refused", None)
        R.plan_cache_clear(clear_tuned=True)
    assert R.autotune_failures(shape, dt) == ()


# ------------------------------ jit + legacy shims ---------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_reduce_is_jittable(backend, rng):
    x = jnp.asarray(rng.randn(3000).astype(np.float32))
    got = float(jax.jit(lambda y: R.reduce(y, backend=backend))(x))
    np.testing.assert_allclose(got, np.asarray(x).sum(), atol=_tol(x))


def test_legacy_core_names_warn_and_delegate(rng):
    import repro.core as C

    x = jnp.asarray(rng.randn(500).astype(np.float32))
    with pytest.deprecated_call():
        legacy = float(C.mma_sum(x, compute_dtype=jnp.float32))
    np.testing.assert_allclose(
        legacy,
        float(R.reduce(x, backend="mma_jnp", compute_dtype="float32")),
        rtol=1e-6,
    )
    with pytest.deprecated_call():
        legacy_norm = float(C.global_norm_sq_mma({"a": x}))
    np.testing.assert_allclose(
        legacy_norm,
        float(R.reduce_tree({"a": x}, "sumsq", backend="mma_jnp")),
        rtol=1e-6,
    )
    assert C.reduce is R  # repro.core re-exports the engine
