"""In-launch non-finite census + the guarded (bitwise-skip) optimizer.

The census is the guarded training loop's detector: the SAME launch that
computes the clipping statistic also counts every NaN/Inf gradient element
(per leaf and total, zero extra HBM input bytes on the kernel backends).
These tests pin:

  * count agreement across every registered backend, including NaN in the
    ragged masked-tail region and Inf, per-leaf layout and the total slot;
  * clean trees count zero AND the statistic is unchanged by asking;
  * gradients still flow through a census launch (counts are piecewise
    constant: their cotangents drop);
  * the direct kernel entry points (fused scalar, segmented);
  * the row census behind the serving guard (``guarded_logit_stat``):
    per-slot statistic and counts against numpy at slot counts up to and
    past PARTS_KERNEL_MAX on every backend class, with poison in tiles
    that straddle rows, and ONE launch whose kernel body does not grow
    with the slots;
  * the empty-"mean" NaN is DEFINED, not a fault: the census never counts
    a statistic, only input elements (satellite: mean empty-input pin);
  * legacy Backend subclasses that predate the census parameter degrade to
    the host reference census, same layout and values;
  * the guarded optimizer: unskipped steps BITWISE equal ``apply_updates``,
    poisoned/spiking steps pass params and state through BITWISE unchanged,
    the loss window only advances on accepted steps, and the whole jitted
    update lowers with no is_finite/select_n outside the kernel.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import optim
from repro import reduce as R
from repro.configs import TrainConfig
from repro.kernels.mma_reduce import ops
from repro.optim import adamw
from repro.reduce import backends as B
from repro.reduce import inspect as rinspect

BACKENDS = R.available_backends()


def _bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb)
    )


def _poisoned_tree():
    """Leaf order (tree_leaves, dict keys sorted): b[0], b[1], w.
    Expected per-leaf non-finite counts [2, 0, 1], total 3."""
    b0 = np.linspace(-1, 1, 3000).astype(np.float32)
    b0[7] = np.inf
    b0[2999] = np.nan  # last element: the ragged masked-tail region
    w = np.full((17, 33), 0.25, np.float32)
    w[3, 5] = np.nan
    return {
        "w": jnp.asarray(w, jnp.bfloat16),
        "b": [jnp.asarray(b0), jnp.ones((), jnp.float32)],
    }


@pytest.mark.parametrize("backend", BACKENDS)
def test_census_counts_agree_across_backends(backend):
    tree = _poisoned_tree()
    out, counts = R.reduce_tree(tree, "sumsq", backend=backend, census=True)
    assert counts.shape == (4,)
    np.testing.assert_array_equal(np.asarray(counts), [2.0, 0.0, 1.0, 3.0])
    assert not np.isfinite(float(out))  # the statistic itself is poisoned


@pytest.mark.parametrize("backend", BACKENDS)
def test_census_clean_tree_counts_zero_and_stat_unchanged(backend):
    tree = {
        "w": jnp.full((40, 256), 0.5, jnp.bfloat16),
        "b": [jnp.linspace(0, 1, 3001, dtype=jnp.float32), jnp.ones(())],
    }
    plain = R.reduce_tree(tree, "norm2", backend=backend)
    out, counts = R.reduce_tree(tree, "norm2", backend=backend, census=True)
    np.testing.assert_array_equal(np.asarray(counts), 0.0)
    assert float(out) == pytest.approx(float(plain), rel=1e-6)


def test_census_per_leaf_and_fork_layout():
    """return_per_leaf + epilogue fork + census from the ONE launch: the
    4-tuple unpack the fused-second-moment guarded optimizer relies on."""
    tree = _poisoned_tree()
    per_leaf, gnorm, clip, counts = adamw.global_norm_and_clip(
        tree, 1.0, backend="pallas_fused", return_per_leaf=True, census=True
    )
    assert per_leaf.shape == (3,)
    assert counts.shape == (4,)
    assert float(counts[-1]) == 3.0
    assert float(counts[-1]) == float(jnp.sum(counts[:-1]))


def test_census_empty_tree():
    out, counts = R.reduce_tree({}, "sumsq", census=True)
    assert float(out) == 0.0
    np.testing.assert_array_equal(np.asarray(counts), [0.0])


def test_census_empty_leaf_counts_zero():
    tree = {"a": jnp.zeros((0,), jnp.float32), "b": jnp.ones((5,))}
    out, counts = R.reduce_tree(tree, "sum", backend="xla", census=True)
    np.testing.assert_array_equal(np.asarray(counts), [0.0, 0.0, 0.0])
    assert float(out) == 5.0


def test_census_integer_leaves_count_zero():
    tree = {"i": jnp.arange(7, dtype=jnp.int32), "x": jnp.ones((9,))}
    _, counts = R.reduce_tree(tree, "sum", backend="mma_jnp", census=True)
    np.testing.assert_array_equal(np.asarray(counts), [0.0, 0.0, 0.0])


@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_hier"))
def test_grads_flow_through_census_launch(backend):
    tree = {"w": jnp.linspace(-1.0, 1.0, 600).reshape(3, 200)}

    def stat(t):
        out, _ = R.reduce_tree(t, "sumsq", backend=backend, census=True)
        return out

    g = jax.grad(stat)(tree)
    np.testing.assert_allclose(
        np.asarray(g["w"]), 2.0 * np.asarray(tree["w"]), rtol=2e-2, atol=1e-3
    )


def test_fused_scalar_census_entry():
    x = np.linspace(0, 2, 70_001).astype(np.float32)
    x[13] = np.nan
    x[70_000] = np.inf  # last element: lives in the masked ragged tail tile
    total, cnt = ops.mma_sum_pallas(jnp.asarray(x), census=True)
    assert float(cnt) == 2.0
    clean = np.nan_to_num(x, nan=0.0, posinf=0.0)
    total2, cnt2 = ops.mma_sum_pallas(jnp.asarray(clean), census=True)
    assert float(cnt2) == 0.0
    assert float(total2) == pytest.approx(float(np.sum(clean)), rel=2e-2)
    assert not np.isfinite(float(total))


def test_segmented_census_entry():
    n = 40_000
    x = np.ones(n, np.float32)
    offsets = (0, 1000, 1000, 25_000, n)  # segment 1 is empty
    x[0] = np.nan
    x[24_999] = np.inf
    out = ops.mma_sum_segments_pallas(jnp.asarray(x), offsets, census=True)
    nseg = len(offsets) - 1
    assert out.shape == (2 * nseg,)
    np.testing.assert_array_equal(np.asarray(out[nseg:]), [1.0, 0.0, 1.0, 0.0])
    # empty segment: additive identity, zero count
    assert float(out[1]) == 0.0


@pytest.mark.parametrize("num_cores", (1, 2, 3))
def test_segmented_offsets_zero_length_middle_segment(num_cores):
    """OFFSETS-path pin (the existing empty-segment coverage rode the parts
    path): a zero-length MIDDLE segment contributes exactly the additive
    identity 0.0 and a census count of 0, at every lane count -- its
    neighbours' totals are unaffected (no tile of the cover may leak across
    the empty boundary)."""
    n = 40_000
    x = np.ones(n, np.float32)
    offsets = (0, 1000, 1000, 25_000, n)  # segment 1 is empty, mid-buffer
    out = ops.mma_sum_segments_pallas(
        jnp.asarray(x), offsets, num_cores=num_cores, census=True
    )
    np.testing.assert_array_equal(
        np.asarray(out), [1000.0, 0.0, 24_000.0, 15_000.0, 0, 0, 0, 0]
    )
    # a poisoned neighbour never bleeds its count into the empty slot
    x[999] = np.nan   # last element of segment 0
    x[1000] = np.inf  # first element of segment 2
    out = ops.mma_sum_segments_pallas(
        jnp.asarray(x), offsets, num_cores=num_cores, census=True
    )
    np.testing.assert_array_equal(np.asarray(out[4:]), [1.0, 0.0, 1.0, 0.0])
    assert float(out[1]) == 0.0


@pytest.mark.parametrize("num_cores", (1, 2))
def test_segmented_empty_middle_segment_epilogue_lane_invariant(num_cores):
    """REGRESSION (found by the zero-length-middle sweep): an empty segment
    never flushes, so the IN-KERNEL epilogue (single-lane launches) never
    mapped its slot -- it came back as raw 0.0 while the multi-lane host
    path and the all-empty path return epilogue(0) (= 1.0 for clip_coeff:
    zero norm clips nothing). The epilogue'd result must not depend on
    num_cores."""
    x = jnp.ones((40_000,), jnp.float32)
    offsets = (0, 1000, 1000, 25_000, 40_000)
    chain = ("clip_coeff", 100.0, 1e-6)
    out = np.asarray(ops.mma_sum_segments_pallas(
        x, offsets, num_cores=num_cores, epilogue=chain,
        compute_dtype=jnp.float32,
    ))
    from repro.kernels import common as _c
    want_empty = float(_c.apply_epilogue(
        jnp.zeros(()), _c.normalize_epilogue(chain)
    ))
    assert out[1] == want_empty, (num_cores, out)
    # non-empty slots: min(1, 100/size), identical at every lane count
    np.testing.assert_allclose(
        out[[0, 2, 3]], [0.1, 100.0 / 24_000, 100.0 / 15_000], rtol=1e-5
    )


def test_mean_empty_is_defined_nan_not_a_fault():
    """Satellite pin: an empty full "mean" is 0/0 -> NaN BY DEFINITION
    (numpy semantics), not a faulted step -- and the census tallies INPUT
    elements only, so the empty mean never increments it."""
    r = R.reduce(jnp.zeros((0,), jnp.float32), kind="mean")
    assert np.isnan(float(r))
    with warnings.catch_warnings():  # numpy warns on its own 0/0 here
        warnings.simplefilter("ignore", RuntimeWarning)
        assert np.isnan(float(np.mean(np.zeros((0,), np.float32))))
    _, counts = R.reduce_tree(
        {"e": jnp.zeros((0,), jnp.float32)}, "sum", census=True
    )
    np.testing.assert_array_equal(np.asarray(counts), [0.0, 0.0])


def test_legacy_backend_without_census_param_degrades():
    """A Backend subclass written before the census parameter existed must
    still serve census=True: the dispatcher appends the host reference
    census to its row -- same layout, same values as the in-kernel count."""
    xla_cls = type(B.get_backend("xla"))

    class Legacy(xla_cls):
        name = "legacy-test"

        def sum_parts_total(self, parts, plan, prologue="identity",
                            total_chains=((),)):
            return super().sum_parts_total(parts, plan, prologue, total_chains)

    tree = _poisoned_tree()
    parts = jax.tree.leaves(tree)
    plan = R.plan_for(
        (sum(p.size for p in parts),), jnp.float32, kind="sum", backend="xla",
        segments=len(parts),
    )
    legacy = B.sum_parts_total_with_census(
        Legacy(), parts, plan, "identity", ((),), True
    )
    native = B.sum_parts_total_with_census(
        B.get_backend("xla"), parts, plan, "identity", ((),), True
    )
    assert legacy.shape == native.shape
    np.testing.assert_array_equal(  # census slots: [S+K:] with K=1
        np.asarray(legacy[-4:]), np.asarray(native[-4:])
    )
    np.testing.assert_array_equal(np.asarray(legacy[-4:]), [2.0, 0.0, 1.0, 3.0])


# ------------- row census: the serving guard's per-slot statistic -------------

# A vocabulary that is no multiple of the 16,384-element MMA tile, so tiles
# straddle rows: row 5 starts inside tile 0, row 6 inside tile 1.
ROW_VOCAB = 3000


def _poisoned_logits(slots):
    """(slots, 1, ROW_VOCAB) f32 logits with NaN at row 0's last element,
    Inf in row 5 inside the tile that straddles rows 4|5, NaN at row 6's
    first element inside the tile that straddles rows 5|6, and NaN at the
    last row's last element (rows that exist only). Returns the logits and
    each row's non-finite count."""
    rng = np.random.default_rng(slots)
    x = rng.standard_normal((slots, 1, ROW_VOCAB)).astype(np.float32)
    for row, col, v in ((0, ROW_VOCAB - 1, np.nan), (5, 100, np.inf),
                        (6, 0, np.nan), (slots - 1, ROW_VOCAB - 1, np.nan)):
        if row < slots:
            x[row, 0, col] = v
    return x, (~np.isfinite(x.reshape(slots, -1))).sum(-1)


@pytest.mark.parametrize("backend",
                         ("pallas_fused", "pallas_hier", "mma_jnp", "xla"))
@pytest.mark.parametrize("slots", (1, 48, 128, ops.PARTS_KERNEL_MAX + 1))
def test_guarded_logit_stat_matches_numpy(slots, backend):
    """Per-slot sumsq to f32 accuracy, per-slot NaN/Inf counts and their
    total exactly, at slot counts up to and past PARTS_KERNEL_MAX."""
    from repro.runtime.serving import guarded_logit_stat

    x, counts = _poisoned_logits(slots)
    stat, got = guarded_logit_stat(jnp.asarray(x), backend=backend)
    assert stat.shape == (slots,) and got.shape == (slots + 1,)
    np.testing.assert_array_equal(
        np.asarray(got), np.append(counts, counts.sum())
    )
    rows = x.reshape(slots, -1).astype(np.float64)
    clean = counts == 0
    np.testing.assert_allclose(
        np.asarray(stat)[clean], (rows[clean] ** 2).sum(-1), rtol=1e-5
    )
    assert not np.isfinite(np.asarray(stat)[~clean]).any()


@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_hier"))
def test_guarded_logit_stat_one_body_at_any_slot_count(backend):
    """One pallas_call of the segmented gather kernel, census-free around
    it, whose kernel jaxpr holds as many equations at 8 slots as at 128
    and 129: the compiled body does not grow with the slots (one operand
    and one unrolled branch per slot did)."""
    from repro.runtime.serving import guarded_logit_stat

    def stat(lg):
        return guarded_logit_stat(lg, backend=backend)

    body_eqns = set()
    for slots in (8, 128, ops.PARTS_KERNEL_MAX + 1):
        lg = jnp.ones((slots, 1, ROW_VOCAB), jnp.float32)
        calls = [
            eqn for eqn, inside in rinspect.iter_eqns(jax.make_jaxpr(stat)(lg))
            if not inside and eqn.primitive.name == "pallas_call"
        ]
        assert len(calls) == 1, (slots, len(calls))
        body = calls[0].params["jaxpr"]
        assert body.debug_info.func_src_info.startswith(
            "segmented_gather_kernel"), body.debug_info.func_src_info
        body_eqns.add(sum(1 for _ in rinspect.iter_eqns(body)))
        rinspect.assert_census_free(stat, lg)
    assert len(body_eqns) == 1, body_eqns


@pytest.mark.parametrize("backend", ("pallas_fused", "mma_jnp"))
@pytest.mark.parametrize("kind", ("sum", "mean", "norm2"))
def test_reduce_row_census_kinds(kind, backend):
    rng = np.random.default_rng(3)
    x = rng.uniform(0.5, 1.5, (7, ROW_VOCAB)).astype(np.float32)
    x[2, ROW_VOCAB - 1] = np.nan
    x[5, 100] = np.inf
    per, counts = R.reduce(jnp.asarray(x), axis=-1, kind=kind,
                           backend=backend, census=True)
    np.testing.assert_array_equal(
        np.asarray(counts), [0, 0, 1, 0, 0, 1, 0, 2]
    )
    xf = x.astype(np.float64)
    want = {"sum": xf.sum(-1), "mean": xf.mean(-1),
            "norm2": np.sqrt((xf * xf).sum(-1))}[kind]
    clean = [0, 1, 3, 4, 6]
    # sum/mean multiply at bf16 by the planner's default
    np.testing.assert_allclose(np.asarray(per)[clean], want[clean],
                               rtol=1e-2 if kind != "norm2" else 1e-5)


@pytest.mark.parametrize("shape", ((0, 5), (3, 0)))
def test_reduce_row_census_empty(shape):
    per, counts = R.reduce(jnp.zeros(shape, jnp.float32), axis=-1,
                           kind="sumsq", backend="pallas_fused", census=True)
    np.testing.assert_array_equal(np.asarray(per), np.zeros(shape[0]))
    np.testing.assert_array_equal(np.asarray(counts), np.zeros(shape[0] + 1))


def test_reduce_row_census_refusals():
    with pytest.raises(ValueError, match="rows of a 2-D"):
        R.reduce(jnp.ones((2, 3, 4)), axis=-1, kind="sumsq", census=True)
    with pytest.raises(ValueError, match="rows of a 2-D"):
        R.reduce(jnp.ones((3, 4)), axis=0, kind="sumsq", census=True)
    with pytest.raises(ValueError, match="mesh_axes"):
        R.reduce(jnp.ones((3, 4)), axis=-1, kind="sumsq", census=True,
                 mesh_axes="d")
    with pytest.raises(ValueError, match="moments"):
        R.reduce(jnp.ones((3, 4)), axis=-1, kind="moments", census=True)


@pytest.mark.parametrize("backend", ("pallas_fused", "xla"))
@pytest.mark.parametrize("kind", ("sum", "sumsq"))
def test_grads_flow_through_row_census(kind, backend):
    x = jnp.linspace(-1.0, 1.0, 3 * 700, dtype=jnp.float32).reshape(3, 700)
    w = jnp.asarray([1.0, 2.0, 3.0])

    def loss(v):
        per, _ = R.reduce(v, axis=-1, kind=kind, backend=backend, census=True)
        return jnp.dot(per, w)

    want = np.broadcast_to(np.asarray(w)[:, None], x.shape)
    if kind == "sumsq":
        want = 2.0 * np.asarray(x) * want
    np.testing.assert_allclose(np.asarray(jax.grad(loss)(x)), want,
                               rtol=1e-5, atol=1e-6)


# --------------------- guarded optimizer (bitwise skip) ---------------------


def _params():
    return {
        "w": jnp.full((40, 64), 0.5, jnp.float32),
        "b": jnp.linspace(-1, 1, 300, dtype=jnp.float32),
    }


@pytest.mark.parametrize("fused", (False, True))
def test_guarded_clean_step_bitwise_equals_unguarded(fused):
    tcfg = TrainConfig()
    params = _params()
    grads = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    state = optim.init_state(params, fused_second_moment=fused)
    ref_p, ref_s, _ = optim.apply_updates(
        params, grads, state, tcfg, reduce_backend="pallas_fused",
        fused_second_moment=fused,
    )
    new_p, new_s, guard, metrics = optim.guarded_apply_updates(
        params, grads, state, tcfg, loss=jnp.float32(1.0),
        guard=optim.init_guard_state(8), reduce_backend="pallas_fused",
        fused_second_moment=fused,
    )
    assert _bitwise_equal(new_p, ref_p)
    assert _bitwise_equal(new_s, ref_s)
    assert float(metrics["skipped"]) == 0.0
    assert float(metrics["nonfinite"]) == 0.0
    assert int(guard.skipped) == 0
    assert int(guard.filled) == 1  # accepted finite loss entered the window


@pytest.mark.parametrize("bad", (np.nan, np.inf, -np.inf))
def test_guarded_skips_poisoned_step_bitwise(bad):
    tcfg = TrainConfig()
    params = _params()
    g = np.full((40, 64), 0.01, np.float32)
    g[11, 3] = bad
    grads = {"w": jnp.asarray(g), "b": 0.01 * jnp.ones((300,), jnp.float32)}
    state = optim.init_state(params)
    guard0 = optim.init_guard_state(8)
    new_p, new_s, guard, metrics = optim.guarded_apply_updates(
        params, grads, state, tcfg, loss=jnp.float32(1.0), guard=guard0,
        reduce_backend="pallas_fused",
    )
    assert _bitwise_equal(new_p, params)
    assert _bitwise_equal(new_s, state)
    assert float(metrics["skipped"]) == 1.0
    assert float(metrics["nonfinite"]) == 1.0
    assert int(guard.skipped) == 1
    # skipped steps must not advance the loss window either
    assert _bitwise_equal(guard.window, guard0.window)
    assert int(guard.filled) == 0


def test_loss_spike_forces_skip_and_recovers():
    tcfg = TrainConfig()
    params = _params()
    grads = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    state = optim.init_state(params)
    guard = optim.init_guard_state(8)
    # fill the window with accepted ~1.0 losses (slight spread: a genuine
    # MAD so the detector has a scale)
    for i in range(8):
        params, state, guard, m = optim.guarded_apply_updates(
            params, grads, state, tcfg, loss=jnp.float32(1.0 + 0.01 * i),
            guard=guard, reduce_backend="pallas_fused",
        )
        assert float(m["skipped"]) == 0.0
    assert int(guard.filled) == 8
    p_before, s_before = params, state
    params, state, guard, m = optim.guarded_apply_updates(
        params, grads, state, tcfg, loss=jnp.float32(50.0), guard=guard,
        reduce_backend="pallas_fused",
    )
    assert float(m["spike"]) == 1.0 and float(m["skipped"]) == 1.0
    assert _bitwise_equal(params, p_before)
    assert _bitwise_equal(state, s_before)
    # a normal loss right after is accepted again (window never ate the 50)
    params, state, guard, m = optim.guarded_apply_updates(
        params, grads, state, tcfg, loss=jnp.float32(1.05), guard=guard,
        reduce_backend="pallas_fused",
    )
    assert float(m["skipped"]) == 0.0


def test_guarded_update_lowers_census_free_single_launch():
    tcfg = TrainConfig()
    params = _params()
    grads = jax.tree.map(jnp.ones_like, params)
    state = optim.init_state(params)
    guard = optim.init_guard_state(8)
    loss = jnp.zeros((), jnp.float32)

    def gstep(p, g, s, gu, lo):
        return optim.guarded_apply_updates(
            p, g, s, tcfg, loss=lo, guard=gu, reduce_backend="pallas_fused"
        )

    rinspect.assert_census_free(gstep, params, grads, state, guard, loss)
    n = rinspect.count_pallas_calls(gstep, params, grads, state, guard, loss)
    assert n == 1


def test_guarded_update_donation_safe():
    """donate params/state/guard: the bitwise blend writes into the donated
    buffers on skip and advance alike -- two chained calls must work."""
    tcfg = TrainConfig()
    params = _params()
    grads = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    state = optim.init_state(params)
    guard = optim.init_guard_state(4)

    donating = jax.jit(
        lambda p, g, s, gu, lo: optim.guarded_apply_updates(
            p, g, s, tcfg, loss=lo, guard=gu, reduce_backend="pallas_fused"
        ),
        donate_argnums=(0, 2, 3),
    )
    params, state, guard, m1 = donating(
        params, grads, state, guard, jnp.float32(1.0)
    )
    params, state, guard, m2 = donating(
        params, grads, state, guard, jnp.float32(1.1)
    )
    assert float(m1["skipped"]) == 0.0 and float(m2["skipped"]) == 0.0
    assert int(guard.filled) == 2
