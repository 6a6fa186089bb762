"""Zero-copy native-dtype ingestion: proven on values, bits, jaxprs, bytes.

Four angles on the same contract:

  * tail-masking sweep -- every ragged n (incl. n < m^2, m^2 +- 1) x dtype
    {bf16, f16, f32} x num_cores {1, 2, 4} agrees with the jnp.sum oracle
    AND the updated op-for-op ``ref.py`` emulation (which models the masked
    loads as zero-padding);
  * bit-compatibility -- tile-multiple f32 inputs reproduce the PR-3
    (staged-ingestion) kernels bit-for-bit at every lane count, because a
    masked zero and a padded zero are the same zero;
  * staging-free jaxprs -- lowering ``reduce`` / ``reduce_many`` on bf16
    never materializes an n-sized convert/pad/concatenate outside the
    pallas_call (``repro.reduce.inspect``);
  * traffic -- ``cost_model.hbm_bytes`` equals the bytes actually crossing
    the lowered pallas_call boundary (asserted exactly for the fused and
    parts paths; upper bound for non-aligned segmented gathers, exact when
    aligned), and bf16 ingestion moves n*2 + O(c m^2).
"""

from _optional_hypothesis import hypothesis, st
import harness
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import reduce as R
from repro.core import cost_model
from repro.kernels import common
from repro.kernels.mma_reduce import kernel as K
from repro.kernels.mma_reduce import ops, ref
from repro.reduce import inspect as rinspect

M = common.MXU
GROUP = M * M
PALLAS_BACKENDS = ["pallas_fused", "pallas_hier"]

# the tail-masking sweep: below one tile, one tile +- 1, straddling block
# and lane boundaries, and a large ragged stream
TAIL_SIZES = [1, 7, 100, GROUP - 1, GROUP, GROUP + 1, 3 * GROUP - 5, 100_000]
DTYPES = [jnp.bfloat16, jnp.float16, jnp.float32]


def _tol(x64: np.ndarray, dt) -> float:
    # bf16 multipliers everywhere; bf16/f16 STORAGE also quantizes the data
    # (shared budget; see tests/harness.py)
    return harness.mass_tol(x64, harness.storage_rel(dt))


@pytest.mark.parametrize("num_cores", [1, 2, 4])
@pytest.mark.parametrize("dt", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("n", TAIL_SIZES)
def test_tail_masking_sweep(n, dt, num_cores, rng):
    """Ragged n x native dtype x lane count vs the jnp.sum oracle."""
    x = jnp.asarray(rng.randn(n), dt)
    x64 = np.asarray(x, np.float64)
    for backend in PALLAS_BACKENDS:
        got = float(R.reduce(x, backend=backend, num_cores=num_cores))
        assert abs(got - x64.sum()) <= _tol(x64, dt), (backend, n, dt)


@pytest.mark.parametrize("dt", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("n", [100, GROUP + 1, 50_000])
def test_tail_masking_matches_ref_emulation_bitwise(n, dt, rng):
    """The kernel's masked loads == the emulation's zero-pad model, to the
    BIT, for every native dtype (pins cast order: native -> compute directly,
    mask after cast)."""
    x = jnp.asarray(rng.randn(n), dt)
    for c in (1, 2, 4):
        got = np.asarray(K.reduce_fused(x.reshape(-1), num_cores=c))
        want = np.asarray(ref.fused_lanes_ref(x, num_cores=c))
        np.testing.assert_array_equal(
            got.view(np.uint32), want.view(np.uint32), err_msg=f"{n} {dt} {c}"
        )


@pytest.mark.parametrize("num_cores", [1, 2, 4])
def test_tile_multiple_f32_bit_identical_to_staged_kernels(num_cores, rng):
    """Acceptance: tile-multiple f32 inputs reproduce the PR-3 kernels
    bit-for-bit at every lane count. The PR-3 kernel consumed a host-padded
    f32 (T, m, m) stream; feeding the SAME bytes through the zero-copy path
    must produce identical partials (mask statically elided) and identical
    final bits through the combine."""
    n = 24 * GROUP  # tile- AND block-multiple: no masking anywhere
    x = jnp.asarray(rng.randn(n).astype(np.float32))
    got = np.asarray(K.reduce_fused(x, num_cores=num_cores))
    # the staged path == emulation (pinned since PR 3); transitively the
    # zero-copy kernel must equal it
    want = np.asarray(ref.fused_lanes_ref(x, num_cores=num_cores))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    # end-to-end bits through the public API as well
    a = np.asarray(
        R.reduce(x, backend="pallas_fused", num_cores=num_cores), np.float32
    )
    b = np.asarray(
        ops.combine_lane_partials(jnp.asarray(want)), np.float32
    )
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    # hierarchical mode: bit-identical to the eq. (13) emulation
    got_h = float(R.reduce(x, backend="pallas_hier", num_cores=num_cores))
    assert got_h == float(ref.hierarchy_ref(x))


def test_non_contiguous_and_transposed_views(rng):
    """Transposed / strided views reduce correctly on every Pallas path
    (XLA materializes the view once -- a layout copy, not ingestion
    staging; the kernel then streams it zero-copy)."""
    base = jnp.asarray(rng.randn(257, 129).astype(np.float32))
    views = [
        base.T,                      # transposed
        base[::2, ::3],              # strided slice
        jnp.swapaxes(base.reshape(257, 3, 43), 0, 2),  # permuted 3-d
    ]
    for v in views:
        want = float(np.asarray(v, np.float64).sum())
        for backend in PALLAS_BACKENDS:
            for c in (1, 2):
                got = float(R.reduce(v, backend=backend, num_cores=c))
                assert abs(got - want) <= 4e-3 * max(
                    np.abs(np.asarray(v, np.float64)).sum(), 1.0
                ), (backend, c, v.shape)
        many = np.asarray(R.reduce_many([v, v[:5]], backend="pallas_fused"))
        want2 = float(np.asarray(v[:5], np.float64).sum())
        for got, w, part in zip(many, (want, want2), (v, v[:5])):
            tol = 4e-3 * max(np.abs(np.asarray(part, np.float64)).sum(), 1.0)
            assert abs(float(got) - w) <= tol, (v.shape, got, w)


@pytest.mark.parametrize("backend", PALLAS_BACKENDS)
def test_reduce_staging_free_jaxpr(backend):
    """Satellite gate (mirrored in benchmarks/check_bench.py): no n-sized
    convert/pad/concatenate outside the pallas_call for bf16 ingestion."""
    x = jnp.zeros((300_000,), jnp.bfloat16)
    rinspect.assert_staging_free(
        lambda v: R.reduce(v, backend=backend), x
    )
    rinspect.assert_staging_free(
        lambda v: R.reduce(v, backend=backend, num_cores=2), x
    )


@pytest.mark.parametrize("backend", PALLAS_BACKENDS)
def test_reduce_many_staging_free_jaxpr(backend):
    arrs = [jnp.zeros((s,), jnp.bfloat16) for s in (70_000, 33, 20_000)]
    rinspect.assert_staging_free(
        lambda a: R.reduce_many(a, backend=backend), arrs
    )
    # f16 and f32 parts are native too
    arrs = [jnp.zeros((s,), jnp.float16) for s in (300, 5)]
    rinspect.assert_staging_free(
        lambda a: R.reduce_many(a, backend=backend), arrs
    )


def test_reduce_tree_no_partial_concatenation():
    """reduce_tree feeds per-leaf partials as separate operands: no
    concatenate at ANY size in the lowered program, and still one launch."""
    tree = {
        "w": jnp.ones((40, 256)),
        "b": [jnp.ones((3000,)), jnp.ones(())],
        "e": jnp.ones((0, 8)),
    }
    jaxpr = jax.make_jaxpr(
        lambda g: R.reduce_tree(g, "norm2", backend="pallas_fused")
    )(tree)
    assert not rinspect.staging_eqns(jaxpr, 2), rinspect.staging_eqns(jaxpr, 2)
    assert rinspect.count_pallas_calls(
        lambda g: R.reduce_tree(g, "norm2", backend="pallas_fused"), tree
    ) == 1


@pytest.mark.parametrize("dt,bs", [(jnp.bfloat16, 2), (jnp.float16, 2),
                                   (jnp.float32, 4)])
def test_fused_hbm_bytes_match_traced_geometry(dt, bs):
    """Acceptance: hbm_bytes(pallas_fused, bf16) == n*2 + O(c m^2), and the
    model's launch_io equals the bytes crossing the lowered pallas_call
    boundary EXACTLY, for every dtype x n x lane count."""
    for n in (5, GROUP, 100_000, 300_000):
        x = jnp.zeros((n,), dt)
        for c in (1, 2, 4):
            model = cost_model.fused_hbm_bytes(n, bs, num_cores=c)
            jaxpr = jax.make_jaxpr(
                lambda v, c=c: R.reduce(v, backend="pallas_fused", num_cores=c)
            )(x)
            assert rinspect.pallas_io_bytes(jaxpr) == model.launch_io, (n, dt, c)
            # n*itemsize + O(c m^2): the overhead term is exactly the
            # partial round-trip + result
            eff_c = cost_model.stripe_geometry(
                max(1, -(-n // GROUP)), 8, c
            )[1]
            assert model.total == n * bs + (2 * eff_c * GROUP * 4 + 4)
            # trace agrees with the model
            tr = []
            ops.mma_sum_pallas(x, num_cores=c, trace=tr)
            assert tr[0].hbm_bytes == model.total


def test_parts_hbm_bytes_match_traced_geometry():
    sizes = (70_000, 33, 20_000, 0)
    arrs = [jnp.zeros((s,), jnp.bfloat16) for s in sizes]
    model = cost_model.parts_hbm_bytes(
        sum(a.nbytes for a in arrs), segments=len(arrs)
    )
    jaxpr = jax.make_jaxpr(
        lambda a: R.reduce_many(a, backend="pallas_fused")
    )(arrs)
    assert rinspect.pallas_io_bytes(jaxpr) == model.launch_io
    tr = []
    ops.mma_sum_parts_pallas(arrs, trace=tr)
    assert tr[0].hbm_bytes == model.total


def test_segmented_hbm_bytes_aligned_exact_unaligned_bounded():
    plan = R.plan_for((5 * GROUP,), jnp.float32, backend="pallas_fused",
                      segments=2, num_cores=2)
    backend = R.get_backend("pallas_fused")
    for sizes, aligned in (
        ((2 * GROUP, 3 * GROUP), True),     # tile-aligned: exact equality
        ((20_000, 20_000), False),          # straddled boundary: re-fetch
    ):
        offsets = tuple(np.concatenate([[0], np.cumsum(sizes)]).tolist())
        flat = jnp.zeros((int(offsets[-1]),), jnp.float32)
        _, src, seg, lo, hi = ops.segment_cover_layout(offsets, GROUP)
        fetched = ops._cover_fetched_elems(src, flat.size, GROUP)
        model = cost_model.segmented_hbm_bytes(
            fetched, 4, segments=len(sizes), tiles=int(src.size), num_cores=2
        )
        jaxpr = jax.make_jaxpr(
            lambda v: backend.sum_segments(v, offsets, plan)
        )(flat)
        measured = rinspect.pallas_io_bytes(jaxpr)
        if aligned:
            assert measured == model.launch_io, (sizes, measured)
            assert fetched == int(flat.size)
        else:
            # the model charges the straddled block twice; the operand aval
            # counts it once -- measured is a strict lower bound
            assert measured < model.launch_io
            assert fetched > int(flat.size)
            # and the remainder overhead is bounded by one block per
            # non-aligned boundary
            assert fetched - int(flat.size) <= len(sizes) * GROUP


def test_staged_ingestion_costs_3x_on_bf16():
    """The motivating arithmetic: the old cast+pad staging moved ~3x the
    bytes of the zero-copy path for bf16 operands (2 + 4 + 4 per element vs
    2), and >2x even for f32."""
    n = 1 << 20
    zc = cost_model.hbm_bytes("fused", n, 2).total
    staged = cost_model.hbm_bytes("fused_staged", n, 2).total
    assert staged / zc > 3.0
    assert cost_model.hbm_bytes("fused_staged", n, 4).total \
        / cost_model.hbm_bytes("fused", n, 4).total > 2.0


def test_plan_hbm_bytes_threads_backend_paths():
    n = 1 << 20
    fused = R.plan_for((n,), jnp.bfloat16, backend="pallas_fused")
    assert fused.hbm_bytes(n, jnp.bfloat16).total == \
        cost_model.fused_hbm_bytes(n, 2, num_cores=fused.num_cores).total
    hier = fused.replace(backend="pallas_hier")
    assert hier.hbm_bytes(n, jnp.bfloat16).total == \
        cost_model.hier_hbm_bytes(n, 2).total
    # non-native dtypes pay the documented staged pre-cast
    assert fused.hbm_bytes(n, jnp.int32).total == \
        cost_model.staged_fused_hbm_bytes(
            n, 4, num_cores=fused.num_cores
        ).total
    # jnp-level backends: one native stream
    xla = fused.replace(backend="xla")
    assert xla.hbm_bytes(n, jnp.bfloat16).total == n * 2 + 4
    # segmented multi-reduce routes to the parts model on kernel backends
    assert fused.hbm_bytes(n, jnp.bfloat16, segments=8).total == \
        cost_model.parts_hbm_bytes(n * 2, segments=8).total


def test_ingest_fallback_dtypes_still_exact(rng):
    """f64 / int / bool inputs pre-cast to f32 (the documented staging
    fallback) and reduce exactly where exactness is representable."""
    xi = jnp.asarray(rng.randint(-50, 50, size=30_000), jnp.int32)
    for backend in PALLAS_BACKENDS:
        got = float(R.reduce(xi, backend=backend, compute_dtype="float32"))
        assert got == float(np.asarray(xi).sum())
    xb = jnp.asarray(rng.rand(1000) > 0.5)
    got = float(R.reduce(xb, backend="pallas_fused", compute_dtype="float32"))
    assert got == float(np.asarray(xb).sum())


def test_parts_kernel_fallback_past_threshold(rng):
    """More live parts than PARTS_KERNEL_MAX: the backend falls back to the
    packed stream (documented), stays correct, and still launches once."""
    nseg = ops.PARTS_KERNEL_MAX + 3
    arrs = [jnp.asarray(rng.randn(7).astype(np.float32)) for _ in range(nseg)]
    got = np.asarray(R.reduce_many(arrs, backend="pallas_fused"))
    want = np.asarray([np.asarray(a).sum() for a in arrs])
    tol = 4e-3 * np.maximum(
        np.asarray([np.abs(np.asarray(a)).sum() for a in arrs]), 1.0
    )
    assert np.all(np.abs(got - want) <= tol)
    assert rinspect.count_pallas_calls(
        lambda a: R.reduce_many(a, backend="pallas_fused"), arrs
    ) == 1


def test_segment_cover_layout_maps():
    """Cover-map algebra: aligned segments reuse the buffer's own blocks;
    straddled boundaries share a block with two masked windows."""
    tcounts, src, seg, lo, hi = ops.segment_cover_layout(
        (0, 5, 5, 40), 16
    )
    assert tcounts == (1, 0, 3)
    np.testing.assert_array_equal(src, [0, 0, 1, 2])
    np.testing.assert_array_equal(seg, [0, 2, 2, 2])
    np.testing.assert_array_equal(lo, [0, 5, 0, 0])
    np.testing.assert_array_equal(hi, [5, 16, 16, 8])
    # block 0 is fetched twice (segments 0 and 2 share it), masked disjointly
    assert ops._cover_fetched_elems(src, 40, 16) == 16 + 16 + 16 + 8


@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.given(
    n=st.integers(1, 60_000),
    seed=st.integers(0, 2**31 - 1),
    num_cores=st.sampled_from([1, 2, 4]),
    dt=st.sampled_from(["bfloat16", "float16", "float32"]),
)
def test_property_zero_copy_vs_oracle(n, seed, num_cores, dt):
    """Property sweep: ragged n x native dtype x lanes, zero-copy fused
    kernel vs the f64 oracle on the quantized data."""
    x = jnp.asarray(
        np.random.RandomState(seed).randn(n), jnp.dtype(dt)
    )
    x64 = np.asarray(x, np.float64)
    got = float(R.reduce(x, backend="pallas_fused", num_cores=num_cores))
    tol = (4e-3 if dt == "float32" else 1.6e-2) * max(np.abs(x64).sum(), 1e-3)
    assert abs(got - x64.sum()) <= tol


@pytest.mark.parametrize("shape,dt", [
    ((64, 256), jnp.float32),       # one whole block per step
    ((70, 256), jnp.float32),       # ragged last block
    ((40, 2048), jnp.bfloat16),     # two tiles per step, ragged rows
    ((2, 16, 1024), jnp.bfloat16),  # leading dims collapse into rows
])
def test_parts_2d_view_matches_flat_stream_bitwise(shape, dt, rng):
    """A leaf whose last dim tiles evenly streams through its (rows, C)
    view (no flat relayout on the chip); its blocks hold the flat stream's
    tiles in stream order, so every slot -- sum, census, the clip fork --
    is bit-identical to the same leaf streamed flat."""
    x = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dt)
    assert K.part_view(x).ndim == 2
    small = jnp.asarray(rng.randn(33).astype(np.float32))

    def stat(leaves):
        return ops.mma_sum_parts_pallas(
            leaves, prologue="square", total_chains=((), ("clip_coeff", 1.0)),
            census=True,
        )

    got = stat([x, small])
    want = stat([x.reshape(-1), small])
    harness.assert_bits_equal(got, want, str(shape))
    assert rinspect.pallas_io_bytes(jax.make_jaxpr(stat)([x, small])) == \
        rinspect.pallas_io_bytes(jax.make_jaxpr(stat)([x.reshape(-1), small]))
    rinspect.assert_staging_free(stat, [x, small])
