"""Pallas TPU kernels for the paper's MMA reduction -- zero-copy ingestion.

Every kernel here consumes the caller's buffer DIRECTLY: a flat 1D BlockSpec
over the unpadded, native-dtype (bf16/f16/f32) input, with the (r, m, m) tile
view, the cast to ``compute_dtype``, and the tail handling all happening
in-VMEM. Nothing is reshaped-to-f32, padded, or concatenated host-side, so a
bf16 reduction moves n*2 bytes of HBM instead of the staged path's
read-n*2 + write-n*4 + read-n*4 (the reduction is memory-bound -- see
``cost_model.fused_hbm_bytes`` vs ``staged_fused_hbm_bytes``; the traces the
ops layer emits are asserted against those models). Tail tiles are masked
with ``broadcasted_iota`` against the true length -- a masked load of the
boundary block, not a padded copy -- which keeps tile-multiple f32 inputs
bit-identical to the pre-zero-copy kernels (the mask is statically elided
when the lane geometry needs none).

Every body takes a trace-time ELEMENTWISE PROLOGUE (identity / square /
abs, plus the paired (x, x^2) dual accumulator for moments), applied after
the compute-dtype cast and the tail mask, before the eq. (9) MMA -- so
sumsq/norm2/moments stream the caller's raw leaf exactly once (x^2 @ 1
instead of x @ 1; no host-side square pass, no f32 staging write). The
identity prologue adds no ops, keeping kind="sum" bit-identical to the
prologue-free kernels.

Four kernel bodies:

``tile_partials_kernel`` -- paper-faithful: every (m, m) tile of the flat
  block goes through the 2-MMA sequence of eqs. (9)-(12); each grid step
  emits its per-tile group sums. The hierarchy (eq. 13) is driven from
  ops.py by re-invoking the kernel on the (f32) partials, exactly like the
  paper's repeated kernel launches. Grid steps are independent, so the
  single grid dimension is ``parallel``.

``fused_accumulate_kernel`` -- beyond-paper optimization: a VMEM-resident
  f32 accumulator serves as the MMA C operand across grid steps
  (acc <- X_t @ 1 + acc), so each tile costs ONE MMA and no intermediate
  level touches HBM. Multi-core streaming: 2D ``(num_cores, blocks)`` grid
  with ``dimension_semantics=("parallel", "arbitrary")`` -- the flat element
  stream is STRIPED block-wise across lanes (lane c owns blocks c, c+C,
  ...), each lane carries its own accumulator and emits one (m, m) partial;
  ops.py collapses the lanes with a deterministic fixed-order f32 combine.
  ``kahan=True`` (``fused_kahan_kernel``) adds a second VMEM scratch row
  carrying a per-lane Kahan compensation, all inside the single launch.

``segmented_gather_kernel`` -- MANY independent reductions in ONE launch
  over ONE flat buffer, with NO stream staging: scalar-prefetched per-tile
  maps (source block, in-block [lo, hi) validity window, segment id,
  lane-aware flush flag) let the kernel gather every tile straight from the
  caller's buffer. Each segment is covered by the m^2-aligned blocks that
  overlap it -- tile-aligned segments stream every byte exactly once; a
  non-aligned boundary re-fetches (and masks) the one block it straddles,
  so the only overhead for arbitrary offsets is O(S) extra block fetches
  (the non-aligned remainder -- modeled by ``segmented_hbm_bytes``), never
  an n-sized copy. Striping is tile-granular (the gather fixes the block
  depth at one tile); flushes collapse per-(lane, segment) sub-partials
  exactly as before.

``parts_accumulate_kernel`` -- the multi-reduce behind ``reduce_many`` /
  ``reduce_tree``: S separate arrays enter the SAME launch as S operands
  (no packing concatenation). Each part is blocked over a shared
  sequential grid; part i's BlockSpec dwells on a clamped block index
  outside its run of grid steps -- Pallas only re-DMAs when a block index
  CHANGES, so the dwell costs no traffic -- and inside its run the
  statically-unrolled body masks the part's ragged tail against its true
  length and flushes its total at its last step. A multi-dimensional leaf
  streams through its (rows, last dim) view (``part_view``): on the TPU a
  flat view of it would be a relayout copy. The
  whole layout is trace-time static (sizes are static), so the kernel
  needs no scalar prefetch at all. Compile cost and VMEM residency are
  O(S) -- ops.py documents the fallback threshold.

Block geometry: each fused/hierarchical grid step stages
``tiles_per_block * m^2`` flat elements (8 * 16384 * 4B = 512 KiB f32, half
that for bf16) -- well inside the ~16 MiB VMEM budget and large enough to
hide DMA latency behind the systolic pipeline. The segmented gather and
parts kernels stage one m^2 block (64 KiB f32) per step by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cost_model
from repro.kernels import common

MXU = common.MXU


def _two_mma(tiles: jax.Array, compute_dtype) -> jax.Array:
    """(R, m, m) -> (R, 1) column of tile totals via the paper's two
    all-ones MMAs, f32 accumulate (a column, not a rank-1 vector: the chip
    blocks outputs in (8, 128) tiles)."""
    m = tiles.shape[-1]
    ones = common.ones_mma(m, compute_dtype)
    d = common.mma(
        tiles.astype(compute_dtype),
        jnp.broadcast_to(ones, tiles.shape),
        (((2,), (1,)), ((0,), (0,))),
    )
    d2 = common.mma(
        jnp.broadcast_to(ones, d.shape),
        d.astype(compute_dtype),
        (((2,), (1,)), ((0,), (0,))),
    )
    return d2[:, 0, :1]


def _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask):
    """Flat (r*m*m,) native block -> (r, m, m) compute-dtype tiles, in-VMEM.

    The three staged host-side ops this replaces -- reshape, astype, pad --
    all become register work: the 1D->2D view is a relayout (last dim = the
    128 lanes), the cast feeds the MXU at its native multiplier width, and
    the tail beyond the true length ``n`` is a ``broadcasted_iota`` mask
    (boundary blocks are CLIPPED reads of the caller's buffer; whatever the
    pad lanes hold is zeroed here, so garbage -- even NaN -- never reaches
    the accumulate). ``needs_mask`` is static: lane geometries that cover n
    exactly skip the mask entirely, keeping the tile-multiple fast path
    op-identical to the pre-zero-copy kernels."""
    rows = _flat_rows(x_ref[...], r * m, m)
    if needs_mask:
        row = jax.lax.broadcasted_iota(jnp.int32, (r * m, m), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (r * m, m), 1)
        rows = jnp.where(base + row * m + col < n, rows, jnp.zeros_like(rows))
    return rows.astype(compute_dtype).reshape(r, m, m)


def _flat_rows(flat, rows, m):
    """Flat native block -> (rows, m) f32, the lane-preserving 1D->2D view.

    The widening cast comes first: the TPU compiler relays a 1-D f32
    vector into (8, 128) tiles but refuses the packed 16-bit 1-D layout,
    and bf16/f16 -> f32 is exact, so the caller's later cast to the compute
    dtype sees the same values. The tail mask also runs at f32 (the v5e
    vector unit has no 16-bit arithmetic). A small operand arrives as its
    whole (1, n) row (``flat_operand``) and is zero-padded here, in VMEM,
    to the same row-major tile a flat block would give; a (rows, C) block
    of a parts operand's 2-D view (``part_view``) holds consecutive flat
    elements already and only splits its rows into 128 lanes."""
    f = flat.astype(jnp.float32)
    if f.ndim == 2 and f.shape[0] == 1:
        f = jnp.pad(f, ((0, 0), (0, SMALL_FLAT - f.shape[1])))
        return jnp.pad(f.reshape(SMALL_FLAT // m, m),
                       ((0, rows - SMALL_FLAT // m), (0, 0)))
    return f.reshape(rows, m)


# XLA lays a 1-D array of fewer elements than this out in tiles that the
# chip's kernel compiler does not accept for a flat block; such an operand
# enters the launch as a (1, n) row instead (a same-size reshape, no copy).
SMALL_FLAT = 1024


def flat_operand(flat: jax.Array, block: int, index_map):
    """``(operand, BlockSpec)`` for a flat native input streamed in
    ``block``-element blocks. A small input (always a single block) enters
    whole as a (1, n) row, which ``_flat_rows`` pads in VMEM."""
    n = flat.shape[0]
    if n < SMALL_FLAT:
        return flat.reshape(1, n), pl.BlockSpec((1, n), lambda *_: (0, 0))
    return flat, pl.BlockSpec((block,), index_map)


# Rows of a parts operand's 2-D block: the sublane tile of 16-bit types
# (and a multiple of f32's 8), so every ingest dtype blocks legally.
PART_ROWS = 16


def _part_rows(c: int) -> int:
    """Block rows for a (rows, c) view: whole tiles per block, >= PART_ROWS."""
    return max(PART_ROWS, MXU * MXU // c)


def part_view(x: jax.Array) -> jax.Array:
    """The array the parts kernel streams for one leaf.

    A flat 1-D view of a multi-dimensional array is a relayout copy on the
    TPU (its tiles span rows), so a leaf whose last dim ``c`` divides or is
    divided evenly into whole m^2 tiles (``_part_rows``) enters as its
    (rows, c) view instead: collapsing leading dims keeps the tiled layout,
    and each block's rows still hold consecutive flat elements, so the
    tiles are the flat stream's tiles. Other leaves, and leaves smaller
    than one block, enter flat."""
    group = MXU * MXU
    if x.ndim >= 2:
        c = x.shape[-1]
        if c % MXU == 0 and (
            group % c == 0 or c % (group // PART_ROWS) == 0
        ) and x.size >= _part_rows(c) * c:
            return x.reshape(-1, c)
    return x.reshape(-1)


def part_tiles_per_step(view: jax.Array) -> int:
    """m^2 tiles one grid step of the parts kernel reads from ``view``."""
    if view.ndim == 1:
        return 1
    c = view.shape[1]
    return _part_rows(c) * c // (MXU * MXU)


def _part_operand(view: jax.Array, start: int, steps: int):
    """``(operand, BlockSpec)`` for one parts operand: its block index
    dwells, clamped, outside the part's run of grid steps."""

    def step(j):
        return jnp.clip(j - start, 0, steps - 1)

    if view.ndim == 2:
        c = view.shape[1]
        return view, pl.BlockSpec((_part_rows(c), c), lambda j: (step(j), 0))
    return flat_operand(view, MXU * MXU, lambda j: (step(j),))


def _collapse(acc: jax.Array) -> jax.Array:
    """(m, m) f32 accumulator of row sums -> its (1, 1) total via the
    trailing f32 MMA (1 x acc), kept as a tile: the chip stores no scalar
    into VMEM, so a finished statistic stays a tile until ``_place`` writes
    it into a lane-dense output row."""
    ones = common.ones_mma(acc.shape[0], jnp.float32)
    return common.mma(ones, acc, (((1,), (0,)), ((), ())))[:1, :1]


def _place(shape, col, value, row):
    """``row`` broadcast to the (1, W) ``shape`` with lane ``col`` (static
    or a traced int) replaced by the (1, 1) ``value`` -- the vector form of
    a scalar slot store."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return jnp.where(lanes == col, value, row)


def tile_partials_kernel(
    x_ref, o_ref, *, n, r, m, compute_dtype, needs_mask, prologue="identity",
    epilogue=(),
):
    """One grid step: (r*m*m,) flat native elements -> (r, 1) partials.

    ``prologue`` is the trace-time elementwise map applied after the
    compute-dtype cast and tail mask, before the eq. (9) MMA -- so
    sumsq/norm2 stream the caller's raw leaf (x^2 @ 1 instead of x @ 1).
    ``prologue="moments"`` emits the paired (r, 2) partials (group sums of
    x in lane 0 AND x^2 in lane 1) from one pass over the tile block.

    ``epilogue`` (a normalized scalar chain) is only passed on the FINAL
    hierarchy level, where the launch covers a single tile (r == 1) and its
    lone partial IS the total -- the chain maps it in-kernel, so the
    hierarchy's consumer reads its statistic (sqrt / clip / scale) straight
    from the last launch with no host-side scalar eqns."""
    base = pl.program_id(0) * r * m * m
    tiles = _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask)
    if prologue == "moments":
        lanes = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
        o_ref[...] = jnp.where(
            lanes == 0,
            _two_mma(tiles, compute_dtype),
            _two_mma(tiles * tiles, compute_dtype),
        )
        return
    tiles = common.apply_prologue(tiles, prologue)
    o_ref[...] = common.apply_epilogue(_two_mma(tiles, compute_dtype), epilogue)


def _tile_row_sums(xv, compute_dtype):
    """(m, m) compute-dtype tile -> (m, m) f32 column-replicated row sums:
    the single-tile eq. (9) MMA (D = X @ 1) the gather/parts bodies fold
    into their VMEM accumulators."""
    m = xv.shape[-1]
    return common.mma(
        xv, common.ones_mma(m, compute_dtype), (((1,), (0,)), ((), ()))
    )


def _block_row_sums(tiles, compute_dtype):
    """(r, m, m) compute-dtype block -> (r, m, m) f32 column-replicated row
    sums: D = X @ 1. One batched MMA per block; the accumulate operand (C)
    is carried by the caller's VMEM accumulator, the MXU's native
    accumulation mode."""
    m = tiles.shape[-1]
    ones = common.ones_mma(m, compute_dtype)
    return common.mma(
        tiles, jnp.broadcast_to(ones, tiles.shape), (((2,), (1,)), ((0,), (0,)))
    )


def fused_accumulate_kernel(
    x_ref, o_ref, acc_ref, *maybe_cacc, n, r, c, m, compute_dtype,
    needs_mask, prologue="identity", epilogue=(), census=False,
):
    """Striped grid-accumulating reduction: one lane of the 2D grid.

    Grid is (num_cores, blocks_per_lane) with semantics ("parallel",
    "arbitrary"): dimension 0 indexes the lane (spread across cores, each
    with its own acc scratch instance), dimension 1 the lane's sequential
    block stream over the FLAT native input. Each step performs one batched
    MMA per tile block: acc += sum_t P(X_t) @ 1, where P is the trace-time
    elementwise ``prologue`` (identity adds no ops, keeping kind="sum"
    op-identical to the prologue-free kernel). On the lane's last step the
    raw (m, m) accumulator is emitted as this lane's partial; the
    deterministic collapse runs in ops.py (``combine_lane_partials``).

    ``epilogue`` (normalized scalar chain; single-lane grids only -- the
    launcher enforces c == 1) moves that collapse INTO the launch: the last
    step folds the accumulator with the trailing f32 MMA (1 x acc), maps
    the scalar through the chain, and emits a (1, 1) result -- the
    consumer's statistic leaves the kernel finished, with no host-side
    combine or scalar eqns.

    ``census=True`` adds the non-finite census, moments dual-accumulator
    style: a second VMEM scratch (``maybe_cacc``) folds the 0/1
    not-isfinite mask of every masked, pre-prologue block through the same
    ones-dot, and the emit widens -- the epilogue path emits (1, 2)
    [chained total, NaN/Inf count], the partials path (1, 2, m, m)
    [acc, census acc] -- at zero extra input bytes."""
    j = pl.program_id(1)
    cacc_ref = maybe_cacc[0] if census else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if census:
            cacc_ref[...] = jnp.zeros_like(cacc_ref)

    base = (j * c + pl.program_id(0)) * r * m * m
    tiles = _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask)
    if census:  # census BEFORE the prologue: count the raw masked values
        cacc_ref[...] += jnp.sum(
            _block_row_sums(_tile_nonfinite(tiles), CENSUS_DTYPE), axis=0
        )
    tiles = common.apply_prologue(tiles, prologue)
    d = _block_row_sums(tiles, compute_dtype)
    acc_ref[...] += jnp.sum(d, axis=0)  # batched-MMA partial fold (f32, VPU-add
    # of R tiles; R is small and this models the MXU's native C-accumulation)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        if epilogue:  # static: in-launch collapse + scalar chain
            total = common.apply_epilogue(_collapse(acc_ref[...]), epilogue)
            if census:
                total = _place(o_ref.shape, 1, _collapse(cacc_ref[...]), total)
            o_ref[...] = jnp.broadcast_to(total, o_ref.shape)
        elif census:
            o_ref[0, 0] = acc_ref[...]
            o_ref[0, 1] = cacc_ref[...]
        else:
            o_ref[0] = acc_ref[...]


def fused_moments_kernel(
    x_ref, o_ref, acc_ref, acc2_ref, *, n, r, c, m, compute_dtype, needs_mask
):
    """Fused lane under the moments prologue: the paired (x, x^2)
    DUAL-ACCUMULATOR. Each block is loaded once and feeds two batched MMAs
    (X_t @ 1 and X_t^2 @ 1) into separate VMEM accumulators, so one pass
    over the raw leaf yields both statistics LayerNorm-style consumers
    need; the lane emits the (2, m, m) pair and ops.py collapses each half
    with the same deterministic fixed-order combine."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    base = (j * c + pl.program_id(0)) * r * m * m
    tiles = _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask)
    acc_ref[...] += jnp.sum(_block_row_sums(tiles, compute_dtype), axis=0)
    acc2_ref[...] += jnp.sum(
        _block_row_sums(tiles * tiles, compute_dtype), axis=0
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0, 0] = acc_ref[...]
        o_ref[0, 1] = acc2_ref[...]


def _split_exact(tiles: jax.Array):
    """(r, m, m) f32 tiles -> (hi, lo), hi + lo == tiles exactly.

    Each row gets a power-of-two anchor 2^(e+1) above its largest
    magnitude. Adding and removing 3 * 2^(e+7) keeps every element inside
    one binade, so ``hi`` is the element rounded to a multiple of
    u = 2^(e-15): |hi| <= 2^16 u, and a row sum of m <= 256 such values
    stays within 2^24 u, which the MMA forms exactly in any order. ``lo``
    is the exact residue, |lo| <= u / 2. The anchor exponent is capped so
    the shift stays finite."""
    amax = jnp.max(jnp.abs(tiles), axis=-1, keepdims=True)
    bits = jnp.minimum(
        jax.lax.bitcast_convert_type(amax, jnp.int32) & 0x7F800000,
        0x7A000000,
    )
    anchor = jax.lax.bitcast_convert_type(bits + (1 << 23), jnp.float32)
    shift = anchor * 192.0
    hi = (tiles + shift) - shift
    return hi, tiles - hi


def fused_kahan_kernel(
    x_ref, o_ref, acc_ref, comp_ref, *, n, r, c, m, compute_dtype, needs_mask,
    prologue="identity",
):
    """Fused lane with a per-lane Kahan carry in a second scratch row.

    The MMA's own row sum of m f32 elements rounds at every step, and at
    useful sizes those in-tile roundings, not the cross-tile carry,
    dominate the error. So each tile is first split error-free
    (``_split_exact``) into a coarse part whose row sums the MMA forms
    EXACTLY and a residue below 2^-16 of the row's magnitude; both row-sum
    matrices are then two-summed into (acc, comp), so the lane
    accumulates O(1) rounding error instead of O(elements). Both matrices
    are emitted; the host-side combine folds acc and -comp in one
    compensated pass (Kahan's corrected sum is s - c). The elementwise
    prologues compose (a compensated in-kernel sumsq); "moments" does not
    (it needs its own accumulator pair -- the launcher rejects it).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    base = (j * c + pl.program_id(0)) * r * m * m
    tiles = _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask)
    tiles = common.apply_prologue(tiles, prologue).astype(jnp.float32)
    hi, lo = _split_exact(tiles)
    d_hi = _block_row_sums(hi, jnp.float32)
    d_lo = _block_row_sums(lo, jnp.float32)
    for t in range(r):  # static unroll: every tile is two compensated adds
        for d in (d_hi[t], d_lo[t]):
            y = d - comp_ref[...]
            s = acc_ref[...] + y
            comp_ref[...] = (s - acc_ref[...]) - y
            acc_ref[...] = s

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0, 0] = acc_ref[...]
        o_ref[0, 1] = comp_ref[...]


def reduce_tiles(
    flat: jax.Array,
    *,
    tiles_per_block: int = 8,
    compute_dtype=jnp.bfloat16,
    prologue: str = "identity",
    epilogue: tuple = (),
    interpret: bool | None = None,
) -> jax.Array:
    """Paper-faithful level: (n,) flat native elements -> (T,) partials
    (T = ceil(n / m^2)) via one pallas launch, zero-copy; under
    ``prologue="moments"`` the launch emits the (T, 2) partial PAIR (group
    sums of x and x^2 from one pass).

    Grid steps have no carried state, so the grid is declared ``parallel``:
    on a multi-core chip every core runs its own slice of the element
    stream concurrently -- the paper's "all tile MMAs in parallel"
    assumption. The ragged tail is a masked load of the boundary block.

    ``epilogue`` is legal only on a FINAL level -- a launch whose single
    partial is the total (t == 1) -- where the chain maps it in-kernel.
    """
    interpret = common.resolve_interpret(interpret)
    common.check_prologue(prologue)
    m = MXU
    n = flat.size
    t = max(1, common.ceil_div(n, m * m))
    if epilogue and (t != 1 or prologue == "moments"):
        raise ValueError(
            "reduce_tiles epilogue requires a final single-tile level "
            f"(t == 1, non-moments); got t={t}, prologue={prologue!r}"
        )
    r = max(1, min(tiles_per_block, t))
    blocks = common.ceil_div(t, r)
    tpad = blocks * r
    kernel = functools.partial(
        tile_partials_kernel,
        n=n,
        r=r,
        m=m,
        compute_dtype=compute_dtype,
        needs_mask=tpad * m * m != n,
        prologue=prologue,
        epilogue=epilogue,
    )
    if prologue == "moments":
        out_specs = pl.BlockSpec((r, 2), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((tpad, 2), jnp.float32)
    else:  # a (tpad, 1) column: the same bytes as (tpad,), legal blocks
        out_specs = pl.BlockSpec((r, 1), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((tpad, 1), jnp.float32)
    operand, in_spec = flat_operand(flat, r * m * m, lambda i: (i,))
    out = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[in_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=common.compiler_params(("parallel",)),
        interpret=interpret,
    )(operand)
    if prologue != "moments":
        out = out.reshape(tpad)
    return out[:t]


def _lane_geometry(t: int, tiles_per_block: int, num_cores: int):
    """Clamp + pad the (tiles, block, lanes) geometry for a striped stream.

    Returns ``(r, c, blocks_per_lane, tpad)``: block depth, effective lane
    count (never more lanes than blocks), per-lane sequential block count,
    and the padded tile-stream length ``r * c * blocks_per_lane``.
    Delegates to ``cost_model.stripe_geometry`` -- the kernels must run
    exactly the grid the cost model charges for.
    """
    return cost_model.stripe_geometry(t, tiles_per_block, num_cores)


def reduce_fused(
    flat: jax.Array,
    *,
    tiles_per_block: int = 8,
    num_cores: int = 1,
    compute_dtype=jnp.bfloat16,
    kahan: bool = False,
    prologue: str = "identity",
    epilogue: tuple = (),
    census: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Beyond-paper single-launch reduction: (n,) flat native elements ->
    (C, m, m) lane partials (``kahan=True`` or ``prologue="moments"``:
    (C, 2, m, m) -- compensation rows, resp. the dual-accumulator pair),
    zero-copy. The elementwise prologues (square/abs) map each element
    in-kernel after the cast and tail mask, so sumsq/norm2 stream the raw
    leaf once.

    ``census=True`` (non-kahan, non-moments -- both need the second scratch
    for themselves) rides the non-finite census on the same read: partials
    widen to (C, 2, m, m) with half 1 the census accumulator; with an
    in-kernel ``epilogue`` the launch emits (1, 2)
    [chained total, NaN/Inf count].

    The element stream is striped block-wise across ``num_cores`` lanes (the
    tail beyond n is a masked boundary load, never a padded copy); the
    caller collapses the partials with ``combine_lane_partials``
    (deterministic, fixed lane order).

    ``epilogue`` (single-lane, non-kahan, non-moments launches only -- the
    caller pre-computes the effective lane count via
    ``cost_model.stripe_geometry``) moves the collapse in-kernel: the
    launch returns the (1, 1) finished statistic instead of lane partials.
    """
    interpret = common.resolve_interpret(interpret)
    common.check_prologue(prologue)
    if kahan and prologue == "moments":
        raise ValueError(
            "prologue='moments' needs its own accumulator pair and does not "
            "compose with the in-kernel Kahan carry; run the moments pass "
            "at precision='native' (or compensate the two sums separately)"
        )
    m = MXU
    n = flat.size
    t = max(1, common.ceil_div(n, m * m))
    r, c, blocks_per_lane, tpad = _lane_geometry(t, tiles_per_block, num_cores)
    if epilogue and (c != 1 or kahan or prologue == "moments"):
        raise ValueError(
            "reduce_fused epilogue requires a single-lane, non-kahan, "
            f"non-moments launch; got c={c}, kahan={kahan}, "
            f"prologue={prologue!r}"
        )
    if census and (kahan or prologue == "moments"):
        raise ValueError(
            "reduce_fused census does not compose with kahan or "
            "prologue='moments' (both own the second scratch accumulator)"
        )
    needs_mask = tpad * m * m != n
    if kahan or prologue == "moments":
        if kahan:
            kernel = functools.partial(
                fused_kahan_kernel, n=n, r=r, c=c, m=m,
                compute_dtype=compute_dtype, needs_mask=needs_mask,
                prologue=prologue,
            )
        else:
            kernel = functools.partial(
                fused_moments_kernel, n=n, r=r, c=c, m=m,
                compute_dtype=compute_dtype, needs_mask=needs_mask,
            )
        out_shape = jax.ShapeDtypeStruct((c, 2, m, m), jnp.float32)
        out_specs = pl.BlockSpec((1, 2, m, m), lambda ci, j: (ci, 0, 0, 0))
        scratch = [
            common.vmem_scratch((m, m), jnp.float32),
            common.vmem_scratch((m, m), jnp.float32),
        ]
    else:
        kernel = functools.partial(
            fused_accumulate_kernel, n=n, r=r, c=c, m=m,
            compute_dtype=compute_dtype, needs_mask=needs_mask,
            prologue=prologue, epilogue=epilogue, census=census,
        )
        if epilogue:
            cols = 2 if census else 1
            out_shape = jax.ShapeDtypeStruct((1, cols), jnp.float32)
            out_specs = pl.BlockSpec((1, cols), lambda ci, j: (0, 0))
        elif census:
            out_shape = jax.ShapeDtypeStruct((c, 2, m, m), jnp.float32)
            out_specs = pl.BlockSpec(
                (1, 2, m, m), lambda ci, j: (ci, 0, 0, 0)
            )
        else:
            out_shape = jax.ShapeDtypeStruct((c, m, m), jnp.float32)
            out_specs = pl.BlockSpec((1, m, m), lambda ci, j: (ci, 0, 0))
        scratch = [common.vmem_scratch((m, m), jnp.float32)]
        if census:
            scratch.append(common.vmem_scratch((m, m), jnp.float32))
    # striping: lane ci owns blocks ci, ci+c, ci+2c, ... so concurrent
    # lanes stream CONTIGUOUS HBM at every step (coalesced across cores).
    operand, in_spec = flat_operand(
        flat, r * m * m, lambda ci, j, c=c: (j * c + ci,)
    )
    return pl.pallas_call(
        kernel,
        grid=(c, blocks_per_lane),
        in_specs=[in_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=common.compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(operand)


def segmented_gather_kernel(
    src_ref, seg_ref, flush_ref, lo_ref, hi_ref, x_ref, o_ref, acc_ref,
    *maybe_acc2, num_cores, m, compute_dtype, prologue="identity",
    epilogue=(),
    moments_offset=0,
    census_offset=0,
):
    """Striped segmented single-launch multi-reduce over ONE flat buffer.

    The five scalar-prefetched (SMEM) int32 maps cover the whole
    aligned-block tile stream, indexed by ORIGINAL stream position
    (``ops.segment_cover_layout`` builds them trace-time):

      ``src_ref``   -- which m^2-aligned block of the caller's flat buffer
                       this tile reads (consumed by the BlockSpec index map,
                       so the DMA itself does the gather);
      ``lo_ref`` / ``hi_ref`` -- the tile's validity window within its
                       block: elements with in-block position in [lo, hi)
                       belong to this tile's segment, the rest are masked
                       (this is how a non-aligned boundary shares its block
                       with the neighbouring segment);
      ``seg_ref``   -- tile -> segment id;
      ``flush_ref`` -- lane-aware flush flag (1 on the last tile of each
                       segment *within its lane's stripe* -- ops.py builds
                       it, so each lane flushes exactly once per segment it
                       touches).

    The grid is (num_cores, tiles_per_lane) with ("parallel", "arbitrary")
    semantics; lane ci streams tiles ci, ci+C, ... sequentially, its
    accumulator carries across its own tiles only, and each flush collapses
    it with one trailing f32 MMA into the lane's row of the (num_cores, S)
    sub-partial output. Trailing pad tiles carry lo == hi == 0 (fully
    masked) and no flush bit: they add exact zeros to an accumulator nobody
    reads again.

    ``prologue`` maps each masked tile before the accumulate (identity adds
    no ops); ``prologue="moments"`` carries the (x, x^2) dual accumulator
    (``maybe_acc2`` holds the second scratch) and each flush writes the
    segment's sum to column ``seg`` and its sum of squares to column
    ``seg + moments_offset`` of the widened (C, 2S) output.

    ``epilogue`` (normalized scalar chain; single-lane launches only -- each
    segment then flushes exactly once, so its flushed value IS its total)
    maps every flushed per-segment scalar in-kernel before the write.

    ``census_offset`` (> 0 enables; does not compose with "moments" -- the
    launcher rejects that) rides the non-finite census on the same gather:
    a second scratch (the trailing ``maybe_acc2`` ref) folds the 0/1
    not-isfinite mask of each windowed tile, and every flush writes the
    segment's NaN/Inf count to column ``seg + census_offset`` of the
    widened (C, 2S) output. The [lo, hi) window masks shared boundary
    blocks to exact zeros, so each element is counted exactly once.
    """
    j = pl.program_id(1)
    cacc_ref = maybe_acc2[-1] if census_offset else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        o_ref[...] = jnp.zeros_like(o_ref)
        if prologue == "moments":
            maybe_acc2[0][...] = jnp.zeros_like(maybe_acc2[0])
        if census_offset:
            cacc_ref[...] = jnp.zeros_like(cacc_ref)

    t = j * num_cores + pl.program_id(0)  # original stream position
    xv = _flat_rows(x_ref[...], m, m)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    lin = row * m + col
    mask = (lin >= lo_ref[t]) & (lin < hi_ref[t])
    xv = jnp.where(mask, xv, jnp.zeros_like(xv)).astype(compute_dtype)
    if census_offset:  # census BEFORE the prologue: count raw masked values
        cacc_ref[...] += _tile_row_sums(_tile_nonfinite(xv), CENSUS_DTYPE)
    if prologue == "moments":
        acc_ref[...] += _tile_row_sums(xv, compute_dtype)
        maybe_acc2[0][...] += _tile_row_sums(xv * xv, compute_dtype)
    else:
        acc_ref[...] += _tile_row_sums(
            common.apply_prologue(xv, prologue), compute_dtype
        )

    @pl.when(flush_ref[t] != 0)
    def _flush():
        # one trailing MMA collapses the accumulated row-sums: 1 x acc;
        # each slot lands in the lane-dense (1, W) row by a lane select.
        seg = seg_ref[t]
        row_out = o_ref[0]
        row_out = _place(
            row_out.shape, seg,
            common.apply_epilogue(_collapse(acc_ref[...]), epilogue), row_out,
        )
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if prologue == "moments":
            row_out = _place(
                row_out.shape, seg + moments_offset,
                _collapse(maybe_acc2[0][...]), row_out,
            )
            maybe_acc2[0][...] = jnp.zeros_like(maybe_acc2[0])
        if census_offset:
            row_out = _place(
                row_out.shape, seg + census_offset, _collapse(cacc_ref[...]),
                row_out,
            )
            cacc_ref[...] = jnp.zeros_like(cacc_ref)
        o_ref[0] = row_out


def reduce_segments(
    flat: jax.Array,
    src_blk: jax.Array,
    seg_of: jax.Array,
    flush: jax.Array,
    lo_in: jax.Array,
    hi_in: jax.Array,
    num_segments: int,
    *,
    num_cores: int = 1,
    compute_dtype=jnp.bfloat16,
    prologue: str = "identity",
    epilogue: tuple = (),
    census: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-launch segmented gather reduction: (n,) flat native buffer +
    (T,) cover maps -> (C, S) lane sub-partials; the caller sums lanes
    (``combine_segment_partials``). ``prologue="moments"`` widens the
    output to (C, 2S): columns [0, S) carry the per-segment sums, columns
    [S, 2S) the sums of squares, both from one pass over the buffer.
    ``census=True`` (non-moments) widens the same way, columns [S, 2S)
    instead carrying each segment's NON-FINITE element count (lanes add).

    The maps are trace-time constants (segment offsets are static) built by
    ``ops.segment_cover_layout`` / ``ops.lane_flush_map`` (``flush`` must be
    LANE-AWARE for ``num_cores > 1``). Striping is tile-granular -- the
    gather fixes the block depth at one tile, so ``tiles_per_block`` plays
    no role on this path -- and the maps are padded here to whole lanes
    (src 0, lo == hi == 0: fully-masked no-op tiles).

    ``epilogue`` (single-lane, non-moments launches only: every segment
    then flushes exactly once, so its flush IS its total) maps each
    per-segment scalar in-kernel before the slot write.
    """
    interpret = common.resolve_interpret(interpret)
    common.check_prologue(prologue)
    m = MXU
    t = int(src_blk.shape[0])
    _, c, tiles_per_lane, tpad = _lane_geometry(t, 1, num_cores)
    if epilogue and (c != 1 or prologue == "moments"):
        raise ValueError(
            "reduce_segments epilogue requires a single-lane, non-moments "
            f"launch; got c={c}, prologue={prologue!r}"
        )
    if census and prologue == "moments":
        raise ValueError(
            "reduce_segments census does not compose with prologue="
            "'moments' (both widen the output to (C, 2S))"
        )

    def _pad_map(a):
        return common.pad_to(jnp.asarray(a, jnp.int32), tpad, axis=0)

    src_blk, seg_of, flush, lo_in, hi_in = map(
        _pad_map, (src_blk, seg_of, flush, lo_in, hi_in)
    )
    dual = prologue == "moments"
    out_cols = (2 * num_segments) if (dual or census) else num_segments
    scratch = [common.vmem_scratch((m, m), jnp.float32)]
    if dual or census:
        scratch.append(common.vmem_scratch((m, m), jnp.float32))
    kernel = functools.partial(
        segmented_gather_kernel, num_cores=c, m=m,
        compute_dtype=compute_dtype, prologue=prologue, epilogue=epilogue,
        moments_offset=num_segments if dual else 0,
        census_offset=num_segments if census else 0,
    )
    # the gather: the DMA source block is read from the prefetched cover
    # map, straight off the caller's buffer.
    operand, in_spec = flat_operand(
        flat, m * m, lambda ci, j, src_ref, *_, c=c: (src_ref[j * c + ci],)
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(c, tiles_per_lane),
            in_specs=[in_spec],
            # (c, 1, W): each lane's row is a whole (1, W) block at any c
            out_specs=pl.BlockSpec(
                (1, 1, out_cols), lambda ci, j, *_: (ci, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((c, 1, out_cols), jnp.float32),
        compiler_params=common.compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(
        src_blk,
        seg_of,
        flush,
        lo_in,
        hi_in,
        operand,
    ).reshape(c, out_cols)


# The census mask's MMA width: 0/1 is exact at any width, so it takes the
# single native MXU pass whatever the statistic's compute dtype.
CENSUS_DTYPE = jnp.bfloat16


def _tile_nonfinite(xv):
    """(m, m) tile -> (m, m) 0/1 non-finite mask, ready for the ones-dot
    fold: the finiteness CENSUS is just another masked reduction riding the
    same tile (NaN/Inf -> 1, everything else -> 0; masked pad lanes are
    exact zeros, hence finite, hence never counted). The MMA accumulates
    the mask in f32, so the count is exact up to 2^24 elements per slot.
    The test runs at f32 (the v5e vector unit has no 16-bit compare; the
    widening is exact)."""
    return (~jnp.isfinite(xv.astype(jnp.float32))).astype(CENSUS_DTYPE)


def parts_accumulate_kernel(
    *refs, layout, m, compute_dtype, prologues=None, moments_offset=0,
    slot_epilogue=(), total_chains=None, chain_offset=None, census_offset=None,
):
    """S separate arrays -> (S,) per-segment totals, one launch.

    ``layout`` is the static schedule: one ``(seg, start, steps, size, k)``
    tuple per live part, assigning it the grid-step run
    [start, start + steps) of the shared sequential grid, k m^2 tiles per
    step (k > 1 for a 2-D ``part_view`` operand). The body is statically
    unrolled over parts; at any grid step exactly one ``pl.when`` fires
    (runs are disjoint), the active part's tiles are masked against its
    true ``size`` and folded, one tile after another in stream order, into
    the shared accumulator, and the part's last step flushes its total with
    one trailing f32 MMA into the (static) output slot. Empty parts never
    enter the layout -- the j == 0 init leaves their slots at the additive
    identity. Everything the kernel branches on is trace-time static, so
    there is no scalar prefetch; the cost is O(S) compiled branches
    (ops.py bounds S).

    ``prologues`` (one name per layout entry; None = all identity) selects
    each part's in-kernel elementwise map. A part with prologue "moments"
    accumulates the (x, x^2) pair -- the second scratch accumulator is the
    trailing ref -- and flushes its sum to slot ``seg`` and its sum of
    squares to slot ``seg + moments_offset``, so both statistics of every
    leaf ride the SAME single read of its buffer.

    ``slot_epilogue`` (normalized scalar chain) maps EVERY flushed per-part
    total before its slot write. ``total_chains`` (tuple of K chains) adds
    the TREE total: a (1, 1) f32 scratch (the trailing ref) accumulates the
    raw flushed totals across the sequential grid -- part flush order is
    static and deterministic -- and the LAST part's flush emits chain k of
    the running cross-part total into slot ``num_slots + k``, so a whole
    tree's norm AND its clip coefficient leave this one launch finished
    (``total_chains`` composes with ``slot_epilogue`` on the per-slot
    writes but not with "moments" parts -- the launcher rejects that).

    ``census_offset`` (an output-slot index; None disables) adds the
    NON-FINITE CENSUS: a second (m, m) accumulator folds the 0/1
    not-isfinite mask of every masked tile through the SAME ones-dot MMA,
    each part's flush writes its count to slot ``census_offset + seg``, a
    (1, 1) scratch carries the running cross-part count, and the last part's
    flush emits it into the final slot -- per-leaf and total NaN/Inf counts
    with ZERO extra input bytes (the mask is computed on the tile already in
    registers). Pad lanes are masked to exact zeros before the mask, so the
    ragged tail never under- or over-counts. Census does not compose with
    "moments" parts (the launcher rejects that); ``chain_offset`` then pins
    the total-chain slots explicitly (census slots sit after them)."""
    if prologues is None:
        prologues = ("identity",) * len(layout)
    dual = "moments" in prologues
    part_refs = refs[: len(layout)]
    rest = refs[len(layout):]
    o_ref, acc_ref = rest[0], rest[1]
    idx = 2
    acc2_ref = None
    if dual:
        acc2_ref = rest[idx]
        idx += 1
    tot_ref = None
    if total_chains:
        tot_ref = rest[idx]
        idx += 1
    cacc_ref = ctot_ref = None
    if census_offset is not None:
        cacc_ref, ctot_ref = rest[idx], rest[idx + 1]
    n_chains = len(total_chains) if total_chains else 0
    num_slots = chain_offset if chain_offset is not None else (
        o_ref.shape[-1] - n_chains
    )
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        o_ref[...] = jnp.zeros_like(o_ref)
        if dual:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)
        if total_chains:
            tot_ref[...] = jnp.zeros_like(tot_ref)
        if census_offset is not None:
            cacc_ref[...] = jnp.zeros_like(cacc_ref)
            ctot_ref[...] = jnp.zeros_like(ctot_ref)

    for ref, (seg, start, steps, size, k), pro in zip(
        part_refs, layout, prologues
    ):

        @pl.when((j >= start) & (j < start + steps))
        def _accumulate(
            ref=ref, seg=seg, start=start, steps=steps, size=size, k=k,
            pro=pro,
        ):
            rows = _flat_rows(ref[...], k * m, m)
            if size % (k * m * m):  # static: whole-block parts skip the mask
                valid = size - (j - start) * k * m * m  # THIS part's tail
                row = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
                rows = jnp.where(row * m + col < valid, rows,
                                 jnp.zeros_like(rows))
            for q in range(k):  # tile by tile, in stream order
                xv = rows[q * m:(q + 1) * m].astype(compute_dtype)
                if census_offset is not None:
                    # census BEFORE the prologue: count the raw (masked)
                    # values, not their squares -- one extra ones-dot MMA
                    cacc_ref[...] += _tile_row_sums(
                        _tile_nonfinite(xv), CENSUS_DTYPE
                    )
                if pro == "moments":
                    acc_ref[...] += _tile_row_sums(xv, compute_dtype)
                    acc2_ref[...] += _tile_row_sums(xv * xv, compute_dtype)
                else:
                    acc_ref[...] += _tile_row_sums(
                        common.apply_prologue(xv, pro), compute_dtype
                    )

            @pl.when(j == start + steps - 1)
            def _flush():
                # every slot write is a lane select into the (1, W) row
                row_out = o_ref[...]
                total = _collapse(acc_ref[...])
                row_out = _place(
                    row_out.shape, seg,
                    common.apply_epilogue(total, slot_epilogue), row_out,
                )
                acc_ref[...] = jnp.zeros_like(acc_ref)
                if pro == "moments":
                    row_out = _place(
                        row_out.shape, seg + moments_offset,
                        common.apply_epilogue(_collapse(acc2_ref[...]),
                                              slot_epilogue),
                        row_out,
                    )
                    acc2_ref[...] = jnp.zeros_like(acc2_ref)
                if total_chains:
                    # sequential cross-part fold of the RAW totals (f32,
                    # static part order -> deterministic, same contraction
                    # order as the host-side jnp.sum over the (S,) slots).
                    tot_ref[...] += total
                    # layout is start-ordered, so the last layout entry
                    # flushes on the final grid step: emit the chains there.
                    if seg == layout[-1][0]:
                        for k, chain in enumerate(total_chains):
                            row_out = _place(
                                row_out.shape, num_slots + k,
                                common.apply_epilogue(tot_ref[...], chain),
                                row_out,
                            )
                if census_offset is not None:
                    ctile = _collapse(cacc_ref[...])
                    row_out = _place(
                        row_out.shape, census_offset + seg, ctile, row_out
                    )
                    cacc_ref[...] = jnp.zeros_like(cacc_ref)
                    ctot_ref[...] += ctile
                    if seg == layout[-1][0]:
                        row_out = _place(
                            row_out.shape, row_out.shape[-1] - 1,
                            ctot_ref[...], row_out,
                        )
                o_ref[...] = row_out


def reduce_parts(
    parts: list[jax.Array],
    layout: tuple[tuple[int, int, int, int], ...],
    num_segments: int,
    *,
    compute_dtype=jnp.bfloat16,
    prologues: tuple[str, ...] | None = None,
    moments_offset: int = 0,
    slot_epilogue: tuple = (),
    total_chains: tuple | None = None,
    census: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """One launch over S separate native-dtype flat arrays -> (S,) totals
    (``num_segments`` counts OUTPUT slots: a moments part owns two).

    ``parts`` holds only the LIVE (non-empty) arrays, in ``layout`` order
    (``ops.parts_layout`` builds both; ``prologues`` aligns with it). Each
    part's BlockSpec clamps its block index into its own tile run, so
    outside the run the spec dwells on an already-resident block (Pallas
    re-DMAs only on index change -- the dwell moves no bytes) and the total
    traffic is exactly the parts' native bytes plus the output row --
    including under "moments", where both statistics ride one read.

    ``slot_epilogue`` maps every flushed per-part total in-kernel;
    ``total_chains`` (tuple of K normalized chains) widens the output to
    (num_segments + K,), slot ``num_segments + k`` carrying chain k of the
    cross-part RAW total -- the reduce_tree consumer's norm/clip, fully
    in-kernel at ANY core count (this grid is sequential and ignores
    ``num_cores`` altogether). Neither composes with "moments" parts.

    ``census=True`` widens the output further to
    (num_segments + K + num_segments + 1,): slot
    ``num_segments + K + seg`` carries part ``seg``'s NON-FINITE element
    count and the final slot the total across all parts -- the guarded
    optimizer's NaN/Inf detector, riding the same single read of every
    part (zero extra input bytes; see ``parts_accumulate_kernel``).
    """
    interpret = common.resolve_interpret(interpret)
    if prologues is not None:
        for p in prologues:
            common.check_prologue(p)
    if (slot_epilogue or total_chains or census) and (
        prologues is not None and "moments" in prologues
    ):
        raise ValueError(
            "parts epilogues/census do not compose with a 'moments' part "
            "(its flush writes two coupled slots); drop the epilogue or "
            "run the moments leaf as separate 'identity'/'square' parts"
        )
    m = MXU
    n_chains = len(total_chains) if total_chains else 0
    num_out = num_segments + n_chains + ((num_segments + 1) if census else 0)
    # the step schedule: each part's tile run, k tiles per grid step
    schedule, ingest, total_steps = [], [], 0
    for part, (seg, _, nblk, size) in zip(parts, layout):
        k = part_tiles_per_step(part)
        steps = common.ceil_div(nblk, k)
        schedule.append((seg, total_steps, steps, size, k))
        ingest.append(_part_operand(part, total_steps, steps))
        total_steps += steps
    kernel = functools.partial(
        parts_accumulate_kernel,
        layout=tuple(schedule),
        m=m,
        compute_dtype=compute_dtype,
        prologues=prologues,
        moments_offset=moments_offset,
        slot_epilogue=slot_epilogue,
        total_chains=total_chains,
        chain_offset=num_segments if census else None,
        census_offset=(num_segments + n_chains) if census else None,
    )
    scratch = [common.vmem_scratch((m, m), jnp.float32)]
    if prologues is not None and "moments" in prologues:
        scratch.append(common.vmem_scratch((m, m), jnp.float32))
    if total_chains:
        scratch.append(common.vmem_scratch((1, 1), jnp.float32))
    if census:
        scratch.append(common.vmem_scratch((m, m), jnp.float32))
        scratch.append(common.vmem_scratch((1, 1), jnp.float32))
    # a (1, num_out) row: the same bytes as (num_out,), slot-addressable
    # by lane selects
    return pl.pallas_call(
        kernel,
        grid=(total_steps,),
        in_specs=[spec for _, spec in ingest],
        out_specs=pl.BlockSpec((1, num_out), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, num_out), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=common.compiler_params(("arbitrary",)),
        interpret=interpret,
    )(*[operand for operand, _ in ingest]).reshape(num_out)
