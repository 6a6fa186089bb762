"""Public jit'd entry points for the MMA reduction kernels.

This layer owns everything the kernels keep static: the zero-copy ingestion
contract (which dtypes stream natively, the one documented pre-cast
fallback), the aligned-block cover layout for segmented gathers, the
per-part tile schedule for multi-operand launches, the lane-striping
geometry for the multi-core grid, the lane-aware segment flush maps, and
the DETERMINISTIC lane combines. The combines run as plain f32 XLA dots in
a fixed lane order -- never an atomic or a scheduling-dependent tree -- so
every reduction is bit-reproducible run-to-run regardless of how many cores
streamed the partials.

Zero-copy ingestion: every entry point hands the kernels the caller's
buffer as a FLAT view in its native dtype (``reshape(-1)`` of a contiguous
buffer is free at the XLA level); reshaping to (r, m, m) tiles, casting to
the compute dtype, and masking the ragged tail all happen in-VMEM. The only
host-side copy left on any path is the ``_ingest`` pre-cast for dtypes the
MXU cannot read (f64, ints, bools -> f32), and the traces carry the modeled
HBM bytes (``cost_model.hbm_bytes``) of the geometry actually launched.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model
from repro.core import precision as _precision
from repro.core.mma_reduce import ReductionTrace
from repro.kernels import common
from repro.kernels.mma_reduce import kernel as _k

MXU = common.MXU

# The parts kernel compiles one (predicated) branch per part and keeps one
# m^2 block per part resident in VMEM, so both compile time and VMEM grow
# linearly in S. Past this many live parts the packed-stream fallback (one
# concatenation of the small per-part buffers) is the better trade -- see
# ``backends.Backend.sum_parts``.
PARTS_KERNEL_MAX = 128


def _native(x: jax.Array) -> jax.Array:
    """``x`` in a dtype the kernels ingest directly.

    bf16/f16/f32 stream straight from the caller's buffer; anything the MXU
    cannot read natively (f64, ints, bools) is pre-cast to f32 -- the one
    documented staging copy left, and one the planner already routes away
    from the Pallas backends (ints go to xla)."""
    if common.native_ingest_dtype(x.dtype):
        return x
    return x.astype(jnp.float32)


def _ingest(x: jax.Array) -> jax.Array:
    """Flat native-dtype view of ``x`` for zero-copy kernel ingestion (a
    same-size reshape; on the TPU a relayout copy for arrays of rank >= 2,
    which the parts kernel avoids through ``kernel.part_view``)."""
    return _native(x).reshape(-1)


def combine_lane_partials(partials: jax.Array) -> jax.Array:
    """(C, m, m) column-replicated lane accumulators -> scalar, fixed order.

    Two dots, both f32: one batched trailing MMA collapses each lane's
    accumulated row-sums (1 x acc, the fused kernel's old finalize step),
    then a single length-C all-ones dot folds the lane scalars in lane
    order. Everything is a static-order f32 contraction, so the result is
    bit-reproducible run-to-run; with C = 1 the second dot multiplies by
    1.0 and the value is bit-identical to the pre-striping kernel's.
    """
    c, m, _ = partials.shape
    onesf = common.ones_tile(m, "float32")  # cached host-side constant
    d = common.mma(
        jnp.broadcast_to(onesf, partials.shape),
        partials,
        (((2,), (1,)), ((0,), (0,))),
    )
    lane = d[:, 0, 0]  # (C,) per-lane totals
    return common.mma(
        jnp.ones((c,), jnp.float32), lane, (((0,), (0,)), ((), ()))
    )


def combine_lane_partials_kahan(partials: jax.Array) -> jax.Array:
    """(C, 2, m, m) (acc, comp) lane pairs -> scalar via one compensated pass.

    Kahan's corrected sum is ``s - c``; we fold, in fixed lane order, each
    lane's accumulator rows followed by its negated compensation rows
    through one serial Kahan scan, so the cross-lane AND cross-row combine
    are both compensated and deterministic.
    """
    acc = partials[:, 0, :, 0]  # (C, m): column 0 carries the row sums
    comp = partials[:, 1, :, 0]
    v = jnp.stack([acc, -comp], axis=1).reshape(-1)
    return _precision.kahan_sum(v, dtype=jnp.float32)


def combine_segment_partials(sub: jax.Array) -> jax.Array:
    """(C, S) lane sub-partials -> (S,) per-segment totals, fixed lane order.

    One exact-order f32 add per lane per segment (C is tiny); with C = 1
    this is the identity on the kernel's output bits.
    """
    return jnp.sum(sub, axis=0)


def combine_lane_pair_partials(partials: jax.Array) -> tuple:
    """(C, 2, m, m) dual-accumulator lane pairs (the moments prologue) ->
    the (sum, sumsq) scalar pair, each half collapsed by the SAME
    deterministic fixed-order combine as a plain lane stack."""
    return (
        combine_lane_partials(partials[:, 0]),
        combine_lane_partials(partials[:, 1]),
    )


def mma_sum_pallas(
    x: jax.Array,
    *,
    mode: str = "fused",
    tiles_per_block: int = 8,
    num_cores: int = 1,
    compute_dtype=jnp.bfloat16,
    kahan: bool = False,
    prologue: str = "identity",
    epilogue=(),
    census: bool = False,
    interpret: bool | None = None,
    trace: Optional[list] = None,
) -> jax.Array:
    """Sum all (prologue-mapped) elements of ``x`` on the MXU, reading ``x``
    zero-copy. ``prologue`` ("identity" | "square" | "abs") is the in-kernel
    elementwise map -- applied after the compute-dtype cast and tail mask,
    before the eq. (9) MMA -- so ``sumsq``/``norm2`` stream the caller's raw
    leaf exactly once (the moments pair has its own entry point,
    ``mma_moments_pallas``).

    ``census=True`` (fused mode only, not with Kahan) makes the SAME single
    launch also count ``x``'s non-finite elements on a second ones-dot
    accumulator -- the tiles are already in registers, so the count costs
    zero extra HBM input bytes -- and changes the return to the
    ``(total, nonfinite_count)`` pair. The count is exact (0/1 mask summed
    in f32) and the masked ragged tail never contributes.

    ``epilogue`` (a normalized scalar chain -- ``common.normalize_epilogue``)
    maps the reduced total. It runs IN-KERNEL whenever the total is formed
    inside the launch -- the single-lane fused collapse, or the final
    hierarchy level -- and falls back to the same ``apply_epilogue``
    definition host-side only where the total genuinely forms on the host
    (multi-lane or Kahan combines): the values are identical either way,
    and the empty chain leaves every path byte-for-byte unchanged.

    mode="hierarchical": the paper's multi-launch recurrence (eq. 13) --
      each level is one pallas_call producing per-group partials (the grid
      is ``parallel``: every core reduces its own tiles concurrently).
      Level 0 streams the native buffer (and applies the prologue); upper
      levels stream the f32 partials the previous launch wrote (identity --
      partials are already mapped).
    mode="fused": single launch using the MMA C-accumulator, striped across
      ``num_cores`` lanes of a ("parallel", "arbitrary") grid; the lane
      partials collapse through the deterministic fixed-order combine.
      ``kahan=True`` carries a per-lane compensation row in a second VMEM
      scratch (single launch, compensated cross-tile carry; composes with
      the elementwise prologues).

    ``trace``: optional list; a ``ReductionTrace`` with the per-lane /
    combine MMA split and the modeled HBM bytes is appended (Python
    metadata only).
    """
    common.check_prologue(prologue, allow_moments=False)
    epilogue = common.normalize_epilogue(epilogue)
    if census and mode != "fused":
        raise ValueError(
            "census rides the fused single launch; the hierarchical mode "
            "would need a second partials column per level"
        )
    if census and kahan:
        raise ValueError(
            "census does not compose with kahan=True (the compensation "
            "row occupies the second accumulator)"
        )
    if x.size == 0:
        # Empty reduction -> additive identity (matches mma_sum / jnp.sum).
        if trace is not None:
            trace.append(ReductionTrace(n=0, m=MXU, levels=0, mma_ops=0))
        total = common.apply_epilogue(jnp.zeros((), jnp.float32), epilogue)
        if census:  # nothing streamed -> nothing non-finite
            return total, jnp.zeros((), jnp.float32)
        return total
    flat = _ingest(x)
    if mode == "fused":
        t_ = max(1, common.ceil_div(int(flat.size), MXU * MXU))
        _, c_eff, _, _ = _k._lane_geometry(t_, tiles_per_block, num_cores)
        in_kernel = bool(epilogue) and c_eff == 1 and not kahan
        if trace is not None:
            trace.append(
                fused_trace(
                    int(flat.size),
                    tiles_per_block,
                    num_cores,
                    itemsize=flat.dtype.itemsize,
                    kahan=kahan,
                    epilogue=in_kernel,
                    census=census,
                    fallback="" if flat.dtype == x.dtype else "ingest_f32",
                )
            )
        partials = _k.reduce_fused(
            flat,
            tiles_per_block=tiles_per_block,
            num_cores=num_cores,
            compute_dtype=compute_dtype,
            kahan=kahan,
            prologue=prologue,
            epilogue=epilogue if in_kernel else (),
            census=census,
            interpret=interpret,
        )
        if in_kernel:
            if census:  # (1, 2): [finished total, non-finite count]
                return partials[0, 0], partials[0, 1]
            return partials.reshape(())  # chain already applied in-launch
        if census:
            # (C, 2, m, m): sum lanes in [:, 0], census lanes in [:, 1];
            # the chain maps the TOTAL only -- the count is a raw tally.
            total = common.apply_epilogue(
                combine_lane_partials(partials[:, 0]), epilogue
            )
            return total, combine_lane_partials(partials[:, 1])
        if kahan:
            total = combine_lane_partials_kahan(partials)
        else:
            total = combine_lane_partials(partials)
        # multi-lane / Kahan: the total forms on the host, so the chain
        # runs here (same apply_epilogue definition, identical values).
        return common.apply_epilogue(total, epilogue)
    if mode != "hierarchical":
        raise ValueError(f"unknown mode {mode!r}")
    if kahan:
        raise ValueError(
            "kahan=True needs the fused carry; the hierarchical mode "
            "round-trips partials through HBM between launches"
        )
    n0 = flat.size
    fallback = "" if flat.dtype == x.dtype else "ingest_f32"
    hbm = cost_model.hier_hbm_bytes(
        n0, flat.dtype.itemsize, m=MXU, tiles_per_block=tiles_per_block
    )
    levels, mma_ops = 0, 0
    level_prologue = prologue
    epilogue_applied = not epilogue
    while flat.size > 1:
        t = common.ceil_div(flat.size, MXU * MXU)
        flat = _k.reduce_tiles(
            flat,
            tiles_per_block=tiles_per_block,
            compute_dtype=compute_dtype,
            prologue=level_prologue,
            # the FINAL level (t == 1) forms the total in-kernel: the
            # chain maps it there, inside the last launch.
            epilogue=epilogue if t == 1 else (),
            interpret=interpret,
        )
        if t == 1:
            epilogue_applied = True
        level_prologue = "identity"  # upper levels run on mapped partials
        levels += 1
        mma_ops += 2 * t
    if level_prologue != "identity":
        # single-element input: no level ever ran, so apply the map here
        # (at compute precision, exactly like a level-0 launch would).
        flat = common.apply_prologue(
            flat.astype(compute_dtype), prologue
        ).astype(jnp.float32)
    if not epilogue_applied:
        flat = common.apply_epilogue(flat, epilogue)
    if trace is not None:
        trace.append(
            ReductionTrace(
                n=n0, m=MXU, levels=levels, mma_ops=mma_ops,
                hbm_bytes=hbm.total, fallback=fallback,
            )
        )
    return flat.reshape(())


def fused_trace(
    n: int,
    tiles_per_block: int = 8,
    num_cores: int = 1,
    *,
    itemsize: int = 4,
    kahan: bool = False,
    dual: bool = False,
    epilogue: bool = False,
    census: bool = False,
    fallback: str = "",
) -> ReductionTrace:
    """Static per-lane / combine MMA + HBM-byte instrumentation for one
    zero-copy fused pass (the geometry here is ``stripe_geometry``'s -- the
    same one the kernel launches, so trace, cost model, and silicon agree
    by construction). ``dual=True`` is the moments prologue: two MMAs per
    tile and a doubled combine; the elementwise prologues change neither
    count nor byte. ``epilogue=True`` is the in-kernel finish (single-lane
    only): the combine MMA moves inside the launch and the partials write
    shrinks to one finished f32 scalar. ``census=True`` (non-dual,
    non-kahan) carries the non-finite census: byte-identical to the
    moments dual accumulator on the partials path (same doubled output
    shape), and one extra f32 slot on the in-kernel-epilogue path --
    zero extra input bytes either way."""
    k = max(1, common.ceil_div(n, MXU * MXU))
    _, c, _, tpad = _k._lane_geometry(k, tiles_per_block, num_cores)
    d = 2 if (dual or census) else 1
    lane = d * (tpad // c)
    combine = d * (c + 1)
    if census and epilogue:
        # the epilogue model with the census count widening the finished
        # output from one f32 scalar to two
        hbm = cost_model.fused_hbm_bytes(
            n, itemsize, num_cores=num_cores,
            tiles_per_block=tiles_per_block, kahan=kahan, epilogue=True,
        )
        hbm = dataclasses.replace(hbm, kernel_write=2 * hbm.kernel_write)
    else:
        hbm = cost_model.fused_hbm_bytes(
            n, itemsize, num_cores=num_cores,
            tiles_per_block=tiles_per_block, kahan=kahan,
            dual=dual or census, epilogue=epilogue,
        )
    return ReductionTrace(
        n=n,
        m=MXU,
        levels=1,
        mma_ops=d * tpad + combine,
        num_cores=c,
        lane_mma_ops=lane,
        combine_mma_ops=combine,
        hbm_bytes=hbm.total,
        fallback=fallback,
        census=census,
    )


def mma_moments_pallas(
    x: jax.Array,
    *,
    mode: str = "fused",
    tiles_per_block: int = 8,
    num_cores: int = 1,
    compute_dtype=jnp.bfloat16,
    interpret: bool | None = None,
    trace: Optional[list] = None,
) -> tuple:
    """(sum, sum-of-squares) of every element of ``x`` from ONE zero-copy
    pass over the raw buffer -- the paired (x, x^2) dual-accumulator
    prologue. This is the full-reduction moments path: the old route paid a
    host-side f32 square (an n-sized elementwise pass + staging write) and
    a SECOND kernel pass; here both statistics ride the same stream.

    mode="fused": one launch, each lane carrying (acc, acc2); both halves
      collapse through the deterministic fixed-order combine.
    mode="hierarchical": level 0 emits the (T, 2) partial pair from one
      pass over the native buffer; each f32 column then recurses through
      the plain identity hierarchy (eq. 13).
    """
    if x.size == 0:
        if trace is not None:
            trace.append(ReductionTrace(n=0, m=MXU, levels=0, mma_ops=0))
        z = jnp.zeros((), jnp.float32)
        return z, z
    flat = _ingest(x)
    fallback = "" if flat.dtype == x.dtype else "ingest_f32"
    if mode == "fused":
        if trace is not None:
            trace.append(
                fused_trace(
                    int(flat.size),
                    tiles_per_block,
                    num_cores,
                    itemsize=flat.dtype.itemsize,
                    dual=True,
                    fallback=fallback,
                )
            )
        partials = _k.reduce_fused(
            flat,
            tiles_per_block=tiles_per_block,
            num_cores=num_cores,
            compute_dtype=compute_dtype,
            prologue="moments",
            interpret=interpret,
        )
        return combine_lane_pair_partials(partials)
    if mode != "hierarchical":
        raise ValueError(f"unknown mode {mode!r}")
    n0 = int(flat.size)
    hbm = cost_model.hier_moments_hbm_bytes(
        n0, flat.dtype.itemsize, m=MXU, tiles_per_block=tiles_per_block
    )
    t0 = common.ceil_div(n0, MXU * MXU)
    pair = _k.reduce_tiles(
        flat,
        tiles_per_block=tiles_per_block,
        compute_dtype=compute_dtype,
        prologue="moments",
        interpret=interpret,
    )  # (T, 2): both statistics from the single level-0 pass
    levels, mma_ops = 1, 4 * t0  # 2 MMAs per tile per statistic at level 0
    outs = []
    for col in (pair[:, 0], pair[:, 1]):
        v = col
        while v.size > 1:
            t = common.ceil_div(v.size, MXU * MXU)
            v = _k.reduce_tiles(
                v,
                tiles_per_block=tiles_per_block,
                compute_dtype=compute_dtype,
                interpret=interpret,
            )
            levels += 1
            mma_ops += 2 * t
        outs.append(v.reshape(()))
    if trace is not None:
        trace.append(
            ReductionTrace(
                n=n0, m=MXU, levels=levels, mma_ops=mma_ops,
                hbm_bytes=hbm.total, fallback=fallback,
            )
        )
    return outs[0], outs[1]


def segment_cover_layout(
    offsets: Sequence[int], group: int
) -> tuple[tuple[int, ...], np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Aligned-block cover of a segmented flat buffer (trace-time numpy).

    Segment s spans ``[offsets[s], offsets[s+1])`` of the flat buffer; its
    tiles are the ``group``-aligned blocks that OVERLAP it, each carrying
    the in-block validity window ``[lo, hi)`` of the elements that belong
    to s. Tile-aligned segments stream every block exactly once with a full
    window; a non-aligned boundary makes the straddled block appear in BOTH
    neighbours' covers (two masked fetches of one block -- the O(S m^2)
    "non-aligned remainder" traffic, never an n-sized staging copy).

    Returns ``(tile_counts, src_blk, seg_of, lo_in, hi_in)``: per-segment
    cover sizes (0 for empty segments) plus the four flat per-tile maps the
    gather kernel prefetches.
    """
    offs = np.asarray(offsets, np.int64)
    src, seg, lo, hi = [], [], [], []
    tcounts = []
    for s in range(offs.size - 1):
        a, b = int(offs[s]), int(offs[s + 1])
        if b <= a:
            tcounts.append(0)
            continue
        blk0, blk1 = a // group, -(-b // group)
        tcounts.append(blk1 - blk0)
        for k in range(blk0, blk1):
            src.append(k)
            seg.append(s)
            lo.append(max(a - k * group, 0))
            hi.append(min(b - k * group, group))
    return (
        tuple(tcounts),
        np.asarray(src, np.int32),
        np.asarray(seg, np.int32),
        np.asarray(lo, np.int32),
        np.asarray(hi, np.int32),
    )


def segment_tile_layout(
    offsets: Sequence[int], group: int
) -> tuple[tuple[int, ...], np.ndarray, np.ndarray]:
    """Static tile bookkeeping for a PACKED segmented stream (legacy layout).

    Describes the pre-gather stream build -- each segment zero-padded to
    whole tiles and concatenated: per-segment tile counts
    (``ceil(size/group)``, 0 for empty segments), the tile->segment id map,
    and the SERIAL boundary-flag map (1 on the last tile of each non-empty
    segment -- the ``num_cores=1`` flush map; striped lanes use
    ``lane_flush_map``). The zero-copy gather path uses
    ``segment_cover_layout`` instead (aligned-block covers of the caller's
    buffer, which may need one MORE tile per non-aligned segment start);
    this layout remains the right one for callers sizing a packed
    ``(T, m, m)`` stream. All trace-time numpy -- offsets are static."""
    sizes = np.diff(np.asarray(offsets, np.int64))
    tcounts = tuple(int(-(-s // group)) if s > 0 else 0 for s in sizes)
    total = sum(tcounts)
    seg_of = np.zeros((total,), np.int32)
    flush = np.zeros((total,), np.int32)
    pos = 0
    for s, tc in enumerate(tcounts):
        if tc == 0:
            continue
        seg_of[pos : pos + tc] = s
        flush[pos + tc - 1] = 1
        pos += tc
    return tcounts, seg_of, flush


def lane_flush_map(
    seg_of: np.ndarray, tiles_per_block: int, num_cores: int
) -> np.ndarray:
    """Lane-aware flush flags for a striped segmented stream (trace-time).

    Lane ``ci`` of a C-lane grid streams blocks ``ci, ci+C, ci+2C, ...`` --
    so the tiles it visits are interleaved with the other lanes'. A lane
    must flush its accumulator whenever ITS OWN stripe leaves a segment:
    flag position p iff p is the last tile of its segment within the stripe
    that owns it. With C = 1 this reduces exactly to the serial
    last-tile-of-segment map. The gather kernel stripes tile-granularly
    (``tiles_per_block=1``); the parameter is kept for block-striped
    streams and tests.
    """
    seg_of = np.asarray(seg_of)
    t = int(seg_of.size)
    if t == 0:
        return np.zeros((0,), np.int32)
    r, c, _, _ = _k._lane_geometry(t, tiles_per_block, num_cores)
    flush = np.zeros((t,), np.int32)
    for ci in range(c):
        pos: list[int] = []
        j = 0
        while True:
            lo = (j * c + ci) * r
            if lo >= t:
                break
            pos.extend(range(lo, min(lo + r, t)))
            j += 1
        for k_, p in enumerate(pos):
            if k_ + 1 == len(pos) or seg_of[pos[k_ + 1]] != seg_of[p]:
                flush[p] = 1
    return flush


def segmented_trace(
    n: int,
    flushes: int,
    tiles: int,
    num_cores: int,
    *,
    itemsize: int = 4,
    fetched_elems: int | None = None,
    segments: int = 1,
    dual: bool = False,
    census: bool = False,
) -> ReductionTrace:
    """Static instrumentation for one segmented gather pass (flush MMAs =
    combine; ``fetched_elems`` counts every element the cover actually
    DMAs, i.e. n plus the re-fetched straddled blocks). ``dual`` is the
    moments prologue: two main MMAs per tile, and ``segments``/``flushes``
    arrive already widened to the doubled output slots. ``census`` rides
    the same dual-accumulator shape (one extra ones-dot per tile, one
    extra flush per lane-segment visit, doubled slots -- the widened
    counts likewise arrive pre-folded into ``segments``/``flushes``) at
    zero extra input bytes."""
    _, c, _, tpad = _k._lane_geometry(tiles, 1, num_cores)
    d = 2 if (dual or census) else 1
    return ReductionTrace(
        n=n,
        m=MXU,
        levels=1,
        mma_ops=d * tpad + flushes,
        num_cores=c,
        lane_mma_ops=d * (tpad // c),
        combine_mma_ops=flushes,
        hbm_bytes=cost_model.segmented_hbm_bytes(
            fetched_elems if fetched_elems is not None else n,
            itemsize,
            segments=segments,
            tiles=tiles,
            num_cores=num_cores,
        ).total,
        census=census,
    )


def _cover_fetched_elems(
    src_blk: np.ndarray, flat_size: int, group: int
) -> int:
    """Elements the gather DMAs: one (possibly buffer-clipped) block per
    cover tile -- equals n for tile-aligned segments, n + O(S * group) when
    boundaries straddle blocks (shared blocks are fetched once per
    neighbour)."""
    return int(
        sum(min(group, flat_size - int(b) * group) for b in src_blk)
    )


def mma_sum_segments_pallas(
    flat: jax.Array,
    offsets: Sequence[int],
    *,
    tiles_per_block: int = 8,
    num_cores: int = 1,
    compute_dtype=jnp.bfloat16,
    prologue: str = "identity",
    epilogue=(),
    census: bool = False,
    interpret: bool | None = None,
    trace: Optional[list] = None,
) -> jax.Array:
    """Sum S independent segments of ``flat`` in ONE kernel launch, reading
    ``flat`` zero-copy.

    ``epilogue`` (normalized chain; not with "moments") maps every
    per-segment total -- in-kernel on single-lane launches (each segment
    flushes exactly once there), host-side after the lane combine otherwise
    (same ``apply_epilogue`` definition, identical values).

    ``offsets`` (static ints, len S+1) delimit the segments:
    ``out[s] = sum(flat[offsets[s]:offsets[s+1]])``. Each segment is
    covered by the m^2-aligned blocks of the caller's buffer that overlap
    it (``segment_cover_layout``); the cover maps are scalar-prefetched and
    the BlockSpec index map gathers each tile straight from the original
    buffer -- no slice-pad-concatenate stream is ever materialized.
    Tile-aligned segments stream every byte once; a non-aligned boundary
    re-fetches the one straddled block (masked both sides) -- the
    "non-aligned remainder" costs O(S) extra block fetches, modeled by
    ``cost_model.segmented_hbm_bytes``. The cover stream is striped
    tile-granularly across ``num_cores`` lanes (each lane flushing
    per-(lane, segment) sub-partials at its own lane-aware boundaries) and
    one exact fixed-order f32 per-segment combine folds the lanes --
    ~n/m^2 striped main MMAs + one flush MMA per lane-segment visit
    (exactly S at C = 1, at most S per lane). ``tiles_per_block`` is
    accepted for plan compatibility but plays no role on the gather path.
    Empty segments cost no tiles and come back as the additive identity.

    ``prologue`` maps each gathered tile in-kernel (sumsq/norm2 segments
    stream the raw buffer once); ``prologue="moments"`` returns the
    widened (2S,) vector -- per-segment sums in [0, S), sums of squares in
    [S, 2S) -- both statistics from the same single launch.

    ``census=True`` (not with "moments") widens the output the same way:
    per-segment sums in [0, S), per-segment NON-FINITE counts in [S, 2S),
    both from the one gather pass (the counts ride a second accumulator on
    the tiles already in registers -- zero extra input bytes; window-masked
    lanes are exact zeros and never miscount). The epilogue, when present,
    maps only the sum slots; the counts stay raw tallies.
    """
    del tiles_per_block  # gather path is tile-granular by construction
    common.check_prologue(prologue)
    epilogue = common.normalize_epilogue(epilogue)
    dual = prologue == "moments"
    if epilogue and dual:
        raise ValueError(
            "segment epilogues do not compose with prologue='moments' "
            "(each flush writes two coupled slots)"
        )
    if census and dual:
        raise ValueError(
            "census does not compose with prologue='moments' (both claim "
            "the second accumulator); run moments as separate segments"
        )
    nseg = len(offsets) - 1
    if nseg <= 0:
        return jnp.zeros((0,), jnp.float32)
    out_slots = (2 * nseg) if (dual or census) else nseg
    flat = _ingest(flat)
    group = MXU * MXU
    tcounts, src_blk, seg_of, lo_in, hi_in = segment_cover_layout(
        offsets, group
    )
    t = int(src_blk.size)
    if t == 0:  # every segment empty
        per = common.apply_epilogue(
            jnp.zeros((nseg,), jnp.float32), epilogue
        )
        if census:  # nothing streamed -> zero counts, epilogue-free
            return jnp.concatenate([per, jnp.zeros((nseg,), jnp.float32)])
        return per if not dual else jnp.zeros((out_slots,), jnp.float32)
    _, c_eff, _, _ = _k._lane_geometry(t, 1, num_cores)
    in_kernel = bool(epilogue) and c_eff == 1
    flush = lane_flush_map(seg_of, 1, num_cores)
    if trace is not None:
        trace.append(
            segmented_trace(
                int(flat.size),
                (2 if (dual or census) else 1) * int(flush.sum()),
                t,
                num_cores,
                itemsize=flat.dtype.itemsize,
                fetched_elems=_cover_fetched_elems(
                    src_blk, int(flat.size), group
                ),
                segments=out_slots,
                dual=dual,
                census=census,
            )
        )
    sub = _k.reduce_segments(
        flat,
        src_blk,
        seg_of,
        flush,
        lo_in,
        hi_in,
        nseg,
        num_cores=num_cores,
        compute_dtype=compute_dtype,
        prologue=prologue,
        epilogue=epilogue if in_kernel else (),
        census=census,
        interpret=interpret,
    )
    out = combine_segment_partials(sub)
    if in_kernel:
        # An EMPTY segment never flushes, so the in-kernel epilogue never
        # maps its slot: patch it to epilogue(0) host-side -- the value the
        # multi-lane and all-empty paths produce -- so the epilogue'd
        # result never depends on the lane count.
        empty = np.asarray(tcounts) == 0
        if empty.any():
            fixed = common.apply_epilogue(
                jnp.zeros((), jnp.float32), epilogue
            )
            mask = jnp.asarray(empty)
            if census:  # counts stay raw tallies (0 for an empty segment)
                mask = jnp.concatenate(
                    [mask, jnp.zeros_like(mask)]
                )
            out = jnp.where(mask, fixed, out)
    if epilogue and not in_kernel:
        if census:  # the chain maps sums only; counts are raw tallies
            out = jnp.concatenate(
                [common.apply_epilogue(out[:nseg], epilogue), out[nseg:]]
            )
        else:
            out = common.apply_epilogue(out, epilogue)
    return out


def parts_layout(
    sizes: Sequence[int], group: int
) -> tuple[tuple[int, int, int, int], ...]:
    """Static tile schedule for a multi-operand parts launch: one
    ``(seg, start, nblk, size)`` run per NON-EMPTY part, consecutive on the
    shared grid (``start`` = running block total)."""
    layout = []
    start = 0
    for s, size in enumerate(sizes):
        size = int(size)
        if size == 0:
            continue
        nblk = common.ceil_div(size, group)
        layout.append((s, start, nblk, size))
        start += nblk
    return tuple(layout)


def parts_trace(
    sizes: Sequence[int],
    itemsizes: Sequence[int],
    prologues=None,
    *,
    extra_slots: int = 0,
    census: bool = False,
) -> ReductionTrace:
    """Static instrumentation for one parts pass: one main MMA per tile
    (two for a moments part -- both statistics from the same read) + one
    flush MMA per live part slot; traffic = the parts' native bytes (the
    prologues move NO extra bytes -- the whole point). ``extra_slots``
    counts epilogue total-chain outputs: K finished scalars widen the
    output row by K f32 slots and cost nothing else. ``census=True`` adds
    the non-finite census: one extra ones-dot MMA per tile + one flush MMA
    per live part, and S + 1 more f32 output slots -- still ZERO extra
    input bytes."""
    group = MXU * MXU
    prologues = common.normalize_part_prologues(
        "identity" if prologues is None else prologues, len(sizes)
    )
    dual = "moments" in prologues
    layout = parts_layout(sizes, group)
    tiles = flushes = 0
    for (s, _, nblk, _) in layout:
        k = 2 if (prologues[s] == "moments" or census) else 1
        tiles += k * nblk
        flushes += k
    part_bytes = sum(
        int(s) * int(b) for s, b in zip(sizes, itemsizes) if int(s)
    )
    return ReductionTrace(
        n=int(sum(int(s) for s in sizes)),
        m=MXU,
        levels=1,
        mma_ops=tiles + flushes,
        num_cores=1,
        lane_mma_ops=tiles,
        combine_mma_ops=flushes,
        hbm_bytes=cost_model.parts_hbm_bytes(
            part_bytes,
            segments=(2 if dual else 1) * len(sizes) + extra_slots
            + ((len(sizes) + 1) if census else 0),
        ).total,
        census=census,
    )


def mma_sum_parts_pallas(
    parts: Sequence[jax.Array],
    *,
    compute_dtype=jnp.bfloat16,
    prologue="identity",
    slot_epilogue=(),
    total_chains=None,
    census: bool = False,
    interpret: bool | None = None,
    trace: Optional[list] = None,
) -> jax.Array:
    """Sum S separate (prologue-mapped) arrays in ONE kernel launch with NO
    packing copy.

    Every part enters the launch as its own operand (flattened in its
    native dtype -- free) and streams through the shared accumulator on its
    own statically-scheduled tile run; per-part totals flush to the (S,)
    output in part order. ``prologue`` (a name, or one name per part)
    selects each part's in-kernel elementwise map, so
    ``reduce_many(kind="sumsq")`` / ``reduce_tree(kind="norm2")`` stream
    every raw leaf exactly once -- no host-side square, no f32 staging
    write. If ANY part carries "moments" the output widens to (2S,): sums
    in [0, S), sums of squares in [S, 2S) (non-moments parts leave their
    square slot at the additive identity), both statistics riding the same
    single read per leaf. This is the zero-copy engine behind
    ``reduce_many(axis=None)`` / ``reduce_tree``: the packed-stream
    ``concatenate`` (and its accumulate-dtype cast) never happens. Compile
    cost and VMEM residency are O(S); callers bound S via
    ``PARTS_KERNEL_MAX`` (``backends.Backend.sum_parts`` falls back to the
    packed stream past it). Empty parts return the additive identity.

    ``slot_epilogue`` (normalized chain) maps every per-part total
    in-kernel at its flush. ``total_chains`` (tuple of K normalized
    chains) widens the output to (S + K,): slot ``S + k`` carries chain k
    applied to the RAW cross-part total, folded in-kernel in static part
    order -- this is ``reduce_tree``'s single-launch norm/clip finish,
    fully inside the launch at any core count. Neither composes with a
    "moments" part.

    ``census=True`` (non-moments) widens the output further by S + 1
    slots: slot ``S + K + s`` carries part s's NON-FINITE element count
    and the final slot the cross-part total count, both counted in-kernel
    on the tiles already in registers -- the guarded optimizer's NaN/Inf
    detector at ZERO extra input bytes (empty parts count 0; the ragged
    tail is masked to exact zeros before the isfinite test, so pad lanes
    never miscount).
    """
    nseg = len(parts)
    slot_epilogue = common.normalize_epilogue(slot_epilogue)
    if total_chains is not None:
        total_chains = tuple(
            common.normalize_epilogue(c) for c in total_chains
        ) or None
    n_chains = len(total_chains) if total_chains else 0
    if nseg == 0:
        if total_chains:
            raise ValueError("total_chains need at least one part")
        if census:
            raise ValueError("census needs at least one part")
        return jnp.zeros((0,), jnp.float32)
    pros = common.normalize_part_prologues(prologue, nseg)
    dual = "moments" in pros
    if (slot_epilogue or total_chains or census) and dual:
        raise ValueError(
            "parts epilogues/census do not compose with a 'moments' part "
            "(its flush writes two coupled slots); drop the epilogue or "
            "run the moments leaf as separate 'identity'/'square' parts"
        )
    out_slots = (2 * nseg) if dual else nseg
    views = [_k.part_view(_native(p)) for p in parts]
    layout = parts_layout([f.size for f in views], MXU * MXU)
    if not layout:  # every part empty
        per = common.apply_epilogue(
            jnp.zeros((out_slots,), jnp.float32), slot_epilogue
        )
        pieces = [per]
        if total_chains:
            pieces.append(
                jnp.stack(
                    [
                        common.apply_epilogue(
                            jnp.zeros((), jnp.float32), chain
                        )
                        for chain in total_chains
                    ]
                )
            )
        if census:  # nothing streamed -> nothing non-finite
            pieces.append(jnp.zeros((nseg + 1,), jnp.float32))
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    if trace is not None:
        trace.append(
            parts_trace(
                [f.size for f in views],
                [f.dtype.itemsize for f in views],
                pros,
                extra_slots=n_chains,
                census=census,
            )
        )
    live = [views[s] for (s, _, _, _) in layout]
    return _k.reduce_parts(
        live,
        layout,
        out_slots,
        compute_dtype=compute_dtype,
        prologues=tuple(pros[s] for (s, _, _, _) in layout),
        moments_offset=nseg if dual else 0,
        slot_epilogue=slot_epilogue,
        total_chains=total_chains,
        census=census,
        interpret=interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def mma_sum_pallas_diff(x: jax.Array, mode: str = "fused") -> jax.Array:
    return mma_sum_pallas(x, mode=mode)


def _fwd(x, mode):
    return mma_sum_pallas(x, mode=mode), jnp.zeros((0,) + x.shape, x.dtype)


def _bwd(mode, res, g):
    return (jnp.broadcast_to(g, res.shape[1:]).astype(res.dtype),)


mma_sum_pallas_diff.defvjp(_fwd, _bwd)
