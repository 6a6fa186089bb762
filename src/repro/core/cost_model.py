"""Cost models: the paper's step model and its TPU roofline extension.

Paper model (section IV.B, simplified GPU/PRAM model):
  coalesced r/w = 1, tile fill = 1, MMA = 1 cycle, result write = 1
  => T_tc(n) = 5 log_{m^2}(n)       (eq. 16)
     T_classic(n) = 4 log_2(n)      (pairwise baseline)
     S = (4/5) log_2(m^2)           (eq. 17)

TPU extension: the paper's model has no bandwidth or pipe-depth term. We add
both so EXPERIMENTS.md can say *where* the MMA encoding wins on real silicon:
a cold HBM-resident sum is bandwidth-bound and no compute trick helps; a
VMEM-resident (fused-epilogue) reduction is compute-unit-bound and moving it
from the VPU to the MXU is the win the paper predicts.
"""

from __future__ import annotations

import dataclasses
import math

MXU_DIM = 128             # systolic array linear size
VPU_LANES = 8 * 128       # VPU operates on (8, 128) vregs


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one TPU generation."""

    bf16_flops: float     # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int
    ici_bytes_per_s: float  # per link
    mxus: int             # MXUs per chip

    @property
    def clock_hz(self) -> float:
        """Clock implied by the bf16 peak: each MXU retires one
        MXU_DIM x MXU_DIM multiply-accumulate (2 FLOPs each) per cycle."""
        return self.bf16_flops / (self.mxus * 2 * MXU_DIM * MXU_DIM)


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (system architecture and chip specifications): 197 TFLOP/s bf16,
# 16 GiB of HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect over
# four links, one TensorCore with four MXUs per chip.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30,
        ici_bytes_per_s=50e9, mxus=4,
    ),
}

# The chip the analytic models assume unless told otherwise.
DEFAULT_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks table entry for a device kind; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


# ----------------------------- paper's model --------------------------------

def t_tensor_core(n: float, m: int) -> float:
    """Paper eq. (16): T_tc(n) = 5 log_{m^2}(n), in model steps."""
    if n <= 1:
        return 0.0
    return 5.0 * math.log(n, m * m)


def t_classic(n: float) -> float:
    """Paper's classic pairwise reduction: T(n) = 4 log2(n)."""
    if n <= 1:
        return 0.0
    return 4.0 * math.log2(n)


def speedup_model(m: int) -> float:
    """Paper eq. (17): S = (4/5) log2(m^2). S>1 for every m >= 2."""
    return 0.8 * math.log2(m * m)


def levels(n: int, m: int) -> int:
    """Number of 2-MMA passes the hierarchical driver executes (exact)."""
    if n <= 1:
        return 0
    group, out = m * m, 0
    while n > 1:
        n = -(-n // group)
        out += 1
    return out


# ------------------- multi-core striped-pipeline model ----------------------
#
# The paper's T(n) = 5 log_{m^2}(n) assumes every tensor-core unit reduces in
# parallel. The striped fused kernel realizes that on TPU: the n/m^2 tile
# MMAs split across c concurrent lanes (one per core), each lane paying one
# MMA per tile plus one trailing collapse, and a fixed-order combine of the
# c lane partials closes the reduction. Critical-path MMA count per lane:
#   n/(m^2 c) + c  (the +c is the lane collapses + lane fold, serialized).


@dataclasses.dataclass(frozen=True)
class MmaOpCount:
    """Static MMA instrumentation for one striped fused/segmented pass."""

    n: int
    m: int
    num_cores: int    # effective lanes (clamped to the block count)
    lane: int         # main-stream MMAs issued per lane, all lanes concurrent
    combine: int      # collapse/flush MMAs beyond the main streams (chip-wide)
    # Collapse/flush MMAs on ONE lane's serial chain. For the fused kernel
    # the whole combine runs after every lane finishes (serial tail), so
    # this equals `combine`; for the segmented kernel flushes execute
    # INSIDE their lanes concurrently, so it is the worst lane's share.
    serial_tail: int | None = None

    @property
    def total(self) -> int:
        """MMAs issued chip-wide: lanes * per-lane + the combine work."""
        return self.num_cores * self.lane + self.combine

    @property
    def critical_path(self) -> int:
        """MMAs on the longest serial chain: one lane's stream + its tail."""
        return self.lane + (
            self.combine if self.serial_tail is None else self.serial_tail
        )


def stripe_geometry(tiles: int, tiles_per_block: int, num_cores: int):
    """(r, c, blocks_per_lane, padded_tiles) for a striped tile stream.

    THE source of truth for the lane geometry -- the Pallas kernels
    (``kernels.mma_reduce.kernel._lane_geometry``) and the bit-exact
    reference emulation both delegate here, so the grid the silicon runs
    and the grid this model charges for can never diverge."""
    r = max(1, min(tiles_per_block, tiles))
    blocks = -(-tiles // r)
    c = max(1, min(num_cores, blocks))
    blocks_per_lane = -(-blocks // c)
    return r, c, blocks_per_lane, r * c * blocks_per_lane


def fused_mma_ops(
    n: int,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
    dual: bool = False,
) -> MmaOpCount:
    """MMA count for the striped fused C-accumulator kernel.

    Per lane: padded-tiles/c main MMAs; combine: c lane collapses (one
    batched f32 MMA) + 1 lane fold, all after the lanes join (serial
    tail). ``num_cores=1`` recovers the serial fused count n/m^2 + 2.
    ``dual=True`` models the moments prologue's paired (x, x^2)
    accumulators: every tile costs two MMAs and the combine collapses both
    statistics, so lane and combine counts double."""
    tiles = max(1, -(-n // (m * m)))
    _, c, _, tpad = stripe_geometry(tiles, tiles_per_block, num_cores)
    k = 2 if dual else 1
    return MmaOpCount(
        n=n, m=m, num_cores=c, lane=k * (tpad // c), combine=k * (c + 1)
    )


@dataclasses.dataclass(frozen=True)
class ScanMmaOps:
    """Static MMA instrumentation for one striped triangular-scan pass.

    The scan kernel (Dakkak-style two-level scheme on a CONTIGUOUS lane
    partition: lane ci owns blocks [ci*bpl, (ci+1)*bpl)) issues, per tile,
    two carry MMAs -- T1 = X @ J (row sums broadcast) and D = Ls @ T1 (rows-
    before-i totals, whose corner yields the tile total) -- during BOTH the
    carry-reconstruction prefix and the owned stripe, plus one prefix MMA
    (R = X @ U) only on owned tiles. Lanes therefore do DIFFERENT amounts
    of work (lane ci re-streams ci*bpl blocks before its stripe), which is
    why this is not an ``MmaOpCount``: that class models uniform lanes."""

    n: int
    m: int
    num_cores: int       # effective lanes (clamped to the block count)
    tiles: int           # padded tile count (r * c * blocks_per_lane)
    lane_scan: int       # MMAs on one lane's OWNED stripe (3 per tile)
    carry_worst: int     # carry-phase MMAs on the LAST lane (2 per tile)

    @property
    def total(self) -> int:
        """MMAs issued chip-wide: every lane's stripe + all carry prefixes.

        sum_ci [3*tiles/c + 2*(tiles/c)*ci] = tiles * (c + 2) / ... exactly
        ``3*tiles + tiles*(c-1)`` -- the serial count ``3*tiles`` at c=1."""
        t_per = self.tiles // self.num_cores
        return self.num_cores * self.lane_scan + sum(
            2 * t_per * ci for ci in range(self.num_cores)
        )

    @property
    def critical_path(self) -> int:
        """MMAs on the longest serial chain: the last lane's carry prefix
        plus its owned stripe. Approaches ``2/3`` of the serial chain as c
        grows -- the carry re-stream costs 2 MMAs/tile where the full scan
        costs 3 -- and there is no cross-lane combine at all."""
        return self.carry_worst + self.lane_scan


def scan_mma_ops(
    n: int,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
) -> ScanMmaOps:
    """MMA count for the striped triangular-scan kernel (kernels/scan.py).

    Same ``stripe_geometry`` as the reduction kernels, but the lanes own
    CONTIGUOUS block ranges (a scan is order-dependent; striping would
    interleave carries). ``num_cores=1`` recovers the serial triangular
    count 3 * tiles: one T1 = X@J, one D = Ls@T1, one R = X@U per tile."""
    tiles = max(1, -(-n // (m * m)))
    _, c, bpl, tpad = stripe_geometry(tiles, tiles_per_block, num_cores)
    per_lane_tiles = tpad // c
    return ScanMmaOps(
        n=n,
        m=m,
        num_cores=c,
        tiles=tpad,
        lane_scan=3 * per_lane_tiles,
        carry_worst=2 * per_lane_tiles * (c - 1),
    )


def segmented_mma_ops(
    n: int,
    tiles: int,
    flushes: int,
    m: int = MXU_DIM,
    num_cores: int = 1,
    max_lane_flushes: int | None = None,
) -> MmaOpCount:
    """MMA count for the striped segmented gather kernel.

    The gather path stripes at TILE granularity (each grid step fetches one
    m^2-aligned source block through its scalar-prefetched cover map, so
    there is no multi-tile block depth): lane ci owns tiles ci, ci+C, ... .
    ``tiles`` is the aligned-cover tile count (ops.segment_cover_layout --
    at most one extra tile per non-aligned segment boundary over n/m^2).
    ``flushes`` is the TOTAL lane-aware boundary count (>= non-empty
    segments, <= segments * lanes -- one per lane-segment visit); each is
    one collapse MMA issued inside its lane, so the lanes flush
    concurrently and only the worst lane's share (``max_lane_flushes``,
    conservatively ``flushes`` when unknown) sits on the critical path.
    ``num_cores=1`` recovers the serial segmented count n/m^2 + S."""
    _, c, _, tpad = stripe_geometry(tiles, 1, num_cores)
    return MmaOpCount(
        n=n,
        m=m,
        num_cores=c,
        lane=tpad // c,
        combine=flushes,
        serial_tail=flushes if max_lane_flushes is None else max_lane_flushes,
    )


# --------------------------- HBM traffic model -------------------------------
#
# The reduction is memory-bound (see tpu_reduction_roofline below), so the
# quantity that decides wall time on real silicon is BYTES MOVED, not MMAs.
# The zero-copy kernels read the caller's buffer once, in its native dtype,
# and write only O(c m^2) partials; the pre-zero-copy ("staged") ingestion
# paid ~3x that for a bf16 operand: read n*2 (cast) + write n*4 (f32 staging
# copy) + read n*4 (kernel). These models are asserted against the geometry
# the kernels actually run (ops.py traces carry the modeled bytes, and
# benchmarks/check_bench.py re-derives the "measured" number from the lowered
# jaxpr's pallas_call operands), so model and silicon cannot drift silently.

_F32 = 4  # partials/accumulators/outputs are always f32


@dataclasses.dataclass(frozen=True)
class HbmTraffic:
    """Modeled HBM bytes for one reduction, split along the launch boundary.

    ``kernel_read`` / ``kernel_write`` -- operands DMA'd into and results
    written out of the pallas launch(es): exactly the avals crossing the
    ``pallas_call`` boundary, so ``launch_io`` can be asserted EQUAL to
    ``repro.reduce.inspect.pallas_io_bytes`` of the lowered program (the
    "traced geometry" check -- model and silicon cannot drift).
    ``stage_read`` / ``stage_write`` -- host-side staging copies before the
    launch (zero on every zero-copy path; the pre-zero-copy comparison
    model charges its cast+pad copy here).
    ``combine_read`` / ``combine_write`` -- the deterministic host-side
    lane/segment combine re-reading the partials and writing the result.
    ``refetch_read`` -- bytes a launch DMAs from HBM *again* beyond its
    operand avals (the scan kernel's carry-reconstruction prefix re-streams
    already-counted blocks through the same BlockSpec). These are real wire
    bytes but invisible to the aval accounting, so they are kept OUT of
    ``launch_io`` -- the ``pallas_io_bytes`` equality stays exact -- and
    charged in ``read``/``total``.
    """

    kernel_read: int
    kernel_write: int
    stage_read: int = 0
    stage_write: int = 0
    combine_read: int = 0
    combine_write: int = 0
    refetch_read: int = 0

    @property
    def launch_io(self) -> int:
        """Bytes crossing the pallas_call boundary (== pallas_io_bytes)."""
        return self.kernel_read + self.kernel_write

    @property
    def read(self) -> int:
        return (
            self.kernel_read + self.stage_read + self.combine_read
            + self.refetch_read
        )

    @property
    def write(self) -> int:
        return self.kernel_write + self.stage_write + self.combine_write

    @property
    def total(self) -> int:
        return self.read + self.write


def fused_hbm_bytes(
    n: int,
    itemsize: int,
    *,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
    kahan: bool = False,
    dual: bool = False,
    epilogue: bool = False,
) -> HbmTraffic:
    """Zero-copy fused pass: the kernel streams the caller's buffer once at
    native width (boundary blocks clip to the true length -- masked loads,
    not padded copies), writes C lane partials ((C, 2, m, m) under the Kahan
    carry or the moments dual accumulator -- ``dual=True``), and the host
    combine reads those partials back and writes the scalar (a (2,) pair
    for moments). Total = n*itemsize + O(c m^2): ingestion dominates,
    exactly the stream term of the roofline. The elementwise prologues
    (square/abs) change NO bytes -- that is the whole point: the sumsq /
    norm2 stream costs exactly what the plain sum costs. ``epilogue=True``
    is the in-kernel scalar finish (single-lane, non-kahan launches): the
    chain itself ADDS no bytes -- the lane-partial write and the host
    combine are replaced by one finished f32 scalar crossing the launch
    boundary."""
    tiles = max(1, -(-n // (m * m)))
    _, c, _, _ = stripe_geometry(tiles, tiles_per_block, num_cores)
    if epilogue:
        if c != 1 or kahan or dual:
            raise ValueError(
                "in-kernel fused epilogue requires a single-lane, "
                f"non-kahan, non-dual launch; got c={c}, kahan={kahan}, "
                f"dual={dual}"
            )
        return HbmTraffic(kernel_read=n * itemsize, kernel_write=_F32)
    partials = (2 if (kahan or dual) else 1) * c * m * m * _F32
    return HbmTraffic(
        kernel_read=n * itemsize,
        kernel_write=partials,
        combine_read=partials,
        combine_write=(2 if dual else 1) * _F32,
    )


def staged_sumsq_hbm_bytes(
    n: int,
    itemsize: int,
    *,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
) -> HbmTraffic:
    """The PRE-prologue sumsq/norm2 ingestion (kept as the benchmark
    comparison point): the host squared at f32 BEFORE the kernel --
    read n*itemsize (the native leaf) + write n*4 (the f32 squares) -- and
    the zero-copy kernel then streamed that f32 temporary instead of the
    caller's data. For bf16 that is read-n*2 + write-n*4 + read-n*4: ~5x
    the single-stream bytes of the in-kernel square prologue."""
    zc = fused_hbm_bytes(
        n, _F32, m=m, num_cores=num_cores, tiles_per_block=tiles_per_block
    )
    return HbmTraffic(
        kernel_read=zc.kernel_read,
        kernel_write=zc.kernel_write,
        stage_read=n * itemsize,
        stage_write=n * _F32,
        combine_read=zc.combine_read,
        combine_write=zc.combine_write,
    )


def staged_fused_hbm_bytes(
    n: int,
    itemsize: int,
    *,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
    kahan: bool = False,
) -> HbmTraffic:
    """The PRE-zero-copy ingestion (kept as the benchmark comparison point):
    ``reshape(-1).astype(f32)`` + ``pad_to`` materialized a padded f32 copy
    of the whole input before the launch -- read n*itemsize, write tpad*m^2
    f32 -- and the kernel then read that staging buffer instead of the
    caller's data. For bf16 that is read-n*2 + write-n*4 + read-n*4: ~3x
    the zero-copy bytes before any partial traffic."""
    tiles = max(1, -(-n // (m * m)))
    _, c, _, tpad = stripe_geometry(tiles, tiles_per_block, num_cores)
    staged = tpad * m * m * _F32
    partials = (2 if kahan else 1) * c * m * m * _F32
    return HbmTraffic(
        kernel_read=staged,
        kernel_write=partials,
        stage_read=n * itemsize,
        stage_write=staged,
        combine_read=partials,
        combine_write=_F32,
    )


def hier_hbm_bytes(
    n: int, itemsize: int, *, m: int = MXU_DIM, tiles_per_block: int = 8
) -> HbmTraffic:
    """Multi-launch hierarchy (eq. 13): level 0 streams the native buffer
    with masked-tail loads; every level writes its (block-padded) partials
    to HBM and the next level reads them back -- the round-trip the fused
    kernel removes."""
    group = m * m
    kread, kwrite, size, bs = 0, 0, max(n, 1), itemsize
    while size > 1:
        kread += size * bs
        t = -(-size // group)
        r = max(1, min(tiles_per_block, t))
        tpad = -(-t // r) * r  # the launch writes its padded partial row
        kwrite += tpad * _F32
        size = t
        bs = _F32
    return HbmTraffic(kernel_read=kread, kernel_write=kwrite)


def hier_moments_hbm_bytes(
    n: int, itemsize: int, *, m: int = MXU_DIM, tiles_per_block: int = 8
) -> HbmTraffic:
    """Multi-launch hierarchy under the moments dual-accumulator prologue:
    level 0 streams the native buffer ONCE and writes a (tpad, 2) partial
    pair (both statistics from one pass); the upper rungs then reduce each
    f32 column with the plain identity hierarchy."""
    group = m * m
    size = max(n, 1)
    t = -(-size // group)
    r = max(1, min(tiles_per_block, t))
    tpad = -(-t // r) * r
    upper = hier_hbm_bytes(t, _F32, m=m, tiles_per_block=tiles_per_block)
    return HbmTraffic(
        kernel_read=size * itemsize + 2 * upper.kernel_read,
        kernel_write=2 * tpad * _F32 + 2 * upper.kernel_write,
    )


def segmented_hbm_bytes(
    fetched_elems: int,
    itemsize: int,
    *,
    segments: int,
    tiles: int = 0,
    m: int = MXU_DIM,
    num_cores: int = 1,
) -> HbmTraffic:
    """Zero-copy segmented gather: every tile is a masked view of one
    m^2-aligned block of the caller's flat buffer, so ``fetched_elems`` is
    n plus at most one re-fetched block per non-aligned segment boundary
    (``ops.segment_cover_layout`` computes the exact count -- O(S m^2) over
    n). The launch also prefetches five (tpad,) int32 cover maps; it writes
    (C, S) sub-partials, which the combine reads back to produce the (S,)
    result. NOTE: ``launch_io`` here uses the FETCHED bytes; the lowered
    program's operand avals count the flat buffer once, so
    ``pallas_io_bytes`` == ``launch_io`` exactly when every boundary is
    tile-aligned and is a lower bound otherwise."""
    _, c, _, tpad = stripe_geometry(max(tiles, 1), 1, num_cores)
    maps = 5 * tpad * 4
    sub = c * segments * _F32
    return HbmTraffic(
        kernel_read=fetched_elems * itemsize + maps,
        kernel_write=sub,
        combine_read=sub,
        combine_write=segments * _F32,
    )


def parts_hbm_bytes(part_bytes: int, *, segments: int) -> HbmTraffic:
    """Zero-copy parts pass (``reduce_many``/``reduce_tree``): each of the S
    arrays enters the launch as its own operand -- no packing copy -- and is
    streamed once at native width (``part_bytes`` = sum of the live parts'
    nbytes; boundary blocks clip and dwelled blocks never re-DMA, so there
    is no padding traffic). The (S,) output is final: no combine. Epilogue
    total chains cost NO input bytes -- K finished scalars just widen
    ``segments`` by K output slots (callers pass segments + K)."""
    return HbmTraffic(kernel_read=part_bytes, kernel_write=segments * _F32)


def scan_hbm_bytes(
    n: int,
    itemsize: int,
    *,
    out_itemsize: int | None = None,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
) -> HbmTraffic:
    """Zero-copy triangular scan: the kernel streams the caller's native
    buffer once (masked boundary loads, no padding traffic on the operand
    side) and writes the FULL prefix array -- block-padded, in the output
    dtype -- which the caller slices back to n. A scan cannot shrink its
    output the way a reduction does, so the write side is O(n), not
    O(c m^2), and there is no host combine at all: the in-kernel carry
    chain finishes the result. ``refetch_read`` charges the carry-
    reconstruction prefix: lane ci re-streams blocks [0, ci*bpl) -- clipped
    to the real data extent -- to rebuild its exclusive carry without any
    cross-lane traffic (the Dakkak decoupled scheme's redundant-work trade:
    O(n) extra read bandwidth buys a combine-free, bitwise-deterministic
    multi-core scan)."""
    out_itemsize = itemsize if out_itemsize is None else out_itemsize
    tiles = max(1, -(-n // (m * m)))
    r, c, bpl, tpad = stripe_geometry(tiles, tiles_per_block, num_cores)
    block_elems = r * m * m
    refetch = sum(min(ci * bpl * block_elems, n) for ci in range(c))
    return HbmTraffic(
        kernel_read=n * itemsize,
        kernel_write=tpad * m * m * out_itemsize,
        refetch_read=refetch * itemsize,
    )


def staged_scan_hbm_bytes(
    n: int,
    itemsize: int,
    *,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
) -> HbmTraffic:
    """The XLA two-pass comparison point for a sub-f32 cumsum: XLA upcasts
    the operand to a materialized f32 copy (read n*itemsize + write n*4),
    scans that temporary at f32 (read n*4 + write n*4), and downcasts the
    result back to the storage dtype (read n*4 + write n*itemsize). For
    bf16 that is ~5x the single-stream bytes of the native-ingest kernel,
    the same ratio the staged-sumsq comparison showed for reductions."""
    zc = scan_hbm_bytes(
        n, _F32, out_itemsize=_F32, m=m, num_cores=num_cores,
        tiles_per_block=tiles_per_block,
    )
    return HbmTraffic(
        kernel_read=zc.kernel_read,
        kernel_write=zc.kernel_write,
        stage_read=n * itemsize,
        stage_write=n * _F32,
        combine_read=n * _F32,
        combine_write=n * itemsize,
        refetch_read=zc.refetch_read,
    )


# ------------------------- interconnect traffic ------------------------------


@dataclasses.dataclass(frozen=True)
class IciTraffic:
    """Modeled interconnect bytes for one deterministic fixed-order combine
    of ``slots`` f32 partials across a ``world``-device mesh.

    The combine is ONE all-gather per mesh axis: every device receives the
    other P-1 devices' partial rows and folds them locally in static device
    order (no reduction happens on the wire, which is exactly what buys
    bitwise reproducibility). ``recv_per_device`` is therefore
    ``(world - 1) * slots * itemsize`` for a single axis -- asserted EQUAL to
    ``repro.reduce.inspect.collective_recv_bytes`` of the lowered program,
    the same model==lowered discipline as ``HbmTraffic.launch_io``.
    """

    slots: int
    world: int
    itemsize: int = _F32

    @property
    def recv_per_device(self) -> int:
        """Wire bytes INTO each device (== inspect.collective_recv_bytes)."""
        return (self.world - 1) * self.slots * self.itemsize

    @property
    def send_per_device(self) -> int:
        """Wire bytes OUT of each device (its row to the other P-1)."""
        return (self.world - 1) * self.slots * self.itemsize

    @property
    def wire_total(self) -> int:
        """Total bytes on the interconnect across all devices."""
        return self.world * self.recv_per_device

    @property
    def time_s(self) -> float:
        """Lower-bound gather time on one v5e interconnect link."""
        return self.recv_per_device / PEAKS[DEFAULT_KIND].ici_bytes_per_s

    def vs_psum_recv(self) -> float:
        """Cost ratio vs an idealized reduce-scatter+gather psum of the same
        row (which moves ~2 * slots * itemsize per device regardless of P).
        The fixed-order combine trades O(P) gather bytes for determinism;
        for the guard's slot counts (S + K + census) this is noise next to
        the shard's HBM traffic."""
        psum_recv = 2 * self.slots * self.itemsize
        return self.recv_per_device / max(psum_recv, 1)


def interconnect_bytes(
    slots: int, world: int, *, itemsize: int = _F32
) -> IciTraffic:
    """Interconnect traffic of the mesh_axes= reduce path: the per-device
    additive row (per-leaf slots + raw total + census counts) is all-gathered
    once and folded locally. ``world`` is the product of the mesh axis sizes;
    for multi-axis meshes combined one axis at a time the single-axis model
    applies per axis (callers sum per-axis instances)."""
    if slots < 0 or world < 1:
        raise ValueError(f"invalid interconnect geometry: {slots=} {world=}")
    return IciTraffic(slots=slots, world=world, itemsize=itemsize)


def hbm_bytes(
    path: str,
    n: int,
    itemsize: int,
    *,
    m: int = MXU_DIM,
    num_cores: int = 1,
    tiles_per_block: int = 8,
    kahan: bool = False,
    dual: bool = False,
    segments: int = 1,
    tiles: int = 0,
    fetched_elems: int | None = None,
    epilogue: bool = False,
    census: int = 0,
) -> HbmTraffic:
    """Dispatch over the traffic models above by execution path.

    ``path``: "fused" | "fused_staged" | "sumsq_staged" | "hier" |
    "hier_moments" | "segmented" | "parts" | "scan" | "scan_staged".
    For "segmented", ``fetched_elems`` (from the cover layout) defaults to
    ``n``; for "parts", ``n * itemsize`` must equal the summed native bytes
    of the live parts (heterogeneous dtypes: call parts_hbm_bytes).
    ``dual=True`` selects the moments pair-accumulator output shapes on the
    fused path; the elementwise prologues (square/abs) are byte-identical
    to their identity path and need no flag. ``epilogue=True`` (fused path)
    is the in-kernel scalar finish -- the chain adds 0 bytes and the launch
    emits one f32; on the parts path, epilogue total chains instead widen
    ``segments`` by the chain count. ``census`` (parts/segmented paths)
    counts the NON-FINITE-census output slots: like the epilogue chains,
    the census costs ZERO input bytes -- it rides the tiles already in
    registers -- and only widens the output row by ``census`` f32 slots
    (the parts consumer passes S + 1: per-part counts plus the total)."""
    if path == "fused":
        return fused_hbm_bytes(
            n, itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block, kahan=kahan, dual=dual,
            epilogue=epilogue,
        )
    if path == "fused_staged":
        return staged_fused_hbm_bytes(
            n, itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block, kahan=kahan,
        )
    if path == "sumsq_staged":
        return staged_sumsq_hbm_bytes(
            n, itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block,
        )
    if path == "hier":
        return hier_hbm_bytes(
            n, itemsize, m=m, tiles_per_block=tiles_per_block
        )
    if path == "hier_moments":
        return hier_moments_hbm_bytes(
            n, itemsize, m=m, tiles_per_block=tiles_per_block
        )
    if path == "segmented":
        return segmented_hbm_bytes(
            fetched_elems if fetched_elems is not None else n,
            itemsize, segments=segments + census, tiles=tiles, m=m,
            num_cores=num_cores,
        )
    if path == "parts":
        return parts_hbm_bytes(n * itemsize, segments=segments + census)
    if path == "scan":
        return scan_hbm_bytes(
            n, itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block,
        )
    if path == "scan_staged":
        return staged_scan_hbm_bytes(
            n, itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block,
        )
    if path == "parts_2trip":
        # comparison model for the pre-epilogue optimizer step: the norm
        # launch streams the grads once, the host finishes sqrt/min, and
        # the elementwise update then reads every grad byte AGAIN -- two
        # HBM trips per leaf where the epilogue fork + fused second moment
        # need one
        base = parts_hbm_bytes(n * itemsize, segments=segments + census)
        return HbmTraffic(
            kernel_read=base.kernel_read + n * itemsize,
            kernel_write=base.kernel_write,
        )
    raise ValueError(f"unknown hbm_bytes path {path!r}")


# ----------------------------- TPU extension --------------------------------

@dataclasses.dataclass(frozen=True)
class ReductionRoofline:
    """Three-term roofline for reducing n elements of `bytes_per_el` on TPU."""

    n: int
    bytes_per_el: int
    hbm_s: float      # time to stream the operand from HBM once
    vpu_s: float      # time for a VPU tree reduction, operand in VMEM
    mxu_s: float      # time for the paper's MMA reduction, operand in VMEM

    @property
    def cold_bound_s(self) -> float:
        """A cold reduction can never beat the stream time."""
        return max(self.hbm_s, self.mxu_s)

    @property
    def fused_speedup(self) -> float:
        """VPU/MXU time ratio for a VMEM-resident (fused) reduction. ~0.3 at
        m=128 on v5e in this model: the MXU path is slower on raw time -- its
        value is that it runs on the otherwise-idle MXU, freeing VPU cycles
        for the surrounding kernel (the contended unit in norm/softmax
        fusions). A model ratio, not a measurement."""
        return self.vpu_s / self.mxu_s if self.mxu_s else float("inf")

    @property
    def mxu_bandwidth_neutral(self) -> bool:
        """True when the MMA encoding adds no wall time over the HBM stream
        bound for cold operands (the common case at m=128/bf16)."""
        return self.mxu_s <= self.hbm_s * 1.15


def tpu_reduction_roofline(
    n: int, bytes_per_el: int = 2, device_kind: str = DEFAULT_KIND
) -> ReductionRoofline:
    peaks = peaks_for(device_kind)
    hbm_s = n * bytes_per_el / peaks.hbm_bytes_per_s
    # VPU: streaming tree reduction retires VPU_LANES FMA lanes/cycle plus a
    # log-depth lane-fold tail. Peak VPU ~= 2 * VPU_LANES * CLOCK.
    vpu_cycles = n / VPU_LANES + 10 * math.log2(max(n, 2))
    vpu_s = vpu_cycles / peaks.clock_hz
    # MXU, *throughput* model: each 2-MMA pass over k tiles of m^2=16384
    # elements issues 2k matmuls of 2*m^3 FLOPs, pipelined at chip peak.
    # Per element that is 4m FLOPs; at m=128 and 197 TF/s the MXU reduction
    # sits within ~1.1x of the HBM stream time for cold bf16 operands while
    # leaving the VPU idle, so in this model the MMA encoding is
    # bandwidth-neutral for cold data and a VPU offload for fused
    # (VMEM-resident) reductions.
    group = MXU_DIM * MXU_DIM
    mma_flops, remaining = 0.0, n
    while remaining > 1:
        k = -(-remaining // group)
        mma_flops += 2 * k * 2 * MXU_DIM**3
        remaining = k
    mxu_s = mma_flops / peaks.bf16_flops
    return ReductionRoofline(n, bytes_per_el, hbm_s, vpu_s, mxu_s)


# --------------------- step-model table (benchmarks) ------------------------

def model_table(ns=(2**10, 2**16, 2**20, 2**26, 2**30), ms=(2, 4, 16, 128)):
    """Rows of (n, m, T_tc, T_classic, S_model) for the paper's tables."""
    rows = []
    for n in ns:
        for m in ms:
            rows.append(
                dict(
                    n=n,
                    m=m,
                    t_tc=t_tensor_core(n, m),
                    t_classic=t_classic(n),
                    speedup=t_classic(n) / max(t_tensor_core(n, m), 1e-12),
                    speedup_closed_form=speedup_model(m),
                )
            )
    return rows
