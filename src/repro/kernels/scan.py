"""Triangular-MMA prefix sums (the scan op class).

Dakkak et al., "Accelerating Reduction and Scan Using Tensor Core Units"
(PAPERS.md), extend the source paper's encoding from reduction to SCAN by
swapping the all-ones MMA operands for triangular ones. Per (m, m) tile X
(row-major element order, so flat index p = i*m + j):

    T1 = X @ J    (J all-ones)      -> T1[i, :] broadcasts row i's sum
    D  = Ls @ T1  (Ls strict lower) -> D[i, :] = sum of rows before i
    R  = X @ U    (U upper-tri)     -> R[i, j] = row i's prefix through j
    P  = R + D                      -> P[i, j] = tile prefix through p

with U strictly-upper for EXCLUSIVE prefixes, and the tile's total read
off the last corner (D + T1)[m-1, m-1]. Three MMAs per tile replace the
paper's two; everything else -- flat 1D BlockSpecs, native-dtype in-VMEM
cast, ``broadcasted_iota`` tail masking, ``stripe_geometry`` -- is the
PR-4/5 reduction machinery reused verbatim.

Two-level scheme across tiles: the in-kernel f32 carry chain folds tile
totals strictly left to right, so block b's carry is the SAME fixed-order
fold at every core count. Multi-core lanes own CONTIGUOUS block ranges (a
scan is order-dependent; the reduction kernels' striping would interleave
carries) and each lane REBUILDS its incoming carry by re-streaming the
blocks before its range -- two MMAs per re-streamed tile (T1, D; no R, no
output write) -- rather than waiting on a cross-lane handoff. That is the
Dakkak decoupled trade: O(n) redundant read bandwidth buys a combine-free
scan whose output is bitwise identical at num_cores in {1, 2, 4, ...}.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cost_model
from repro.kernels import common
from repro.kernels.mma_reduce.kernel import _load_tiles, flat_operand


@dataclasses.dataclass(frozen=True)
class ScanTrace:
    """Python-side instrumentation for one scan dispatch (the scan analogue
    of ``core.mma_reduce.ReductionTrace``): geometry + modeled MMA/byte
    counts, appended to the caller's ``trace`` list at trace time."""

    n: int
    m: int
    num_cores: int = 1
    mma_ops: int = 0          # chip-wide MMAs (cost_model.ScanMmaOps.total)
    lane_mma_ops: int = 0     # one lane's owned-stripe MMAs
    carry_mma_ops: int = 0    # the worst lane's carry-rebuild MMAs
    hbm_bytes: int = 0        # modeled total traffic (incl. refetch)
    inclusive: bool = True
    fallback: str = ""        # "" (zero-copy) or "ingest_f32"


def _matmul(a, b):
    """Plain (m, m) @ (m, m) with f32 accumulation -- every scan MMA (the
    f32 carry-down ``lower @ t1`` at full f32 width, ``common.mma``)."""
    return common.mma(a, b, (((1,), (0,)), ((), ())))


def scan_kernel(
    x_ref, o_ref, carry_ref, *,
    n, r, m, bpl, compute_dtype, out_dtype, inclusive, needs_mask,
):
    """One grid step of the striped triangular scan.

    Grid is (c, c*bpl): lane ci walks EVERY block index j, in three phases.
      j <  start: carry rebuild -- fold block totals into the f32 carry
                  (2 MMAs/tile; nothing written).
      j in [start, end): owned stripe -- same totals fold, plus the R MMA
                  and the (P + carry) output write.
      j >= end:   dwell -- the index maps clamp to the last owned block and
                  the body writes nothing.
    The carry scratch is reset at j == 0, so each lane's fold starts from
    the true zero and replays the identical left-to-right chain -- the
    whole bitwise-across-cores argument lives in that one invariant.
    Crucially the tile total is ALWAYS read off (D + T1)[m-1, m-1], never
    off R, so carry-phase and owned-phase folds of the same block are the
    same f32 ops in the same order.
    """
    ci = pl.program_id(0)
    j = pl.program_id(1)
    start = ci * bpl
    end = start + bpl
    base = jnp.minimum(j, end - 1) * (r * m * m)

    @pl.when(j == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    tiles = _load_tiles(x_ref, base, n, r, m, compute_dtype, needs_mask)
    ones = common.ones_mma(m, compute_dtype)
    lower = common.tril_mma(m, jnp.float32, k=-1)
    upper = common.triu_mma(m, compute_dtype, k=0 if inclusive else 1)

    # the carry is a (1, 1) tile, never a scalar: the chip stores no
    # scalar into VMEM, and the tile form broadcasts into the output add.
    running = carry_ref[...]
    corner = slice(m - 1, m)
    carries, downs = [], []
    for t in range(r):
        t1 = _matmul(tiles[t], ones)
        down = _matmul(lower, t1)
        carries.append(running)
        downs.append(down)
        running = running + (down[corner, corner] + t1[corner, corner])

    active = jnp.logical_and(j >= start, j < end)

    @pl.when(active)
    def _emit():
        outs = []
        for t in range(r):
            rowpref = _matmul(tiles[t], upper)
            outs.append(rowpref + downs[t] + carries[t])
        flat = jnp.stack(outs).reshape(r * m, m).astype(out_dtype)
        o_ref[...] = flat.reshape(r * m * m)

    @pl.when(j < end)
    def _advance():
        carry_ref[...] = running


def scan_geometry(n: int, m: int, tiles_per_block: int, num_cores: int):
    """(r, c, blocks_per_lane, padded_tiles) for a scan over n elements --
    ``cost_model.stripe_geometry`` verbatim, with the lane partition
    reinterpreted as contiguous ranges instead of stripes."""
    tiles = max(1, common.ceil_div(n, m * m))
    return cost_model.stripe_geometry(tiles, tiles_per_block, num_cores)


def mma_scan_pallas(
    x: jax.Array,
    *,
    inclusive: bool = True,
    m: int = common.MXU,
    tiles_per_block: int = 8,
    num_cores: int = 1,
    compute_dtype=None,
    interpret: bool | None = None,
    trace: list | None = None,
) -> jax.Array:
    """Single-launch triangular-MMA cumsum of a 1D (or flattened) operand.

    Streams the caller's buffer once at native dtype (non-native ingests
    fall back to one documented f32 pre-cast, like ``ops._ingest``), writes
    the full block-padded prefix array in the storage dtype, and slices it
    back to n -- one ``pallas_call``, no staging, no host combine.
    ``compute_dtype=None`` scans at the ingest dtype itself (an f32 operand
    scans at f32; see the ScanPlan contract -- prefix CONSUMERS read every
    partial result, so the reduce path's default bf16 demotion would be a
    visible precision change, not an internal one).
    """
    flat = x.reshape(-1)
    fallback = ""
    if not common.native_ingest_dtype(flat.dtype):
        flat = flat.astype(jnp.float32)
        fallback = "ingest_f32"
    n = flat.size
    cd = jnp.dtype(flat.dtype if compute_dtype is None else compute_dtype)
    if n == 0:
        if trace is not None:
            trace.append(ScanTrace(n=0, m=m, inclusive=inclusive))
        return jnp.zeros(x.shape, x.dtype)
    r, c, bpl, tpad = scan_geometry(n, m, tiles_per_block, num_cores)
    needs_mask = tpad * m * m != n
    if trace is not None:
        ops_model = cost_model.scan_mma_ops(
            n, m=m, num_cores=num_cores, tiles_per_block=tiles_per_block
        )
        bytes_model = cost_model.scan_hbm_bytes(
            n, flat.dtype.itemsize, m=m, num_cores=num_cores,
            tiles_per_block=tiles_per_block,
        )
        trace.append(ScanTrace(
            n=n, m=m, num_cores=c, mma_ops=ops_model.total,
            lane_mma_ops=ops_model.lane_scan,
            carry_mma_ops=ops_model.carry_worst,
            hbm_bytes=bytes_model.total, inclusive=inclusive,
            fallback=fallback,
        ))
    block = r * m * m
    kernel = functools.partial(
        scan_kernel,
        n=n, r=r, m=m, bpl=bpl, compute_dtype=cd, out_dtype=flat.dtype,
        inclusive=inclusive, needs_mask=needs_mask,
    )
    operand, in_spec = flat_operand(
        flat, block,
        lambda ci, j, bpl=bpl: (jnp.minimum(j, (ci + 1) * bpl - 1),),
    )
    out = pl.pallas_call(
        kernel,
        grid=(c, c * bpl),
        in_specs=[in_spec],
        out_specs=pl.BlockSpec(
            (block,),
            lambda ci, j, bpl=bpl: (jnp.clip(j, ci * bpl, (ci + 1) * bpl - 1),),
        ),
        out_shape=jax.ShapeDtypeStruct((tpad * m * m,), flat.dtype),
        scratch_shapes=[common.vmem_scratch((1, 1), jnp.float32)],
        compiler_params=common.compiler_params(("parallel", "arbitrary")),
        interpret=common.resolve_interpret(interpret),
    )(operand)
    return out[:n].reshape(x.shape).astype(x.dtype)


def mma_scan_jnp(
    x: jax.Array,
    *,
    inclusive: bool = True,
    m: int = common.MXU,
    compute_dtype=None,
) -> jax.Array:
    """Triangular-einsum scan over the LAST axis, any rank -- the mma_jnp
    reference semantics and the batched delegate of the Pallas backend.

    Rows are chunked into (k, m) strips; one batched strip @ U einsum
    yields in-strip prefixes, and the strip carry is the exact f32 shifted
    cumsum of strip totals (never ``cumsum - x``, whose re-rounding breaks
    the exclusive contract). Same U-matrix algebra as the kernel, so the
    two agree wherever the einsum batching order does not re-associate --
    which the differential harness checks against the f64 oracle rather
    than bit-for-bit."""
    orig_dtype = x.dtype
    xf = x if common.native_ingest_dtype(x.dtype) else x.astype(jnp.float32)
    cd = jnp.dtype(xf.dtype if compute_dtype is None else compute_dtype)
    length = x.shape[-1]
    if length == 0:
        return jnp.zeros(x.shape, orig_dtype)
    k = common.ceil_div(length, m)
    chunks = common.pad_to(xf, k * m, axis=x.ndim - 1)
    chunks = chunks.reshape(x.shape[:-1] + (k, m)).astype(cd)
    upper = jnp.asarray(common.triu_tile(m, cd.name, 0 if inclusive else 1))
    rowpref = jnp.einsum(
        "...km,mn->...kn", chunks, upper, preferred_element_type=jnp.float32,
        precision=common.mma_precision(cd),
    )
    totals = rowpref[..., m - 1]
    if not inclusive:
        totals = totals + chunks[..., m - 1].astype(jnp.float32)
    carry = jnp.cumsum(totals, axis=-1)
    carry = jnp.concatenate(
        [jnp.zeros_like(carry[..., :1]), carry[..., :-1]], axis=-1
    )
    out = rowpref + carry[..., None]
    out = out.reshape(x.shape[:-1] + (k * m,))[..., :length]
    return out.astype(orig_dtype)
