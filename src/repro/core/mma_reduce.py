"""The paper's MMA-based parallel reduction, as a pure-JAX algorithm.

Carrasco, Vega & Navarro (2019) encode the reduction of ``n`` numbers as a
hierarchy of matrix-multiply-accumulate (MMA) operations:

  MMA 1:  ``D  = A @ 1 + 0``   (eq. 9-10)  -- row-sums of an m x m data tile,
                                              replicated across columns.
  MMA 2:  ``D' = 1 @ D + 0``   (eq. 11-12) -- column-sum of the row-sums; every
                                              entry of D' is the group total.

Each 2-MMA pass collapses a group of ``m**2`` elements to one value; the
recurrence ``R_tc(X) = R_tc(M(g_1), ..., M(g_k))`` (eq. 13) repeats until one
group remains, giving ``T_tc(n) = 5 * log_{m^2}(n)`` model steps (eq. 15-16).

On TPU the natural tile is the 128x128 MXU systolic pass (m = 128, one pass
reduces 16 384 elements); multiplications run in bf16 with f32 accumulation
(``preferred_element_type``), mirroring the tensor cores' fp16xfp16->fp32 mode.

This module is the *algorithmic* implementation (jnp only, runs anywhere and
differentiates); ``repro.kernels.mma_reduce`` is the Pallas TPU kernel with
explicit VMEM BlockSpec tiling that implements the same contract.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.common import mma

# Default linear MMA tile size. 128 is the TPU MXU systolic dimension; the
# paper uses m=16 (WMMA API tile) / m=4 (V100 hardware tile). Tests sweep all.
DEFAULT_M = 128


@dataclasses.dataclass(frozen=True)
class ReductionTrace:
    """Instrumentation record for one hierarchical reduction.

    ``levels``     -- number of 2-MMA passes executed (recursion depth).
    ``model_steps``-- cost in the paper's unit model: 5 per level (read, fill,
                      MMA, MMA, write); eq. (15).
    ``mma_ops``    -- total m x m MMA operations issued across all levels.
    ``n``, ``m``   -- problem size and tile size.

    Multi-core (striped Pallas kernels; defaults describe the serial jnp
    hierarchy, so existing constructors are unchanged):
    ``num_cores``       -- lanes of the ("parallel", "arbitrary") grid.
    ``lane_mma_ops``    -- main-stream MMAs issued PER LANE (concurrent).
    ``combine_mma_ops`` -- trailing collapse/flush MMAs (the serial tail).
    ``hbm_bytes``       -- modeled HBM traffic of the pass
                          (``cost_model.hbm_bytes``; 0 = not modeled). The
                          zero-copy kernels move n*itemsize + O(c m^2); the
                          traces are asserted against the model so kernel
                          geometry and traffic accounting cannot diverge.
    ``fallback``        -- "" when the pass ran its advertised zero-copy
                          route; otherwise the NAME of the documented
                          degradation taken. Currently emitted:
                          "ingest_f32" (the f64/int/bool pre-cast in
                          ``ops._ingest``). The two other documented
                          degradations never reach a traced launch: the
                          past-``PARTS_KERNEL_MAX`` packed-stream fallback
                          and the batched-row-moments dot both run as plain
                          jnp code in ``backends.py`` (no kernel pass, so
                          no trace) -- they are documented at their call
                          sites instead.
    ``census``          -- True when the pass also carried the in-kernel
                          NON-FINITE census (NaN/Inf counts riding the same
                          tiles; its extra f32 output slots are already
                          folded into ``hbm_bytes``, and its input bytes
                          are zero by construction).
    """

    n: int
    m: int
    levels: int
    mma_ops: int
    num_cores: int = 1
    lane_mma_ops: int = 0
    combine_mma_ops: int = 0
    hbm_bytes: int = 0
    fallback: str = ""
    census: bool = False

    @property
    def model_steps(self) -> int:
        return 5 * self.levels

    @property
    def predicted_steps(self) -> float:
        """Paper eq. (16): T_tc(n) = 5 log_{m^2}(n)."""
        return 5.0 * math.log(max(self.n, 2), self.m**2)


def _two_mma_pass(
    tiles: jax.Array, m: int, compute_dtype: jnp.dtype, accum_dtype: jnp.dtype
) -> jax.Array:
    """One 2-MMA pass over a batch of m x m tiles: (k, m, m) -> (k,).

    Faithful to eqs. (9)-(12): B and the second-pass A are *all-ones m x m
    matrices*; we deliberately compute the full redundant product (the paper
    argues full-matrix MMA beats filtering a single column, and on the MXU the
    128 result lanes are produced by the same systolic pass anyway) and then
    read entry (0, 0).
    """
    ones = jnp.ones((m, m), dtype=compute_dtype)
    a = tiles.astype(compute_dtype)
    # MMA 1: D = A x 1 + 0, accumulated at f32 like the tensor-core D matrix.
    d = mma(
        a,
        jnp.broadcast_to(ones, a.shape),
        (((2,), (1,)), ((0,), (0,))),
        accum_dtype,
    )
    # MMA 2: D' = 1 x D + 0. D re-enters at compute precision (the hardware
    # multiplies at bf16/fp16); accumulation stays f32.
    d = d.astype(compute_dtype)
    d2 = mma(
        jnp.broadcast_to(ones, d.shape),
        d,
        (((2,), (1,)), ((0,), (0,))),
        accum_dtype,
    )
    return d2[:, 0, 0]


def mma_sum(
    x: jax.Array,
    *,
    m: int = DEFAULT_M,
    compute_dtype: jnp.dtype | None = None,
    accum_dtype: jnp.dtype = jnp.float32,
    trace: list[ReductionTrace] | None = None,
) -> jax.Array:
    """Reduce ``x`` to a scalar with the paper's hierarchical 2-MMA algorithm.

    The driver is the recurrence of eq. (13): split into groups of ``m**2``,
    reduce each group with two MMAs, recurse on the partials until one group
    is left. Group padding is with zeros (additive identity).

    Args:
      x: array of any shape; reduced over all elements.
      m: linear MMA tile size (>= 2). 128 = TPU MXU; 16 = WMMA; 4 = V100 HW.
      compute_dtype: dtype fed to the MMA multipliers (bf16 mimics hardware;
        default: bf16 for floating inputs of width <= 32, else x.dtype).
      accum_dtype: accumulator dtype (f32, like tensor cores' D matrix).
      trace: optional list; if given, a ReductionTrace is appended (Python
        metadata only -- does not affect the compiled computation).

    Returns:
      Scalar of ``accum_dtype``.
    """
    if m < 2:
        raise ValueError(f"m must be >= 2 (paper section V); got {m}")
    if compute_dtype is None:
        if jnp.issubdtype(x.dtype, jnp.floating):
            compute_dtype = jnp.bfloat16 if x.dtype != jnp.float64 else jnp.float64
        else:
            compute_dtype = jnp.float32
    group = m * m
    flat = x.reshape(-1).astype(accum_dtype)
    if flat.size == 0:
        # Empty reduction: the additive identity, zero levels (a degenerate
        # pad would otherwise loop on a (0, m, m) tile batch).
        if trace is not None:
            trace.append(ReductionTrace(n=0, m=m, levels=0, mma_ops=0))
        return jnp.zeros((), accum_dtype)
    levels = 0
    mma_ops = 0
    n0 = flat.size
    while flat.size > 1:
        k = -(-flat.size // group)  # ceil division: number of m^2 groups
        pad = k * group - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        tiles = flat.reshape(k, m, m)
        flat = _two_mma_pass(tiles, m, compute_dtype, accum_dtype)
        levels += 1
        mma_ops += 2 * k
    if trace is not None:
        trace.append(ReductionTrace(n=n0, m=m, levels=levels, mma_ops=mma_ops))
    return flat.reshape(())


def mma_mean(x: jax.Array, **kw) -> jax.Array:
    return mma_sum(x, **kw) / x.size


def classic_tree_sum(
    x: jax.Array,
    *,
    accum_dtype: jnp.dtype = jnp.float32,
    trace: list[ReductionTrace] | None = None,
) -> jax.Array:
    """The classic pairwise GPU reduction (Nickolls/Harris), the paper's baseline.

    ``x[i] += x[i + p/2]`` halving passes; T(n) = 4 log2(n) in the paper's
    cost model (read, read, add, write per level). Implemented so that the
    summation *tree* matches the CUDA kernel's exactly (power-of-two halving
    with zero padding), which matters for the precision study.
    """
    flat = x.reshape(-1).astype(accum_dtype)
    n0 = flat.size
    if n0 == 0:
        if trace is not None:
            trace.append(ReductionTrace(n=0, m=2, levels=0, mma_ops=0))
        return jnp.zeros((), accum_dtype)
    size = 1 << max(0, (n0 - 1).bit_length())
    if size != flat.size:
        flat = jnp.pad(flat, (0, size - flat.size))
    levels = 0
    while flat.size > 1:
        half = flat.size // 2
        flat = flat[:half] + flat[half:]
        levels += 1
    if trace is not None:
        # m=2 so that model_steps/levels line up with the 4-per-level model;
        # mma_ops is 0 -- the classic algorithm issues none.
        trace.append(ReductionTrace(n=n0, m=2, levels=levels, mma_ops=0))
    return flat.reshape(())


# ---------------------------------------------------------------------------
# Row-wise (last-axis) reductions: the framework-facing primitives.
#
# Eq. (9)'s first MMA *is* a row-sum: D = X @ 1 puts sum_j X[i, j] in every
# column of row i. On the MXU a (R, L) x (L, 128) product costs the same
# systolic pass as any narrower RHS (lane width is 128), so the redundant
# columns are architecturally free -- this is the paper's "full MMA beats
# filtering" argument transplanted to TPU.
# ---------------------------------------------------------------------------


def _ones_rhs(length: int, width: int, dtype: jnp.dtype) -> jax.Array:
    return jnp.ones((length, width), dtype=dtype)


def row_sum_mma(
    x: jax.Array,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    accum_dtype: jnp.dtype = jnp.float32,
    mxu_width: int = 128,
) -> jax.Array:
    """Sum over the last axis via a single all-ones MMA (paper eq. 9).

    (..., L) -> (...,): computes ``X @ ones(L, mxu_width)`` with f32
    accumulation and reads lane 0.
    """
    length = x.shape[-1]
    ones = _ones_rhs(length, mxu_width, compute_dtype)
    out = mma(
        x.astype(compute_dtype),
        ones,
        (((x.ndim - 1,), (0,)), ((), ())),
        accum_dtype,
    )
    return out[..., 0]


def row_moments_mma(
    x: jax.Array,
    *,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    accum_dtype: jnp.dtype = jnp.float32,
    mxu_width: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """(sum, sum-of-squares) over the last axis, both as all-ones MMAs.

    These two moments are exactly the statistics LayerNorm / RMSNorm need;
    this is the framework's normalization reduction path. The square is an
    elementwise (VPU) op; both reductions ride the MXU.
    """
    length = x.shape[-1]
    ones = _ones_rhs(length, mxu_width, compute_dtype)
    xc = x.astype(compute_dtype)
    stacked = jnp.stack([xc, (x.astype(accum_dtype) ** 2).astype(compute_dtype)], 0)
    out = mma(
        stacked,
        ones,
        (((stacked.ndim - 1,), (0,)), ((), ())),
        accum_dtype,
    )
    return out[0, ..., 0], out[1, ..., 0]


# ---------------------------------------------------------------------------
# Differentiable public entry point. The VJP of a sum is a broadcast of the
# cotangent, independent of the reduction schedule, so we can give the
# hierarchical algorithm an exact, cheap gradient.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def mma_sum_diff(x: jax.Array, m: int = DEFAULT_M) -> jax.Array:
    return mma_sum(x, m=m)


def _mma_sum_fwd(x, m):
    # zero-size residual carries shape+dtype without retaining x
    return mma_sum(x, m=m), jnp.zeros((0,) + x.shape, x.dtype)


def _mma_sum_bwd(m, res, g):
    return (jnp.broadcast_to(g, res.shape[1:]).astype(res.dtype),)


mma_sum_diff.defvjp(_mma_sum_fwd, _mma_sum_bwd)


def mma_sum_axis(
    x: jax.Array, axis: int | Sequence[int], *, m: int = DEFAULT_M, **kw
) -> jax.Array:
    """Reduce selected axes with the MMA path, keeping the rest batched.

    Moves the reduced axes last, flattens them, and applies the hierarchical
    row reduction (single MMA pass while the reduced extent <= m^2, recursing
    via mma_sum semantics otherwise).
    """
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    axes = tuple(a % x.ndim for a in axes)
    keep = tuple(a for a in range(x.ndim) if a not in axes)
    xt = jnp.transpose(x, keep + axes)
    batch_shape = xt.shape[: len(keep)]
    red = int(math.prod(xt.shape[len(keep):])) if axes else 1
    flat = xt.reshape(batch_shape + (red,))
    out = row_sum_mma(flat, **kw)
    # Hierarchical: row_sum_mma accumulates exactly once over the reduced
    # extent; for very long extents the Pallas kernel tiles it, but the jnp
    # algorithm can rely on XLA's single dot. Cost model still counts it as
    # ceil(log_{m^2}) levels in benchmarks (see bench_steps).
    return out


def global_norm_sq_mma(tree, *, m: int = DEFAULT_M) -> jax.Array:
    """Sum of squares over a whole pytree via the MMA path.

    Thin delegate: the sharding-critical per-leaf last-axis reduction lives
    in ``repro.reduce.reduce_tree`` (one implementation; see its docstring
    for the 169 GB all-gather rationale). Kept so pre-engine callers keep
    one numerical behavior with the engine path.
    """
    from repro.reduce import reduce_tree  # deferred: engine imports this module

    return reduce_tree(tree, kind="sumsq", backend="mma_jnp", m=m)
