"""Fused normalization kernels whose statistics ride the MXU.

This is the highest-leverage TPU landing spot for the paper's idea: norm
statistics are per-row arithmetic reductions executed on *every* token of
*every* layer, and in a fused kernel the operand is already in VMEM. The
paper's first MMA (eq. 9, ``D = X @ 1``) computes exactly the row sums; the
row sums of ``X*X`` give the second moment. Both reductions are issued as
all-ones matmuls (f32 accumulation) so the VPU stays free for the square,
rsqrt and scale work, and the MXU -- idle during a conventional norm -- does
the reduction sweep.

The MXU's 128-lane output means an (R, d) x (d, 128) ones-product costs the
same systolic pass as a width-1 product; we read lane 0. (The paper's
"process the full matrix rather than filter a column" argument, literally.)

Block geometry: rows are tiled (block_rows, d) with d kept whole per block
(d <= ~8k => <= 8k*2B*block_rows bytes; block_rows=256 at d=6144/bf16 is
~3 MiB -- inside VMEM with room for the two ones operands and output).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

MXU = common.MXU


def _mma_row_sum(mat: jax.Array, compute_dtype) -> jax.Array:
    """(R, d) -> (R,) row sums via one all-ones MMA, f32 accumulation."""
    d = mat.shape[-1]
    ones = jnp.ones((d, MXU), compute_dtype)
    out = common.mma(mat.astype(compute_dtype), ones, (((1,), (0,)), ((), ())))
    return out[:, 0]


def rmsnorm_kernel(x_ref, gamma_ref, o_ref, *, eps, compute_dtype):
    x = x_ref[...].astype(jnp.float32)  # (R, d)
    d = x.shape[-1]
    sumsq = _mma_row_sum(x * x, compute_dtype)  # MMA 1 on MXU
    rstd = jax.lax.rsqrt(sumsq / d + eps)  # VPU
    o_ref[...] = (x * rstd[:, None] * gamma_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def layernorm_np_kernel(x_ref, o_ref, *, eps, compute_dtype):
    """Non-parametric LayerNorm (OLMo): both moments via MMA, no affine."""
    x = x_ref[...].astype(jnp.float32)
    d = x.shape[-1]
    s = _mma_row_sum(x, compute_dtype)        # MMA: sum
    ss = _mma_row_sum(x * x, compute_dtype)   # MMA: sum of squares
    mu = s / d
    var = jnp.maximum(ss / d - mu * mu, 0.0)
    o_ref[...] = ((x - mu[:, None]) * jax.lax.rsqrt(var + eps)[:, None]).astype(
        o_ref.dtype
    )


def _call_rows(kernel, x, extra_inputs, extra_specs, *, block_rows, interpret):
    interpret = common.resolve_interpret(interpret)
    rows, d = x.shape
    r = min(block_rows, max(rows, 1))
    rpad = common.round_up(rows, r)
    x = common.pad_to(x, rpad, axis=0)
    out = pl.pallas_call(
        kernel,
        grid=(rpad // r,),
        in_specs=[pl.BlockSpec((r, d), lambda i: (i, 0))] + extra_specs,
        out_specs=pl.BlockSpec((r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, d), x.dtype),
        interpret=interpret,
    )(x, *extra_inputs)
    return out[:rows]


def rmsnorm(
    x: jax.Array,
    gamma: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    compute_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused RMSNorm over the last axis of a (rows, d) array."""
    kernel = functools.partial(rmsnorm_kernel, eps=eps, compute_dtype=compute_dtype)
    gspec = pl.BlockSpec((x.shape[-1],), lambda i: (0,))
    return _call_rows(
        kernel, x, [gamma], [gspec], block_rows=block_rows, interpret=interpret
    )


def layernorm_np(
    x: jax.Array,
    *,
    eps: float = 1e-5,
    block_rows: int = 256,
    compute_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> jax.Array:
    kernel = functools.partial(
        layernorm_np_kernel, eps=eps, compute_dtype=compute_dtype
    )
    return _call_rows(kernel, x, [], [], block_rows=block_rows, interpret=interpret)
