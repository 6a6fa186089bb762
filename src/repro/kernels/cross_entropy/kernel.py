"""Fused cross-entropy over huge vocabularies (up to 256 k), MMA reductions.

The CE loss is the longest row-reduction in an LM training step: logsumexp
over the vocabulary axis. The kernel streams (block_rows, block_v) logit
tiles through VMEM with an online logsumexp (same algebra as flash
attention's softmax): running max on the VPU, running denominator
``l += sum exp(s - m)`` as an all-ones MMA (the paper's eq. 9), and the
label logit gathered with a one-hot *matmul* -- reduction-as-MMA applied to
indexing, so the gather also rides the MXU instead of a scatter/gather unit.

Never materializes the (R, V) softmax; peak VMEM is one logits tile + three
(block_rows, 1) carries. Per-row values travel as (rows, 1) columns, never
rank-1 vectors: the chip blocks every operand in (8, 128) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

NEG = -1e30


def _mma_row_sum(mat: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    d = mat.shape[-1]
    ones = jnp.ones((d, common.MXU), compute_dtype)
    return common.mma(
        mat.astype(compute_dtype), ones, (((1,), (0,)), ((), ()))
    )[:, :1]


def _ce_kernel(
    logits_ref,   # (R, BV)
    labels_ref,   # (R, 1)
    o_ref,        # (R, 1)
    m_ref,        # (R, 1) scratch: running max
    l_ref,        # (R, 1) scratch: running denominator
    pick_ref,     # (R, 1) scratch: label logit
    *,
    vocab: int,
    block_v: int,
):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        pick_ref[...] = jnp.zeros_like(pick_ref)

    s = logits_ref[...].astype(jnp.float32)  # (R, BV)
    v0 = iv * block_v
    vpos = v0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = vpos < vocab
    s = jnp.where(valid, s, NEG)

    # label gather as a one-hot MMA: onehot (R, BV) . s -> per-row picked
    onehot = (vpos == labels_ref[...]) & valid
    pick_ref[...] += _mma_row_sum(jnp.where(onehot, s, 0.0), jnp.float32)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * jnp.exp(m_old - m_new) + _mma_row_sum(p)
    m_ref[...] = m_new

    @pl.when(iv == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30)) - pick_ref[...]


def cross_entropy_call(
    logits: jax.Array,   # (R, V)
    labels: jax.Array,   # (R,) int32
    *,
    block_rows: int = 8,
    block_v: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = common.resolve_interpret(interpret)
    rows, vocab = logits.shape
    block_v = min(block_v, common.round_up(vocab, common.LANES))
    r = min(block_rows, max(rows, 1))
    rp = common.round_up(rows, r)
    vp = common.round_up(vocab, block_v)
    logits_p = common.pad_to(common.pad_to(logits, rp, axis=0), vp, axis=1)
    labels_p = common.pad_to(labels.astype(jnp.int32), rp, axis=0)[:, None]
    kernel = functools.partial(_ce_kernel, vocab=vocab, block_v=block_v)
    out = pl.pallas_call(
        kernel,
        grid=(rp // r, vp // block_v),
        in_specs=[
            pl.BlockSpec((r, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((r, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((r, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        scratch_shapes=[
            common.vmem_scratch((r, 1), jnp.float32),
            common.vmem_scratch((r, 1), jnp.float32),
            common.vmem_scratch((r, 1), jnp.float32),
        ],
        compiler_params=common.compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(logits_p, labels_p)
    return out[:rows, 0]
