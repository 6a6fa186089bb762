"""Cross-device continuation of the paper's reduction hierarchy.

Eq. (13)'s recurrence does not care whether a "group" is an MXU tile or a
mesh axis: after the on-chip MMA hierarchy collapses a shard to one partial,
the same recurrence runs across `model` -> `data` -> `pod` mesh axes. These
helpers are written for use *inside* ``jax.shard_map`` bodies (they take axis
names); the pjit'd model path lets GSPMD insert its own collectives, while
the optimizer's explicit reductions (global norm, compressed gradient
exchange) route through here.

Includes the distributed-optimization tricks required at 1000+ node scale:
  * bucketed ring all-reduce (ppermute) -- overlappable with compute,
  * int8 error-feedback compressed psum for the thin cross-pod hop,
  * hierarchical reduce ordered thick-pipe-first.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.mma_reduce import DEFAULT_M

shard_map = jax.shard_map


def shard_map_unchecked(body, *, mesh, in_specs, out_specs):
    """``shard_map`` with the replication checker off: pallas_call has no
    replication rule, so any per-device kernel launch inside a shard_map
    body trips it."""
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def hierarchical_psum(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """psum over mesh axes in order (innermost/thickest link first).

    One psum per axis keeps each collective on its own ICI ring instead of a
    single global ring whose latency is set by the thinnest (cross-pod) hop.
    """
    for ax in axis_names:
        x = lax.psum(x, ax)
    return x


def local_mma_then_psum(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    m: int = DEFAULT_M,
    backend: Optional[str] = None,
) -> jax.Array:
    """Full scalar reduction of a sharded array: the reduction engine on the
    local shard, then the mesh-axis rungs. This is eq. (13) spanning the
    whole machine. ``backend=None`` defers to the engine's process-wide
    default (``--reduce-backend`` / $REPRO_REDUCE_BACKEND / planner)."""
    # local import: repro.core's package init imports this module, while the
    # engine imports repro.core submodules -- deferring breaks the cycle.
    from repro import reduce as R

    local = R.reduce(x, kind="sum", backend=backend, m=m)
    return hierarchical_psum(local, axis_names)


# ------------------- deterministic fixed-order combine ----------------------


def axis_size_of(axis_name: str) -> int:
    """Static size of a bound mesh axis (a Python int inside shard_map)."""
    return lax.axis_size(axis_name)


def mesh_world_size(axis_names: Sequence[str]) -> int:
    """Product of the bound sizes of the given mesh axes."""
    world = 1
    for ax in axis_names:
        world *= int(axis_size_of(ax))
    return world


def fixed_order_combine(
    x: jax.Array, axis_names: Sequence[str], accum_dtype=None
) -> jax.Array:
    """Deterministic cross-device sum: all-gather the per-device partials,
    then fold them in static device order (rank 0 first) — the PR 3
    lane-combine lifted one level up, per eq. (13)'s recurrence.
    ``accum_dtype`` (default: ``x``'s) is the dtype of the fold: a bf16
    gradient gathers at its own width and sums in f32.

    Unlike ``lax.psum`` (whose reduction order is an implementation detail of
    the collective), every device runs the identical left fold over the
    identical gathered array, so the result is BIT-identical on every replica
    at any device count. Axes combine one at a time, innermost first, so each
    gather stays on its own mesh ring (thick-pipe-first, like
    ``hierarchical_psum``).
    """
    accum = x.dtype if accum_dtype is None else accum_dtype
    for ax in axis_names:
        g = lax.all_gather(x, ax, axis=0, tiled=False)
        p = g.shape[0]  # static: all_gather's gathered dim is the axis size
        acc = g[0].astype(accum)
        for i in range(1, p):
            acc = acc + g[i].astype(accum)
        x = acc
    return x


def _as_uint_bits(x: jax.Array) -> jax.Array:
    """Reinterpret floats as same-width unsigned ints so equality compares
    bit patterns (NaN-safe: NaN != NaN as floats, but its bits are its bits).
    """
    if jnp.issubdtype(x.dtype, jnp.floating):
        width = jnp.dtype(x.dtype).itemsize * 8
        return lax.bitcast_convert_type(x, jnp.dtype(f"uint{width}"))
    return x


def replica_bits_agree(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Replicated scalar bool: True iff ``x``'s BIT pattern is identical on
    every device along the given axes (floats compared as raw bits, so NaN
    payloads and last-ulp drift both count as disagreement). Because every
    device gathers and compares the same set, the verdict itself is
    replica-invariant — a guard can fold it into the skip decision without
    introducing divergence of its own."""
    bits = _as_uint_bits(x)
    agree = jnp.bool_(True)
    for ax in axis_names:
        g = lax.all_gather(bits, ax, axis=0, tiled=False)
        agree = agree & jnp.all(g == g[0])
    return agree


def census_agreement(
    row: jax.Array, axis_names: Sequence[str]
) -> tuple[jax.Array, jax.Array]:
    """Combine an additive census/statistic row deterministically AND verify
    every replica arrived at the same bits.

    Returns ``(combined, agree)``: ``combined`` is
    ``fixed_order_combine(row, axis_names)``; ``agree`` is
    ``replica_bits_agree(combined, axis_names)`` — True everywhere unless a
    replica's fold desynced (different shard contents, a nondeterministic
    wire reduction), in which case it flips to False on EVERY device.
    """
    combined = fixed_order_combine(row, axis_names)
    return combined, replica_bits_agree(combined, axis_names)


# ----------------------------- ring all-reduce ------------------------------


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bucketed ring all-reduce built from ppermute: reduce-scatter pass then
    all-gather pass, 2(P-1) hops, each hop moving |x|/P bytes.

    Written explicitly (rather than lax.psum) so the scheduler can overlap
    the per-hop sends with unrelated compute, and so the compressed variant
    below can quantize the wire format per hop.
    """
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    idx = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(p, -1)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def rs_step(t, chunks):
        # each rank accumulates into chunk (idx - t - 1) which it just received
        send_ix = (idx - t) % p
        recv_ix = (idx - t - 1) % p
        sent = lax.ppermute(chunks[send_ix], axis_name, perm)
        return chunks.at[recv_ix].add(sent)

    chunks = lax.fori_loop(0, p - 1, rs_step, chunks)

    def ag_step(t, chunks):
        send_ix = (idx - t + 1) % p
        recv_ix = (idx - t) % p
        sent = lax.ppermute(chunks[send_ix], axis_name, perm)
        return chunks.at[recv_ix].set(sent)

    chunks = lax.fori_loop(0, p - 1, ag_step, chunks)
    out = chunks.reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(x.shape)


# ----------------------- compressed (int8 EF) psum ---------------------------


def compressed_psum(
    x: jax.Array, axis_name: str, err: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """int8 error-feedback all-reduce for the thin cross-pod hop.

    Protocol: add carried error, agree on a shared scale via pmax, quantize
    to int8, psum in int32 (exact), dequantize. The local quantization
    residual is returned as the next step's error carry (EF-SGD; convergence
    preserved under standard assumptions). Wire bytes: 1/4 of f32, 1/2 of
    bf16 -- targeted at the `pod` axis whose link is the bottleneck.

    Returns (allreduced_f32, new_error_carry).
    """
    xf = x.astype(jnp.float32)
    if err is not None:
        xf = xf + err
    amax = lax.pmax(jnp.max(jnp.abs(xf)), axis_name)
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    new_err = xf - q.astype(jnp.float32) * scale
    qsum = lax.psum(q.astype(jnp.int32), axis_name)
    return qsum.astype(jnp.float32) * scale, new_err


def hierarchical_grad_reduce(
    grad: jax.Array,
    *,
    dense_axes: Sequence[str] = ("data",),
    compressed_axis: str | None = "pod",
    err: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Gradient all-reduce: exact psum on intra-pod axes, optional int8-EF on
    the cross-pod axis. Mean-normalization is left to the caller (it knows
    the global data-parallel degree)."""
    g = grad
    for ax in dense_axes:
        g = lax.psum(g, ax)
    if compressed_axis is not None:
        g, err = compressed_psum(g, compressed_axis, err)
    return g, err


def make_sharded_global_norm_sq(
    mesh: jax.sharding.Mesh,
    *,
    backend: Optional[str] = None,
    deterministic: bool = False,
):
    """Global sum-of-squares of a sharded pytree: per-shard reduction through
    the engine (``reduce_tree``'s last-axis MMA path keeps every dot on the
    local shard), then the mesh rungs -- the optimizer's clipping statistic
    at scale. ``deterministic=True`` routes the cross-device rung through
    the engine's ``mesh_axes=`` path (fixed-order combine) instead of
    ``psum``: bit-identical on every replica at any device count."""
    axis_names = tuple(mesh.axis_names)

    def body(tree):
        from repro import reduce as R  # deferred: see local_mma_then_psum

        if deterministic:
            return R.reduce_tree(
                tree, kind="sumsq", backend=backend, mesh_axes=axis_names
            )
        local = R.reduce_tree(tree, kind="sumsq", backend=backend)
        return hierarchical_psum(local, axis_names)

    return functools.partial(
        # the deterministic path may launch a per-device Pallas kernel,
        # which has no shard_map replication rule -- checker off there
        shard_map_unchecked if deterministic else shard_map,
        body,
        mesh=mesh,
        in_specs=None,  # caller supplies per-leaf specs
        out_specs=jax.sharding.PartitionSpec(),
    )
