"""`repro.reduce.reduce` -- the single entry point for every reduction.

One function, five kinds, any registered backend:

    reduce(x)                            # full sum, planner picks the path
    reduce(x, axis=-1, kind="moments")   # (sum, sumsq) rows for norm layers
    reduce(g, kind="norm2", backend="pallas_fused")
    reduce_many(arrays, kind="sumsq")    # N reductions, ONE launch
    reduce_tree(grads, kind="norm2")     # the optimizer's clipping statistic

Kinds are composed from the backend primitives, so each of them is available
on each backend.

``reduce_many`` is the multi-reduce entry point: N independent arrays are
reduced in a single backend pass (one ``segment_sum`` / one batched dot /
one multi-operand Pallas launch, by backend) instead of N separate
launches. On the kernel backends every array enters the launch as its OWN
operand in its native dtype (``sum_parts``) -- nothing is packed, cast, or
concatenated host-side; the jnp-level backends pack internally where XLA
fuses it. ``reduce_tree`` rides the same machinery for the optimizer's
whole-pytree clipping statistic.

Differentiation: backends built from jnp/dot code (``native_autodiff``)
differentiate natively in BOTH reverse and forward mode -- ``jax.jvp`` /
``jacfwd`` / ``hessian`` flow straight through, exactly as they did through
the pre-engine ``jnp.sum`` / ``row_sum_mma`` call sites. Only kernel-backed
full reductions (the Pallas backends) are wrapped in a ``jax.custom_vjp``
(the VJP of a sum is a broadcast of the cotangent, independent of the
reduction schedule); those support reverse mode only, like any Pallas
kernel. Batched row reductions run as native dots on every backend.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import common as _kcommon
from repro.reduce import backends as _backends
from repro.reduce.plan import (
    ReducePlan,
    norm_mesh_axes,
    plan_for,
    segmented_backend_for,
)

Axis = Union[None, int, Sequence[int]]

KINDS = ("sum", "mean", "sumsq", "norm2", "moments")

# sentinel for axis=(): numpy semantics -- reduce over NO axes (identity)
_NO_AXES = ()


def _normalize_axis(axis: Axis, ndim: int):
    """-> None (reduce everything), () (reduce nothing -- numpy semantics for
    an empty axis tuple), or a sorted tuple of unique non-negative axes."""
    if axis is None:
        return None
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    if not axes:
        return _NO_AXES
    out = []
    for a in axes:
        if ndim == 0:
            # numpy convention: 0-d arrays accept axis 0 / -1 (full reduce)
            if a not in (0, -1):
                raise ValueError(f"axis {a} out of range for 0-d array")
            continue
        if not -ndim <= a < ndim:
            raise ValueError(f"axis {a} out of range for ndim {ndim}")
        a %= ndim
        if a in out:
            raise ValueError(f"duplicate axis {a} in reduction axes")
        out.append(a)
    if ndim == 0 or len(out) == ndim:
        return None  # covers every axis: a full reduction
    return tuple(sorted(out))


def _backend_sum_all(backend, x, plan, prologue, epilogue=()):
    """sum_all with the prologue and (optional) epilogue chain; third-party
    backends that predate either keep working for every kind (host-side
    map degradation -- see backends.sum_all_with_epilogue)."""
    return _backends.sum_all_with_epilogue(backend, x, plan, prologue,
                                           epilogue)


def _kahan_sum_all(x, plan: ReducePlan, backend, prologue="identity") -> jax.Array:
    """Blocked compensated combine: backend-reduce each (prologue-mapped)
    block, Kahan the partials (Markidis-style refinement; orthogonal to the
    backend -- zero-padding stays exact because 0 is a fixed point of every
    prologue)."""
    from repro.core import precision as _precision

    flat = x.reshape(-1).astype(plan.accum_jnp)
    block = plan.kahan_block
    if flat.size <= block:
        return _backend_sum_all(backend, flat, plan, prologue)
    nblk = -(-flat.size // block)
    pad = nblk * block - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    partials = jax.lax.map(
        lambda b: _backend_sum_all(backend, b, plan, prologue),
        flat.reshape(nblk, block),
    )
    return _precision.kahan_sum(partials, dtype=plan.accum_jnp)


def _sum_all_impl(
    x: jax.Array,
    plan: ReducePlan,
    prologue: str = "identity",
    epilogue: tuple = (),
) -> jax.Array:
    backend = _backends.get_backend(plan.backend)
    accum = plan.accum_jnp
    if x.size == 0:
        return _kcommon.apply_epilogue(jnp.zeros((), accum), epilogue)
    if plan.precision == "kahan" and not backend.native_kahan:
        # Backends without an in-kernel carry get the blocked compensated
        # combine; native_kahan backends (pallas_fused) compensate inside
        # their single launch instead. The epilogue maps the compensated
        # total (it is a post-combine chain by definition).
        out = _kahan_sum_all(x, plan, backend, prologue)
        return _kcommon.apply_epilogue(out, epilogue).astype(accum)
    return _backend_sum_all(backend, x, plan, prologue, epilogue).astype(
        accum
    )


def _to_rows(x: jax.Array, axis):
    """Move the reduced axes last and flatten them: -> ((..., L), batch_shape)."""
    keep = tuple(a for a in range(x.ndim) if a not in axis)
    xt = jnp.transpose(x, keep + axis)
    batch_shape = xt.shape[: len(keep)]
    red = int(math.prod(xt.shape[len(keep):]))
    return xt.reshape(batch_shape + (red,)), batch_shape, red


def _row_plan(plan: ReducePlan) -> ReducePlan:
    if plan.precision == "kahan":
        # Row reductions have no serial combine to compensate; the policy
        # degrades gracefully to exact-accumulator multipliers.
        return plan.replace(compute_dtype=plan.accum_dtype)
    return plan


def _sum_axis_impl(x: jax.Array, axis, plan: ReducePlan) -> jax.Array:
    backend = _backends.get_backend(plan.backend)
    accum = plan.accum_jnp
    flat, batch_shape, red = _to_rows(x, axis)
    if red == 0 or 0 in batch_shape:
        return jnp.zeros(batch_shape, accum)
    return backend.sum_axis(flat, _row_plan(plan)).astype(accum)


def _moments_axis_impl(x: jax.Array, axis, plan: ReducePlan):
    backend = _backends.get_backend(plan.backend)
    accum = plan.accum_jnp
    flat, batch_shape, red = _to_rows(x, axis)
    if red == 0 or 0 in batch_shape:
        z = jnp.zeros(batch_shape, accum)
        return z, z
    s, ss = backend.moments_axis(flat, _row_plan(plan))
    return s.astype(accum), ss.astype(accum)


# Kernel-backed full reductions (no native autodiff) get the custom VJPs.
# The kernel input is now the RAW leaf (the prologue maps it in-kernel), so
# the cotangent is the prologue's chain rule, not always a plain broadcast:
#   identity: dx = g            (broadcast of the cotangent)
#   square:   dx = 2 x g        (d/dx x^2)
#   abs:      dx = sign(x) g
# square/abs therefore retain x as the residual; identity keeps the
# zero-size shape carrier. An epilogue chain prepends its own scalar
# chain rule: the cotangent flows through jax.vjp of apply_epilogue at the
# RAW reduced total (kept as a residual by the fwd pass, which computes
# the reduction epilogue-free and applies the chain host-side -- same jnp
# ops on the same f32 scalar as the in-kernel primal, so the values
# match bitwise while the chain stays differentiable).


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _ksum(
    x: jax.Array,
    plan: ReducePlan,
    prologue: str = "identity",
    epilogue: tuple = (),
) -> jax.Array:
    return _sum_all_impl(x, plan, prologue, epilogue)


def _ksum_fwd(x, plan, prologue, epilogue):
    res = x if prologue != "identity" else jnp.zeros((0,) + x.shape, x.dtype)
    if not epilogue:
        return _sum_all_impl(x, plan, prologue), (res, None)
    raw = _sum_all_impl(x, plan, prologue)
    return _kcommon.apply_epilogue(raw, epilogue), (res, raw)


def _ksum_bwd(plan, prologue, epilogue, resid, g):
    res, raw = resid
    if epilogue:
        _, vjp_fn = jax.vjp(
            lambda s: _kcommon.apply_epilogue(s, epilogue), raw
        )
        (g,) = vjp_fn(g.astype(raw.dtype))
    if prologue == "identity":
        return (jnp.broadcast_to(g, res.shape[1:]).astype(res.dtype),)
    xf = res.astype(plan.accum_jnp)
    if prologue == "square":
        dx = 2.0 * xf * g
    else:  # abs
        dx = jnp.sign(xf) * g
    return (dx.astype(res.dtype),)


_ksum.defvjp(_ksum_fwd, _ksum_bwd)


def _sum(
    x: jax.Array,
    axis,
    plan: ReducePlan,
    prologue: str = "identity",
    epilogue: tuple = (),
) -> jax.Array:
    """Differentiable sum dispatch (see module docstring). ``prologue`` and
    ``epilogue`` are only meaningful for full reductions (axis=None);
    callers pre-map the rows of axis reductions (a fusible jnp op on the
    row backends)."""
    if axis is not None:
        return _sum_axis_impl(x, axis, plan)
    if _backends.get_backend(plan.backend).native_autodiff:
        return _sum_all_impl(x, plan, prologue, epilogue)
    return _ksum(x, plan, prologue, epilogue)


# Full-array moments: the (sum, sumsq) pair from one backend pass (the
# kernel backends run the paired dual-accumulator prologue -- one launch,
# one read of the raw leaf).


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _kmoments(x: jax.Array, plan: ReducePlan):
    backend = _backends.get_backend(plan.backend)
    return backend.moments_all(x, plan)


def _kmoments_fwd(x, plan):
    return _kmoments(x, plan), x


def _kmoments_bwd(plan, res, g):
    gs, gss = g
    xf = res.astype(plan.accum_jnp)
    return ((gs + 2.0 * xf * gss).astype(res.dtype),)


_kmoments.defvjp(_kmoments_fwd, _kmoments_bwd)


def _moments_all(x: jax.Array, plan: ReducePlan):
    """Differentiable full-array (sum, sumsq) dispatch."""
    accum = plan.accum_jnp
    if x.size == 0:
        z = jnp.zeros((), accum)
        return z, z
    if plan.precision == "kahan":
        # The compensated combine wraps sum_all per statistic (two blocked
        # passes); the dual-accumulator kernel has no compensation rows.
        return (
            _sum(x, None, plan),
            _sum(x, None, plan, prologue="square"),
        )
    backend = _backends.get_backend(plan.backend)
    if backend.native_autodiff:
        s, ss = backend.moments_all(x, plan)
    else:
        s, ss = _kmoments(x, plan)
    return s.astype(accum), ss.astype(accum)


# ---------------------------------------------------------------------------
# Parts multi-reduce: S SEPARATE arrays summed in one backend pass with no
# packing copy (each part is its own kernel operand on the Pallas backends).
# This is the zero-copy engine behind reduce_many(axis=None) / reduce_tree.
# ---------------------------------------------------------------------------


def _sum_parts_impl(
    parts, plan: ReducePlan, prologue="identity", epilogue: tuple = ()
) -> jax.Array:
    backend = _backends.get_backend(plan.backend)
    accum = plan.accum_jnp
    if not parts:
        return jnp.zeros((0,), accum)
    if plan.precision == "kahan":
        # Parts have no serial combine to compensate (each flushes once);
        # degrade gracefully to exact-accumulator multipliers, like rows.
        plan = plan.replace(compute_dtype=plan.accum_dtype)
    if epilogue:
        return backend.sum_parts(
            tuple(parts), plan, prologue, epilogue=epilogue
        ).astype(accum)
    if prologue == "identity":
        return backend.sum_parts(tuple(parts), plan).astype(accum)
    return backend.sum_parts(tuple(parts), plan, prologue).astype(accum)


def _sum_parts_total_impl(
    parts, plan: ReducePlan, prologue="identity", chains=((),),
    census: bool = False,
) -> jax.Array:
    """(S + K,) vector: per-part sums plus chain k of the cross-part total
    at slot S + k -- one backend pass (the Pallas parts kernel finishes the
    chains in-launch via its total accumulator). ``census=True`` widens by
    S + 1 more slots: per-part non-finite counts then their total, counted
    in-kernel on the same pass (host reference on census-less backends)."""
    backend = _backends.get_backend(plan.backend)
    accum = plan.accum_jnp
    if plan.precision == "kahan":
        plan = plan.replace(compute_dtype=plan.accum_dtype)
    return _backends.sum_parts_total_with_census(
        backend, tuple(parts), plan, prologue, chains, census
    ).astype(accum)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _ksum_parts(
    parts, plan: ReducePlan, prologue="identity", epilogue: tuple = ()
) -> jax.Array:
    return _sum_parts_impl(parts, plan, prologue, epilogue)


def _kparts_res(parts, prologue):
    # zero-size residuals carry identity parts' shape+dtype without
    # retaining them; mapped parts keep x for their chain rule
    pros = _kcommon.normalize_part_prologues(prologue, len(parts))
    return tuple(
        p if pro != "identity" else jnp.zeros((0,) + p.shape, p.dtype)
        for p, pro in zip(parts, pros)
    )


def _kparts_fwd(parts, plan, prologue, epilogue):
    res = _kparts_res(parts, prologue)
    if not epilogue:
        return _sum_parts_impl(parts, plan, prologue), (res, None)
    raw = _sum_parts_impl(parts, plan, prologue)
    return _kcommon.apply_epilogue(raw, epilogue), (res, raw)


def _kparts_chain_rule(plan, prologue, res, g):
    # Per-part cotangent: the prologue's chain rule against that part's
    # slot(s) -- identity: g[s] broadcast; square: 2 x g[s]; abs:
    # sign(x) g[s]; moments: g[s] + 2 x g[S + s] (both slots feed back).
    pros = _kcommon.normalize_part_prologues(prologue, len(res))
    nseg = len(res)
    accum = plan.accum_jnp
    outs = []
    for s, (r, pro) in enumerate(zip(res, pros)):
        if pro == "identity":
            outs.append(jnp.broadcast_to(g[s], r.shape[1:]).astype(r.dtype))
            continue
        xf = r.astype(accum)
        if pro == "square":
            dx = 2.0 * xf * g[s]
        elif pro == "abs":
            dx = jnp.sign(xf) * g[s]
        else:  # moments
            dx = g[s] + 2.0 * xf * g[nseg + s]
        outs.append(dx.astype(r.dtype))
    return (tuple(outs),)


def _kparts_bwd(plan, prologue, epilogue, resid, g):
    res, raw = resid
    if epilogue:
        # every epilogue step is elementwise, so one vjp over the (S,) raw
        # totals maps the cotangent back through the whole chain at once
        _, vjp_fn = jax.vjp(
            lambda s: _kcommon.apply_epilogue(s, epilogue), raw
        )
        (g,) = vjp_fn(g.astype(raw.dtype))
    return _kparts_chain_rule(plan, prologue, res, g)


_ksum_parts.defvjp(_kparts_fwd, _kparts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _ksum_parts_total(
    parts, plan: ReducePlan, prologue="identity", chains=((),),
    census: bool = False,
) -> jax.Array:
    return _sum_parts_total_impl(parts, plan, prologue, chains, census)


def _kparts_total_fwd(parts, plan, prologue, chains, census):
    res = _kparts_res(parts, prologue)
    per = _sum_parts_impl(parts, plan, prologue)
    total = jnp.sum(per)
    totals = jnp.stack(
        [_kcommon.apply_epilogue(total, ch) for ch in chains]
    ).astype(per.dtype)
    pieces = [per, totals]
    if census:
        # differentiated forward only (the primal path stays in-kernel):
        # the reference host census fills the count slots
        pieces.append(_backends.host_nonfinite_census(parts, per.dtype))
    return jnp.concatenate(pieces), (res, total)


def _kparts_total_bwd(plan, prologue, chains, census, resid, g):
    # Slot s feeds both its own output g[s] and (through the cross-part
    # total) every chain output g[S + k], each mapped back through jax.vjp
    # of its chain at the raw total. The census count slots (when present)
    # are piecewise-constant in the inputs -- zero cotangent, dropped.
    res, total = resid
    nseg = len(res)
    gtot = jnp.zeros((), total.dtype)
    for k, ch in enumerate(chains):
        _, vjp_fn = jax.vjp(
            lambda s, _ch=ch: _kcommon.apply_epilogue(s, _ch), total
        )
        (dk,) = vjp_fn(g[nseg + k].astype(total.dtype))
        gtot = gtot + dk
    gslots = g[:nseg] + gtot
    return _kparts_chain_rule(plan, prologue, res, gslots)


_ksum_parts_total.defvjp(_kparts_total_fwd, _kparts_total_bwd)


def _sum_parts(
    parts, plan: ReducePlan, prologue="identity", epilogue: tuple = ()
) -> jax.Array:
    """Differentiable parts-sum dispatch (see module docstring)."""
    parts = tuple(parts)
    if not isinstance(prologue, str):
        prologue = tuple(prologue)  # hashable custom_vjp nondiff argument
    if _backends.get_backend(plan.backend).native_autodiff:
        return _sum_parts_impl(parts, plan, prologue, epilogue)
    return _ksum_parts(parts, plan, prologue, epilogue)


def _sum_parts_total(
    parts, plan: ReducePlan, prologue="identity", chains=((),),
    census: bool = False,
) -> jax.Array:
    """Differentiable parts-sum-plus-epilogue'd-total dispatch."""
    parts = tuple(parts)
    if not isinstance(prologue, str):
        prologue = tuple(prologue)
    if _backends.get_backend(plan.backend).native_autodiff:
        return _sum_parts_total_impl(parts, plan, prologue, chains, census)
    return _ksum_parts_total(parts, plan, prologue, chains, census)


def _resolve_plan(x, axis, kind, plan, backend, m, tiles_per_block,
                  compute_dtype, accum_dtype, precision,
                  kahan_block=None, segments=None, num_cores=None,
                  mesh_axes=None) -> ReducePlan:
    if plan is None:
        return plan_for(
            x.shape,
            x.dtype,
            kind=kind,
            axis=axis if axis != _NO_AXES else None,
            backend=backend,
            m=m,
            tiles_per_block=tiles_per_block,
            num_cores=num_cores,
            compute_dtype=compute_dtype,
            accum_dtype=accum_dtype,
            precision=precision,
            kahan_block=kahan_block,
            segments=segments,
            mesh_axes=mesh_axes,
        )
    overrides = {}
    if backend is not None:
        overrides["backend"] = backend
    if m is not None:
        overrides["m"] = int(m)
    if tiles_per_block is not None:
        overrides["tiles_per_block"] = int(tiles_per_block)
    if num_cores is not None:
        overrides["num_cores"] = int(num_cores)
    if compute_dtype is not None:
        overrides["compute_dtype"] = str(jnp.dtype(compute_dtype))
    if accum_dtype is not None:
        overrides["accum_dtype"] = str(jnp.dtype(accum_dtype))
    if precision is not None:
        overrides["precision"] = precision
    if kahan_block is not None:
        overrides["kahan_block"] = int(kahan_block)
    if mesh_axes is not None:
        overrides["mesh_axes"] = norm_mesh_axes(mesh_axes)
    return plan.replace(**overrides) if overrides else plan


def _cross_combine(row: jax.Array, plan: ReducePlan) -> jax.Array:
    """Fold per-device ADDITIVE partials across plan.mesh_axes (the
    deterministic fixed-order combine; see Backend.cross_device_combine)."""
    return _backends.get_backend(plan.backend).cross_device_combine(row, plan)


def _reduce_mesh_full(x: jax.Array, kind: str, p: ReducePlan, chain: tuple):
    """Full reduction inside a shard_map body (``p.mesh_axes`` bound): the
    local launch computes the shard's ADDITIVE statistic exactly as the
    single-device path would (one pallas_call per device on the kernel
    backends), one deterministic fixed-order combine folds the per-device
    partials in static device order, and the kind's finisher plus the
    epilogue chain apply host-side to the combined total -- identical jnp
    ops on identical replicated values, so the global statistic is
    BIT-identical on every replica at any device count. The finishers run
    post-combine by necessity: sqrt/mean/chains are not additive, so they
    cannot be applied before the cross-device fold without changing the
    statistic."""
    from repro.core import collectives as _coll  # deferred: import cycle

    lp = p.replace(mesh_axes=())
    if kind == "moments":
        s, ss = _moments_all(x, lp)
        row = _cross_combine(jnp.stack([s, ss]), p)
        return row[0], row[1]
    if kind in ("sumsq", "norm2"):
        local = _sum(x, None, lp, prologue="square")
    else:
        local = _sum(x, None, lp)
    total = _cross_combine(local, p)
    if kind == "mean":
        # global count: equal shards by shard_map construction. An empty
        # mean keeps the 0/0 -> NaN semantics of the single-device path.
        count = x.size * _coll.mesh_world_size(p.mesh_axes)
        total = total * ((1.0 / count) if count else float("nan"))
    if kind == "norm2":
        total = jnp.sqrt(total)
    return _kcommon.apply_epilogue(total, chain)


def _reduce_census_full(x: jax.Array, kind: str, p: ReducePlan, chain: tuple):
    """Full reduction + in-launch non-finite census of one array: the
    ``reduce_tree(census=True)`` row machinery restricted to a single leaf.
    Returns ``(statistic, count)``. The kind's finisher (norm2's sqrt,
    mean's 1/n) and the epilogue chain fold into the launch's total chain
    on the kernel backends; the count comes back in the same row's tail
    slot -- zero extra HBM input bytes, one launch. Under ``p.mesh_axes``
    the additive row (per-leaf sum, raw total, counts) rides the one
    fixed-order combine and the finishers apply post-combine on the
    replicated totals -- statistic AND count bit-identical per replica."""
    accum = p.accum_jnp
    prologue = "square" if kind in ("sumsq", "norm2") else "identity"
    post = chain
    if kind == "norm2":
        post = (("sqrt",),) + post
    if kind == "mean":
        n = x.size
        if p.mesh_axes:
            from repro.core import collectives as _coll  # deferred: cycle

            n = n * _coll.mesh_world_size(p.mesh_axes)
        post = (("scale", 1.0 / n if n else float("nan")),) + post
    if x.size == 0:
        z = jnp.zeros((), accum)
        return _kcommon.apply_epilogue(z, post).astype(accum), z
    if p.mesh_axes:
        lp = p.replace(mesh_axes=())
        row = _sum_parts_total([x], lp, prologue, ((),), True)
        row = _cross_combine(row, p)
        return _kcommon.apply_epilogue(row[1], post).astype(accum), row[3]
    row = _sum_parts_total([x], p, prologue, (post,), True)
    # row layout: [per-part sum (1) | chain output (1) | counts (2: part0,
    # total)] -- the finished statistic is slot 1, the total count slot 3
    return row[1], row[3]


# Row census: B equal rows of one 2-D array, each row's statistic and its
# NaN/Inf count from ONE segmented launch over the flat buffer (the kernel
# backends). The launch returns (2B,) -- row sums, then row counts -- and the
# custom VJP maps the row cotangents back through the prologue's chain rule;
# the count slots are piecewise constant, so their cotangents drop.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _ksum_rows_census(x: jax.Array, plan: ReducePlan, prologue: str):
    rows, width = x.shape
    backend = _backends.get_backend(plan.backend)
    return backend.sum_segments(
        x.reshape(-1), tuple(range(0, rows * width + 1, width)), plan,
        prologue, census=True,
    ).astype(plan.accum_jnp)


def _krows_fwd(x, plan, prologue):
    res = x if prologue != "identity" else jnp.zeros((0,) + x.shape, x.dtype)
    return _ksum_rows_census(x, plan, prologue), res


def _krows_bwd(plan, prologue, res, g):
    g_rows = g[: res.shape[-2], None]
    if prologue == "identity":
        return (jnp.broadcast_to(g_rows, res.shape[1:]).astype(res.dtype),)
    return ((2.0 * res.astype(plan.accum_jnp) * g_rows).astype(res.dtype),)


_ksum_rows_census.defvjp(_krows_fwd, _krows_bwd)


def _reduce_census_rows(x: jax.Array, kind: str, p: ReducePlan):
    """Per-row statistic + per-row non-finite census of a 2-D ``x``.

    Returns ``(per_row (B,), counts (B + 1,))``: the counts are each row's
    NaN/Inf elements with their total appended. On the kernel backends the
    B rows are B equal segments of the one flat buffer, so the whole census
    is one launch of the segmented gather kernel whose compiled body does
    not depend on B (its cover maps are scalar-prefetched). The total is a
    plain sum of the B counts. The jnp-level backends compute the same
    values as fusible code: the row statistic as the eq. (9) dot, the
    counts as a row sum of the non-finite mask."""
    accum = p.accum_jnp
    rows, width = x.shape
    square = kind in ("sumsq", "norm2")
    if p.backend == "segmented":
        p = p.replace(backend=segmented_backend_for(x.size, x.dtype, p.m))
    if x.size == 0:
        per = counts = jnp.zeros((rows,), accum)
    elif _backends.get_backend(p.backend).native_prologue:
        out = _ksum_rows_census(x, p, "square" if square else "identity")
        per, counts = out[:rows], out[rows:]
    else:
        xf = x.astype(accum)
        per = _sum(xf * xf if square else xf, (1,), p)
        counts = (
            jnp.sum(~jnp.isfinite(x), axis=-1).astype(accum)
            if jnp.issubdtype(x.dtype, jnp.inexact)
            else jnp.zeros((rows,), accum)
        )
    if kind == "mean":
        per = per * (1.0 / width if width else float("nan"))
    elif kind == "norm2":
        per = jnp.sqrt(per)
    return per, jnp.concatenate([counts, jnp.sum(counts)[None]])


def reduce(
    x,
    axis: Axis = None,
    kind: str = "sum",
    *,
    plan: Optional[ReducePlan] = None,
    backend: Optional[str] = None,
    m: Optional[int] = None,
    tiles_per_block: Optional[int] = None,
    num_cores: Optional[int] = None,
    compute_dtype=None,
    accum_dtype=None,
    precision: Optional[str] = None,
    kahan_block: Optional[int] = None,
    epilogue=None,
    census: bool = False,
    mesh_axes=None,
):
    """Reduce ``x`` over ``axis`` (None = all elements; () = no axes,
    matching numpy's empty-tuple convention).

    kind:
      "sum"     -- plain sum, result dtype = plan.accum_dtype.
      "mean"    -- sum / reduced-element count. An EMPTY full reduction is
                   the 0/0 indeterminate and returns NaN BY DEFINITION
                   (numpy's empty-mean semantics) on every backend and on
                   both the plain and the epilogue (in-launch 1/n scale)
                   paths. Guarded consumers must treat that NaN as a
                   defined statistic, not a fault: the non-finite census
                   (``reduce_tree(census=True)``) tallies INPUT elements
                   only, so an empty mean never increments it.
      "sumsq"   -- sum of squares. Full reductions square IN-KERNEL at
                   plan.compute_dtype on the kernel backends (f32 by
                   planner default for sumsq/norm2 -- pin compute_dtype
                   to trade accuracy for width) and at accumulator
                   precision on the jnp-level backends; axis reductions
                   always square at accumulator precision.
      "norm2"   -- sqrt(sumsq): the L2 norm / clipping statistic.
      "moments" -- (sum, sumsq) pair: exactly what LayerNorm/RMSNorm need;
                   axis reductions fuse both moments into one stacked
                   all-ones dot (one MXU pass); full reductions ride the
                   kernel backends' (x, x^2) dual accumulator -- one pass
                   over the raw leaf, squares at plan.compute_dtype (bf16
                   by default for this kind).

    ``plan`` pins the full execution strategy; the keyword overrides adjust
    individual fields (of the given plan, or of the planner's choice) --
    ``num_cores`` stripes the Pallas kernels across that many parallel
    lanes, ``kahan_block`` sizes the compensated combine when
    ``precision="kahan"``. All kinds are differentiable on all backends
    (Pallas backends: reverse mode).

    ``epilogue`` appends a scalar post-combine chain to a FULL reduction
    (axis=None; not "moments"): a step name ("sqrt"), a ``(name, *params)``
    step, or a tuple of steps -- see ``kernels.common.EPILOGUES``. The
    chain composes AFTER the kind's own folding (norm2's sqrt and mean's
    1/n scale become leading chain steps), and on the Pallas backends it
    runs inside the reduction launch wherever the final combine does --
    ``reduce(g, kind="norm2", epilogue=("clip_coeff", max_norm))`` returns
    the clipping coefficient with no host-side sqrt/min/div eqns.
    ``epilogue=None`` / ``"identity"`` / ``()`` is the empty chain: the
    pre-epilogue code path, byte-for-byte.

    ``census=True`` makes the SAME launch also count the NaN/Inf elements
    of ``x``: the return becomes a ``(statistic, count)`` pair, the count a
    scalar in plan.accum_dtype. On the kernel backends the count rides the
    second in-kernel accumulator over the tiles already streaming -- zero
    extra HBM input bytes, exactly the ``reduce_tree(census=True)``
    machinery restricted to one leaf. Kinds sum/mean/sumsq/norm2; a FULL
    reduction composes with ``epilogue`` (the chain finishes the
    statistic, the count is raw) and with ``mesh_axes`` (both halves ride
    the one fixed-order combine). On a 2-D ``x`` with ``axis=-1`` the
    census is per row: the return is ``(per_row (B,), counts (B + 1,))``,
    each row's count with their total appended, and on the kernel backends
    it is ONE segmented launch over the B equal rows whatever B is -- a
    serving engine's per-slot logit statistic and its non-finite detector
    (``runtime.serving.guarded_logit_stat``). The count tallies INPUT
    elements only -- an empty mean's definitional NaN never increments it.

    ``mesh_axes`` (an axis name or tuple of names, bound by an enclosing
    ``shard_map``) makes a FULL reduction global across the mesh: the local
    shard runs the normal backend launch, then a deterministic fixed-order
    all-gather fold (``core.collectives.fixed_order_combine`` -- never an
    opaque ``psum``) combines the per-device partials, so the returned
    statistic is replicated AND bit-identical on every device at any
    device count. Finishers (norm2's sqrt, mean's 1/n, the epilogue chain)
    apply after the combine on the replicated total.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    chain = _kcommon.normalize_epilogue(epilogue)
    x = jnp.asarray(x)
    axis_t = _normalize_axis(axis, x.ndim)
    if census:
        if axis_t is not None and (x.ndim != 2 or axis_t != (1,)):
            raise ValueError(
                "census=True applies to FULL reductions (axis=None) or to "
                "the rows of a 2-D array (axis=-1): the count shares the "
                f"statistic's launch; got axis={axis!r} on ndim {x.ndim}"
            )
        if axis_t is not None and mesh_axes is not None:
            raise ValueError(
                "the row census is a single-device reduction; mesh_axes= "
                "applies to FULL reductions"
            )
        if kind == "moments":
            raise ValueError(
                "census=True does not compose with kind='moments' (the "
                "dual accumulator already uses the second slot); census "
                "the statistic you need instead"
            )
    if chain:
        if axis_t is not None:
            raise ValueError(
                "epilogue chains apply to the single scalar a FULL "
                f"reduction produces; got axis={axis!r}"
            )
        if kind == "moments":
            raise ValueError(
                "epilogue chains do not compose with kind='moments' (two "
                "coupled outputs); chain the statistic you need instead"
            )
    if census and axis_t is not None:
        # B rows are a segmented multi-reduce of B equal pieces: plan the
        # flat stream, as reduce_many does
        probe = jax.ShapeDtypeStruct((x.size,), x.dtype)
        p = _resolve_plan(probe, None, kind, plan, backend, m,
                          tiles_per_block, compute_dtype, accum_dtype,
                          precision, kahan_block, segments=x.shape[0],
                          num_cores=num_cores)
        return _reduce_census_rows(x, kind, p)
    p = _resolve_plan(x, axis_t, kind, plan, backend, m, tiles_per_block,
                      compute_dtype, accum_dtype, precision, kahan_block,
                      num_cores=num_cores, mesh_axes=mesh_axes)
    if census:
        return _reduce_census_full(x, kind, p, chain)
    if p.mesh_axes:
        if axis_t is not None:
            raise ValueError(
                "mesh_axes= applies to FULL reductions (axis=None): the "
                "cross-device combine produces one global statistic; got "
                f"axis={axis!r}"
            )
        return _reduce_mesh_full(x, kind, p, chain)
    if axis_t == _NO_AXES and axis is not None:
        # reduce over no axes: the elementwise identity of each kind
        xf = x.astype(p.accum_jnp)
        if kind in ("sum", "mean"):
            return xf
        if kind == "sumsq":
            return xf * xf
        if kind == "norm2":
            return jnp.abs(xf)
        return xf, xf * xf  # moments
    if kind == "sum":
        return _sum(x, axis_t, p, epilogue=chain)
    if kind == "mean":
        count = (
            x.size
            if axis_t is None
            else int(math.prod(x.shape[a] for a in axis_t))
        )
        if chain:
            # fold the 1/n into the chain: the mean (and everything after
            # it) finishes inside the launch (empty x: nan scale keeps the
            # 0/0 semantics of the plain path)
            inv = 1.0 / count if count else float("nan")
            return _sum(x, None, p, epilogue=(("scale", inv),) + chain)
        return _sum(x, axis_t, p) / count
    if axis_t is None:
        # Full reductions run the IN-KERNEL prologue: the backend squares
        # (or pairs, for moments) each element after its own native-dtype
        # ingest, so the raw leaf streams exactly once -- no host-side
        # n-sized square, no f32 staging write (jnp-level backends apply
        # the same map as fusible XLA code at accumulator precision).
        if kind == "sumsq":
            return _sum(x, None, p, prologue="square", epilogue=chain)
        if kind == "norm2":
            if chain:
                # the norm's sqrt becomes the chain's leading step, so the
                # whole statistic (norm -> clip/rsqrt/...) stays in-launch
                return _sum(
                    x, None, p, prologue="square",
                    epilogue=(("sqrt",),) + chain,
                )
            return jnp.sqrt(_sum(x, None, p, prologue="square"))
        return _moments_all(x, p)
    # Axis (row) reductions are batched eq. (9) dots on every backend; the
    # square is host-side jnp code XLA fuses into the dot's operand.
    xf = x.astype(p.accum_jnp)
    if kind == "sumsq":
        return _sum(xf * xf, axis_t, p)
    if kind == "norm2":
        return jnp.sqrt(_sum(xf * xf, axis_t, p))
    return _moments_axis_impl(x, axis_t, p)


def _reduce_many_full(arrs, kind, plan: ReducePlan, chain: tuple = ()):
    """Per-array FULL reductions via one parts pass (see reduce_many).

    Every leaf is handed to the backend as its own operand in its NATIVE
    dtype -- the packed accumulator-dtype stream (an n-sized
    convert+concatenate staging copy on the kernel backends) is gone; the
    jnp-level backends still pack internally, where XLA fuses it. Squares
    for sumsq/norm2/moments are the IN-KERNEL prologue on the kernel
    backends (the raw leaves stream exactly once; moments rides the paired
    dual accumulator, so both statistics come from the same single read)
    and fusible accumulator-precision jnp code on the rest. ``chain`` (a
    normalized epilogue; sum/sumsq/norm2 only) maps every per-array
    statistic at its flush."""
    accum = plan.accum_jnp
    sizes = [int(a.size) for a in arrs]

    if kind in ("sum", "mean"):
        out = _sum_parts(arrs, plan, epilogue=chain)
        if kind == "mean":
            out = out / jnp.asarray([max(s, 1) for s in sizes], accum)
        return out
    if kind == "sumsq":
        return _sum_parts(arrs, plan, prologue="square", epilogue=chain)
    if kind == "norm2":
        if chain:
            return _sum_parts(
                arrs, plan, prologue="square", epilogue=(("sqrt",),) + chain
            )
        return jnp.sqrt(_sum_parts(arrs, plan, prologue="square"))
    # moments: both statistics ride the SAME single pass (the widened
    # (2S,) layout -- sums in [0, S), sums of squares in [S, 2S))
    out = _sum_parts(arrs, plan, prologue="moments")
    s = len(arrs)
    return out[:s], out[s:]


def _reduce_many_full_mesh(arrs, kind, p: ReducePlan, chain: tuple):
    """``reduce_many(axis=None)`` inside a shard_map body: one local parts
    launch produces the shard's additive (N,) (or (2N,) moments) vector,
    one fixed-order combine folds the per-device vectors elementwise, and
    the finishers/chain map the replicated global vector -- every slot
    bit-identical on every replica (see _reduce_mesh_full)."""
    from repro.core import collectives as _coll  # deferred: import cycle

    lp = p.replace(mesh_axes=())
    accum = lp.accum_jnp
    s = len(arrs)
    if kind == "moments":
        out = _cross_combine(_sum_parts(arrs, lp, prologue="moments"), p)
        return out[:s], out[s:]
    pro = "square" if kind in ("sumsq", "norm2") else "identity"
    out = _cross_combine(_sum_parts(arrs, lp, prologue=pro), p)
    if kind == "mean":
        world = _coll.mesh_world_size(p.mesh_axes)
        out = out / jnp.asarray(
            [max(int(a.size) * world, 1) for a in arrs], accum
        )
    if kind == "norm2":
        out = jnp.sqrt(out)
    return _kcommon.apply_epilogue(out, chain)


def _reduce_many_rows(arrs, kind, plan: ReducePlan):
    """Per-array LAST-AXIS reductions in one width-padded backend pass.

    Arrays of differing widths are zero-padded to the widest row (exact for
    sum/sumsq: f32 accumulation of zeros is the identity) and concatenated
    into one (sum-of-batches, L_max) row stream, so the statistics of every
    array ride a single eq. (9) dot. Native jnp throughout -> jvp and vjp
    both flow, like any engine row reduction.
    """
    accum = plan.accum_jnp
    for a in arrs:
        if a.ndim == 0:
            raise ValueError("reduce_many(axis=-1) needs arrays of ndim >= 1")
    batch_shapes = [a.shape[:-1] for a in arrs]
    widths = [int(a.shape[-1]) for a in arrs]
    rows_per = [int(math.prod(bs)) for bs in batch_shapes]
    # Degenerate leaves (zero width or zero batch) contribute nothing to the
    # stream; they come back as additive identities of the correct shapes,
    # matching reduce()'s zero-size convention.
    live = [i for i in range(len(arrs)) if widths[i] > 0 and rows_per[i] > 0]

    def _identities():
        return [jnp.zeros(bs, accum) for bs in batch_shapes]

    if not live:
        z = _identities()
        return (z, _identities()) if kind == "moments" else z
    lmax = max(widths[i] for i in live)

    def _stream(parts):
        rows = []
        for i in live:
            r = parts[i].astype(accum).reshape(-1, widths[i])
            if widths[i] < lmax:
                r = jnp.pad(r, ((0, 0), (0, lmax - widths[i])))
            rows.append(r)
        return rows[0] if len(rows) == 1 else jnp.concatenate(rows, 0)

    def _split(flat_out):
        bounds = np.cumsum([rows_per[i] for i in live])[:-1]
        pieces = jnp.split(flat_out, [int(b) for b in bounds], axis=0)
        outs = _identities()
        for i, p_ in zip(live, pieces):
            outs[i] = p_.reshape(batch_shapes[i])
        return outs

    rp = _row_plan(plan)
    backend = _backends.get_backend(rp.backend)
    if kind == "moments":
        s, ss = backend.moments_axis(_stream(arrs), rp)
        return _split(s.astype(accum)), _split(ss.astype(accum))
    if kind in ("sumsq", "norm2"):
        src = [jnp.square(a.astype(accum)) for a in arrs]
    else:
        src = list(arrs)
    out = backend.sum_axis(_stream(src), rp).astype(accum)
    outs = _split(out)
    if kind == "mean":
        outs = [o / max(w, 1) for o, w in zip(outs, widths)]
    elif kind == "norm2":
        outs = [jnp.sqrt(o) for o in outs]
    return outs


def reduce_many(
    arrays,
    kind: str = "sum",
    *,
    axis: Optional[int] = None,
    plan: Optional[ReducePlan] = None,
    backend: Optional[str] = None,
    m: Optional[int] = None,
    tiles_per_block: Optional[int] = None,
    num_cores: Optional[int] = None,
    compute_dtype=None,
    accum_dtype=None,
    precision: Optional[str] = None,
    kahan_block: Optional[int] = None,
    epilogue=None,
    mesh_axes=None,
):
    """Reduce N independent arrays in ONE backend pass (segmented
    multi-reduce) instead of N separate launches.

    ``arrays`` is any pytree (typically a list); leaves are reduced in
    ``tree_leaves`` order. With ``axis=None`` every leaf is fully reduced
    and the result is a single stacked ``(N,)`` vector (``kind="moments"``:
    a ``(sums, sumsqs)`` pair of ``(N,)`` vectors -- both moments ride the
    same pass as 2N segments). With ``axis=-1`` (the only supported axis)
    each leaf is reduced over its own last axis -- widths may differ -- and
    the result is a *list* of per-leaf arrays (moments: a pair of lists).

    Execution: one ``jax.ops.segment_sum`` (xla), one batched eq. (9) dot
    over the zero-padded tile stream (mma_jnp), or one multi-operand launch
    of the parts kernel (both pallas modes; each leaf streams zero-copy in
    its native dtype as its own operand) -- ``n/m^2 + N`` MMAs for the
    whole batch and no packing copy. The planner's auto route is the
    registered "segmented" backend. Differentiation: the custom VJP
    generalizes the broadcast-cotangent rule per part, so ``jax.grad``
    flows through every backend.

    ``epilogue`` (full reductions; "sum"/"sumsq"/"norm2" only) maps every
    per-array statistic through one scalar chain at its in-kernel flush --
    see ``reduce``. "mean" is excluded because its per-array 1/n scales
    differ, and a chain carries one parameter set per launch.

    ``mesh_axes`` (inside a shard_map body; ``axis=None`` only) makes every
    per-array statistic global across the mesh via the deterministic
    fixed-order combine -- the whole (N,) vector rides ONE all-gather, and
    each slot is bit-identical on every device. See ``reduce``.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    if axis not in (None, -1):
        raise ValueError(
            f"reduce_many reduces each array fully (axis=None) or over its "
            f"last axis (axis=-1); got axis={axis!r}"
        )
    chain = _kcommon.normalize_epilogue(epilogue)
    if chain:
        if axis is not None:
            raise ValueError(
                "reduce_many epilogues apply to full reductions "
                f"(axis=None); got axis={axis!r}"
            )
        if kind in ("mean", "moments"):
            raise ValueError(
                f"reduce_many epilogues do not compose with kind={kind!r} "
                "(mean: per-array 1/n scales differ; moments: two coupled "
                "outputs)"
            )
    arrs = [jnp.asarray(a) for a in jax.tree_util.tree_leaves(arrays)]
    nseg = len(arrs)
    if nseg == 0:
        accum = jnp.dtype(accum_dtype) if accum_dtype is not None else jnp.float32
        z = jnp.zeros((0,), accum)
        return ((z, z) if kind == "moments" else z) if axis is None else \
            (([], []) if kind == "moments" else [])
    total = sum(int(a.size) for a in arrs)
    probe = jax.ShapeDtypeStruct((total,), jnp.result_type(*arrs))
    p = _resolve_plan(
        probe, None if axis is None else (-1,), kind, plan, backend, m,
        tiles_per_block, compute_dtype, accum_dtype, precision, kahan_block,
        segments=nseg, num_cores=num_cores, mesh_axes=mesh_axes,
    )
    if p.mesh_axes:
        if axis is not None:
            raise ValueError(
                "mesh_axes= applies to FULL per-array reductions "
                f"(axis=None); got axis={axis!r}"
            )
        return _reduce_many_full_mesh(arrs, kind, p, chain)
    if axis is None:
        return _reduce_many_full(arrs, kind, p, chain)
    return _reduce_many_rows(arrs, kind, p)


def reduce_tree(
    tree,
    kind: str = "sumsq",
    *,
    plan: Optional[ReducePlan] = None,
    backend: Optional[str] = None,
    m: Optional[int] = None,
    num_cores: Optional[int] = None,
    epilogue=None,
    return_per_leaf: bool = False,
    census: bool = False,
    mesh_axes=None,
):
    """Reduce a whole pytree to one scalar ("sum", "sumsq" or "norm2").

    This is the optimizer's gradient-clipping statistic -- the highest-volume
    full reduction in a training step -- routed through the engine. Every
    leaf's row partials feed ONE multi-operand pass (``sum_parts``): on the
    Pallas backends the whole pytree costs a single kernel launch with each
    partial entering as its own operand -- no intermediate f32
    concatenation -- where the pre-segmented engine paid one XLA reduce per
    leaf plus a launch for the stacked partials. The trailing combine of
    the S per-leaf scalars is a plain ``jnp.sum`` (S = leaf count,
    trivially small).

    On the KERNEL backends (``native_prologue``) the leaves themselves are
    the launch operands: each raw bf16/f16/f32 leaf streams zero-copy into
    the parts kernel, which squares it in-kernel (the square prologue) --
    the whole-pytree norm is ONE launch, ONE read of every leaf, with no
    host-side square pass and no f32 staging write. Pallas kernels are
    single-device executors, so leaf-direct ingestion costs nothing there.

    SHARDING-CRITICAL (jnp-level backends): each leaf is reduced as a
    *last-axis* all-ones dot (eq. 9) BEFORE packing -- only the small local
    row partials enter the concatenated stream, never the sharded leaves
    themselves. Flattening a leaf into (k, m, m) tiles first would reshape
    across sharded dimensions and force GSPMD to all-gather the full tensor
    (for a 132B model that is a 169 GB gather per step -- caught by the
    dry-run; see EXPERIMENTS.md). The last-axis dot keeps every MMA on the
    local shard, and the cross-device rungs of the paper's hierarchy are
    GSPMD's own reduce of the packed partials -- eq. (13) continued over
    the mesh, as designed. Under GSPMD, route through mma_jnp/xla (the
    planner's auto route off-TPU), which keep exactly this property.

    ``epilogue`` finishes the tree statistic inside the same launch: one
    chain (``("clip_coeff", max_norm)``) or a LIST of chains -- the fork --
    for several scalars from the one reduction (``[(), ("clip_coeff",
    c)]`` -> the ``(statistic, clip)`` pair the optimizer wants). Chains
    apply to the KIND's statistic (for "norm2" the norm itself -- the sqrt
    becomes each chain's leading step), and on the kernel backends they run
    in the parts kernel's in-launch total accumulator at ANY num_cores --
    zero host-side sqrt/min/div eqns (``inspect.assert_epilogue_free``
    checks exactly this). A fork returns a ``(K,)`` vector, chain k's
    scalar at slot k; a single chain returns a scalar.
    ``return_per_leaf=True`` additionally returns the RAW per-leaf partial
    sums (no sqrt, no chain) as ``(per_leaf, result)`` -- the fused
    second-moment consumer reads per-leaf sumsq and the clip coefficient
    from the same single launch.

    ``census=True`` makes the SAME launch also count every NaN/Inf element
    of the tree: the return gains a trailing ``counts`` vector of S + 1
    f32 slots -- per-leaf non-finite counts then their total -- so the
    full shape is ``(result, counts)`` or ``(per_leaf, result, counts)``.
    On the kernel backends the counts ride a second in-kernel accumulator
    over the tiles already streaming (zero extra HBM input bytes; only the
    output row widens -- this is the guarded optimizer's NaN/Inf detector);
    jnp-level backends compute the same counts as fusible host code. The
    counts tally INPUT elements only: statistics that are legitimately NaN
    by definition (e.g. an empty ``kind="mean"``'s 0/0 -- see ``reduce``)
    never enter the census.

    ``mesh_axes`` (an axis name or tuple, bound by an enclosing
    ``shard_map``) makes the whole statistic GLOBAL across the mesh: each
    device runs its normal local launch over its shard's leaves (still one
    pallas_call on the kernel backends, census counted in-kernel), the
    additive row -- per-leaf sums, raw cross-leaf total, census counts --
    rides ONE deterministic fixed-order all-gather fold
    (``core.collectives.fixed_order_combine``, never an opaque ``psum``),
    and the chains (norm2's sqrt included) finish on the replicated global
    total. Statistic, per-leaf partials, chain outputs, AND census counts
    are bit-identical on every replica at any device count -- which is what
    makes a guarded optimizer's skip decision provably the same on all
    hosts. Chains run host-side post-combine on this path by necessity
    (they must see the global total, which exists only after the
    cross-device fold).
    """
    if kind not in ("sum", "sumsq", "norm2"):
        raise ValueError(f"reduce_tree supports sum/sumsq/norm2; got {kind!r}")
    chains = None
    if epilogue is not None or return_per_leaf or census:
        chains = _kcommon.normalize_epilogue_fork(
            epilogue if epilogue is not None else ()
        )
        if kind == "norm2":
            # the norm's sqrt leads every chain: chains see the NORM
            chains = tuple((("sqrt",),) + ch for ch in chains)
    leaves = jax.tree_util.tree_leaves(tree)
    square = kind in ("sumsq", "norm2")
    if plan is None:
        # Probe with the TOTAL element count: the auto heuristic must see
        # the real problem size, not the (arbitrary) first leaf's shape.
        total = sum(int(math.prod(jnp.shape(leaf))) for leaf in leaves)
        plan = plan_for(
            (total,),
            jnp.float32,
            kind="sumsq" if square else "sum",
            backend=backend,
            m=m,
            num_cores=num_cores,
            compute_dtype="float32",  # exactness matters for clipping
            segments=len(leaves) or None,
            mesh_axes=mesh_axes,
        )
    elif backend is not None or m is not None or num_cores is not None \
            or mesh_axes is not None:
        plan = plan.replace(
            **{
                k: v
                for k, v in (
                    ("backend", backend),
                    ("m", m),
                    ("num_cores", num_cores),
                    ("mesh_axes", None if mesh_axes is None
                     else norm_mesh_axes(mesh_axes)),
                )
                if v is not None
            }
        )
    accum = plan.accum_jnp

    def _finish(per_leaf, out, counts=None):
        # fork of K chains -> (K,) vector; single chain -> its scalar
        if chains is not None and len(chains) == 1:
            out = out.reshape(())
        pieces = (out,)
        if return_per_leaf:
            pieces = (per_leaf,) + pieces
        if census:
            pieces = pieces + (counts,)
        return pieces[0] if len(pieces) == 1 else pieces

    if not leaves:
        if chains is None:
            return jnp.zeros((), accum)
        totals = jnp.stack(
            [
                _kcommon.apply_epilogue(jnp.zeros((), accum), ch)
                for ch in chains
            ]
        )
        # an empty tree streams nothing -> a lone zero total-count slot
        return _finish(
            jnp.zeros((0,), accum), totals, jnp.zeros((1,), accum)
        )
    if plan.mesh_axes:
        # Distributed path (inside a shard_map body): the local launch
        # produces the shard's ADDITIVE row -- per-leaf sums, the raw
        # cross-leaf total, the non-finite counts -- then ONE fixed-order
        # combine folds the per-device rows in static device order. Every
        # downstream value derives from the replicated combined row by
        # identical jnp ops, so all outputs are bit-identical per replica.
        lp = plan.replace(mesh_axes=())
        prologue = "square" if square else "identity"
        s = len(leaves)
        if _backends.get_backend(lp.backend).native_prologue:
            # one launch per device: the identity total chain makes
            # _sum_parts_total emit exactly [per-leaf | raw total | counts],
            # census counted in-kernel (zero extra input bytes)
            arrs = [jnp.asarray(leaf) for leaf in leaves]
            row = _sum_parts_total(arrs, lp, prologue, ((),), census)
        else:
            partials = []
            for leaf in leaves:
                xf = jnp.asarray(leaf).astype(accum)
                v = xf * xf if square else xf
                partials.append(
                    v.reshape(1) if v.ndim == 0
                    else _sum(v, (v.ndim - 1,), lp).reshape(-1)
                )
            per = _sum_parts(partials, lp)
            pieces = [per, jnp.sum(per)[None]]
            if census:
                pieces.append(
                    _backends.host_nonfinite_census(
                        [jnp.asarray(leaf) for leaf in leaves], accum
                    )
                )
            row = jnp.concatenate(pieces)
        row = _cross_combine(row, plan)
        total = row[s]
        if chains is None:
            return jnp.sqrt(total) if kind == "norm2" else total
        totals = jnp.stack(
            [_kcommon.apply_epilogue(total, ch) for ch in chains]
        ).astype(accum)
        return _finish(row[:s], totals, row[s + 1:] if census else None)
    if _backends.get_backend(plan.backend).native_prologue:
        # Kernel backends: the raw leaves ARE the launch operands; the
        # square runs in-kernel (single stream, single launch -- see the
        # docstring). No astype, no host square, no partial row pass.
        arrs = [jnp.asarray(leaf) for leaf in leaves]
        prologue = "square" if square else "identity"
        if chains is not None:
            # sum_parts_total: the cross-leaf total folds in-launch and the
            # chains finish it there too -- one launch, zero host eqns
            # (census: the counts come back in the same row's tail slots)
            out = _sum_parts_total(arrs, plan, prologue, chains, census)
            s, k = len(arrs), len(chains)
            if census:
                return _finish(out[:s], out[s:s + k], out[s + k:])
            return _finish(out[:s], out[s:])
        per_leaf = _sum_parts(arrs, plan, prologue=prologue)
        total = jnp.sum(per_leaf)
        return jnp.sqrt(total) if kind == "norm2" else total
    partials = []
    for leaf in leaves:
        xf = jnp.asarray(leaf).astype(accum)
        v = xf * xf if square else xf
        if v.ndim == 0:
            partials.append(v.reshape(1))
            continue
        rs = _sum(v, (v.ndim - 1,), plan)  # local last-axis dot per leaf
        partials.append(rs.reshape(-1))
    # ONE launch over every leaf's row partials, each entering the backend
    # as its own operand -- the old intermediate f32 concatenation of the
    # partials never materializes on the kernel backends.
    per_leaf = _sum_parts(partials, plan)
    total = jnp.sum(per_leaf)
    if chains is not None:
        # host-map reference semantics: same chains, same values (census:
        # the same reference counts over the raw leaves)
        totals = jnp.stack(
            [_kcommon.apply_epilogue(total, ch) for ch in chains]
        ).astype(accum)
        counts = (
            _backends.host_nonfinite_census(
                [jnp.asarray(leaf) for leaf in leaves], accum
            )
            if census
            else None
        )
        return _finish(per_leaf, totals, counts)
    return jnp.sqrt(total) if kind == "norm2" else total
