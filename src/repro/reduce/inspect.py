"""Jaxpr introspection for the reduction engine's zero-copy contract.

"Zero-copy proven, not claimed": the engine advertises that its Pallas
paths read the caller's buffer directly -- no n-sized
``convert_element_type`` (staging cast), ``pad`` (tile padding copy), or
``concatenate`` (stream packing) ever materializes outside the
``pallas_call`` itself. This module turns that sentence into a checkable
predicate over lowered jaxprs, plus a traffic meter that sums the bytes the
lowered kernels actually touch, so ``benchmarks/check_bench.py`` (CI), the
microbenches, and the test suite all audit the same property from the same
walker instead of re-implementing jaxpr string scraping.

The walker descends every sub-jaxpr (pjit bodies, custom_vjp calls, scan
branches, ...) EXCEPT the kernel jaxpr inside a ``pallas_call`` -- in-VMEM
reshape/cast/mask work is exactly what the zero-copy contract moves into
the kernel, so ops inside it are the solution, not a violation.
"""

from __future__ import annotations

import math
from typing import Iterator

import jax

from jax.extend import core as _core

# Primitives that materialize a staging copy of their operand when they run
# at stream size outside the kernel. (reshape is absent on purpose: a
# same-size reshape of a contiguous buffer is metadata-only at the XLA
# level, and flat ingestion relies on exactly that.)
STAGING_PRIMITIVES = ("convert_element_type", "pad", "concatenate")


def _sub_jaxprs(params) -> Iterator[object]:
    """Every jaxpr reachable from an eqn's params (lists/tuples included)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for u in vs:
            if isinstance(u, (_core.Jaxpr, _core.ClosedJaxpr)):
                yield u


def iter_eqns(jaxpr, *, _inside_pallas: bool = False):
    """Yield ``(eqn, inside_pallas)`` for every eqn in ``jaxpr`` and its
    sub-jaxprs; ``inside_pallas`` marks eqns lowered INTO a pallas kernel
    body (where the zero-copy contract places the reshape/cast/mask work).
    """
    if isinstance(jaxpr, _core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, _inside_pallas
        nested = _inside_pallas or eqn.primitive.name == "pallas_call"
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, _inside_pallas=nested)


def _out_elems(eqn) -> int:
    return max(
        (int(math.prod(v.aval.shape)) for v in eqn.outvars), default=0
    )


def _out_bytes(eqn) -> int:
    return sum(
        int(math.prod(v.aval.shape)) * v.aval.dtype.itemsize
        for v in eqn.outvars
    )


# Additional primitives a caller can flag when auditing a MAPPED reduction
# (sumsq/norm2/moments): an n-sized multiply / power / sign outside the
# kernel is the host-side elementwise prologue pass the in-kernel prologues
# removed. Not in STAGING_PRIMITIVES by default because gradients
# legitimately produce n-sized multiplies (the 2x*g cotangent IS the
# output being built, not ingestion staging) -- only forward lowerings
# should be audited with these.
PROLOGUE_PRIMITIVES = ("mul", "integer_pow", "sign", "abs")


def staging_eqns(jaxpr, min_elems: int, extra_primitives: tuple = ()):
    """Staging copies at or above ``min_elems`` elements OUTSIDE any
    pallas_call: the ops the zero-copy ingestion contract forbids
    (``extra_primitives`` widens the audit, e.g. ``PROLOGUE_PRIMITIVES``
    for the single-stream sumsq/norm2 gate).

    Returns ``[(primitive_name, out_elems, out_bytes), ...]`` -- empty iff
    the lowered program never casts, pads, or concatenates a stream-sized
    buffer on the host side of the kernel boundary."""
    names = STAGING_PRIMITIVES + tuple(extra_primitives)
    found = []
    for eqn, inside in iter_eqns(jaxpr):
        if inside or eqn.primitive.name not in names:
            continue
        elems = _out_elems(eqn)
        if elems >= min_elems:
            found.append((eqn.primitive.name, elems, _out_bytes(eqn)))
    return found


# Primitives an in-launch EPILOGUE chain removes from the host side: the
# scalar post-combine math (a norm's sqrt, the clip coefficient's min/div,
# an rsqrt's reciprocal). These eqns are SIZE-1, so the n-sized
# ``staging_eqns`` walker can never see them -- ``assert_epilogue_free``
# audits them at ANY size instead. Only apply it to computations whose
# entire scalar tail is expected in-kernel (e.g. the optimizer's
# norm-and-clip statistic); ordinary model code uses these ops
# legitimately.
EPILOGUE_PRIMITIVES = ("sqrt", "rsqrt", "div", "min", "max")


def epilogue_eqns(jaxpr, primitives: tuple = EPILOGUE_PRIMITIVES):
    """Host-side (outside every pallas_call) occurrences of the epilogue
    primitives at any size: ``[(primitive_name, out_elems), ...]``."""
    found = []
    for eqn, inside in iter_eqns(jaxpr):
        if not inside and eqn.primitive.name in primitives:
            found.append((eqn.primitive.name, _out_elems(eqn)))
    return found


def assert_epilogue_free(
    fn, *args, primitives: tuple = EPILOGUE_PRIMITIVES
) -> None:
    """Trace ``fn(*args)`` and fail if any epilogue primitive survives on
    the host side of the kernel boundary -- the one-launch statistic's
    'no host-side sqrt/min/div eqns' property, checkable because scalar
    eqns are invisible to the n-sized staging walker."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    bad = epilogue_eqns(jaxpr, primitives)
    assert not bad, (
        f"epilogue contract violated: post-combine scalar ops outside the "
        f"pallas_call: {bad}"
    )


# Primitives the in-kernel non-finite CENSUS removes from the host side:
# the n-sized ``is_finite`` sweep a host NaN/Inf check would lower to, and
# the n-sized ``select_n`` a masked skip would need. The guarded optimizer
# replaces both -- the census counts inside the reduction launch and the
# skip is an integer bit-blend (and/or/broadcast, never a select) -- so a
# guarded update's lowering should contain NEITHER at any size. Only apply
# to the optimizer-update computation: model forward passes use select_n
# legitimately (attention masks, dropout).
CENSUS_PRIMITIVES = ("is_finite", "select_n")


def census_eqns(jaxpr, min_elems: int = 1,
                primitives: tuple = CENSUS_PRIMITIVES):
    """Host-side (outside every pallas_call) occurrences of the census /
    masked-skip primitives at or above ``min_elems`` elements:
    ``[(primitive_name, out_elems), ...]``."""
    found = []
    for eqn, inside in iter_eqns(jaxpr):
        if inside or eqn.primitive.name not in primitives:
            continue
        elems = _out_elems(eqn)
        if elems >= min_elems:
            found.append((eqn.primitive.name, elems))
    return found


def assert_census_free(
    fn, *args, min_elems: int = 1, primitives: tuple = CENSUS_PRIMITIVES
) -> None:
    """Trace ``fn(*args)`` and fail if any ``is_finite`` / ``select_n``
    survives on the host side of the kernel boundary -- the guarded step's
    'the NaN check rides the reduction launch and the skip is a bit-blend'
    property. Default ``min_elems=1`` is the strict audit (no host
    occurrence at ANY size)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    bad = census_eqns(jaxpr, min_elems, primitives)
    assert not bad, (
        f"census contract violated: is_finite/select_n outside the "
        f"pallas_call (>= {min_elems} elems): {bad}"
    )


def assert_staging_free(
    fn, *args, min_elems: int | None = None, extra_primitives: tuple = ()
) -> None:
    """Trace ``fn(*args)`` and fail if any n-sized staging op survives
    outside the pallas_call. ``min_elems`` defaults to the largest operand's
    element count -- "n-sized" relative to the problem actually traced.
    Pass ``extra_primitives=PROLOGUE_PRIMITIVES`` to additionally forbid
    host-side elementwise prologue passes (the sumsq/norm2 single-stream
    property)."""
    if min_elems is None:
        min_elems = max(
            (int(math.prod(jax.numpy.shape(a))) for a in jax.tree_util.tree_leaves(args)),
            default=1,
        )
    jaxpr = jax.make_jaxpr(fn)(*args)
    bad = staging_eqns(jaxpr, min_elems, extra_primitives)
    assert not bad, (
        f"zero-copy contract violated: stream-sized staging ops outside the "
        f"pallas_call (>= {min_elems} elems): {bad}"
    )


def _aval_bytes(v) -> int:
    aval = v.aval
    return int(math.prod(aval.shape)) * aval.dtype.itemsize


def pallas_io_bytes(jaxpr) -> int:
    """Bytes crossing every pallas_call boundary in the lowered program:
    the sum of all kernel operands (data + scalar-prefetched maps) and
    results. For the zero-copy kernels this IS the modeled HBM traffic of
    the launch (each operand block is DMA'd once; dwelled parts blocks are
    not re-fetched), which is what makes the 'measured' column of the
    benchmark's HBM table honest on a CPU container: it is derived from the
    lowered program's actual operands, not from the model being checked."""
    total = 0
    for eqn, inside in iter_eqns(jaxpr):
        if inside or eqn.primitive.name != "pallas_call":
            continue
        total += sum(_aval_bytes(v) for v in eqn.invars)
        total += sum(_aval_bytes(v) for v in eqn.outvars)
    return total


# Cross-device collectives a distributed reduce may lower to. The
# deterministic fixed-order combine uses exactly ONE kind -- all_gather --
# so the distributed gate can both meter its wire bytes and assert that no
# opaque reduction collective (psum & friends, whose combine order is an
# implementation detail) sneaks into a path that promises bitwise
# reproducibility.
COLLECTIVE_PRIMITIVES = (
    "all_gather", "psum", "ppermute", "all_to_all", "reduce_scatter",
    "pmax", "pmin",
)


def collective_eqns(jaxpr):
    """Cross-device collective eqns outside every pallas_call:
    ``[(primitive_name, in_bytes, out_bytes), ...]``. The walker descends
    shard_map bodies, so collectives emitted inside a per-device program are
    visible."""
    found = []
    for eqn, inside in iter_eqns(jaxpr):
        if inside or eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
            continue
        inb = sum(_aval_bytes(v) for v in eqn.invars if hasattr(v.aval, "shape"))
        found.append((eqn.primitive.name, inb, _out_bytes(eqn)))
    return found


def collective_recv_bytes(jaxpr) -> int:
    """Per-device interconnect bytes RECEIVED by the lowered program's
    ``all_gather`` eqns: each gather's output holds the local shard plus
    P-1 remote shards, so ``out_bytes - in_bytes = (P-1) * shard_bytes`` is
    exactly the wire traffic into this device. This is the 'lowered' side of
    ``cost_model.interconnect_bytes`` -- derived from the traced program's
    collectives, not from the model being checked."""
    return sum(
        out - inb
        for name, inb, out in collective_eqns(jaxpr)
        if name == "all_gather"
    )


def measured_hbm_bytes(fn, *args, min_elems: int = 0) -> int:
    """Traffic meter for one traced call: pallas_call boundary bytes plus
    the bytes of any host-side staging ops at/above ``min_elems`` (so a
    staged path is charged for its copies and a zero-copy path is not)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    staged = sum(
        nbytes for _, _, nbytes in staging_eqns(jaxpr, max(min_elems, 1))
    )
    return pallas_io_bytes(jaxpr) + staged


def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call launches in the lowered program (the 1-launch
    property check, without string scraping)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(
        1
        for eqn, inside in iter_eqns(jaxpr)
        if not inside and eqn.primitive.name == "pallas_call"
    )
