"""Backend registry: the interchangeable executors behind ``repro.reduce``.

A backend implements seven primitives and nothing else:

  sum_all(x, plan, prologue)
                       -- every element of ``x``, mapped by the elementwise
                          ``prologue`` ("identity" | "square" | "abs"),
                          -> scalar of plan.accum_dtype.
  sum_axis(x, plan)    -- ``(..., L) -> (...)`` sum over the last axis.
  scan_axis(x, plan, inclusive)
                       -- ``(..., L) -> (..., L)`` prefix sum over the last
                          axis (``plan`` is a ``ScanPlan``); the new op
                          class behind ``repro.scan``. Default: exact-shift
                          ``jnp.cumsum`` reference semantics, so pre-scan
                          subclasses inherit it for free.
  moments_axis(x, plan)-- ``(..., L) -> ((...), (...))`` fused (sum, sumsq).
  moments_all(x, plan) -- full-array (sum, sumsq) scalar pair; the kernel
                          backends run the paired (x, x^2) dual-accumulator
                          prologue (ONE pass over the raw leaf).
  sum_segments(flat, offsets, plan, prologue)
                       -- S independent prologue'd sums over static slices
                          of one packed 1-D stream -> (S,) ("moments":
                          (2S,) -- sums then sumsqs).
  sum_parts(parts, plan, prologue)
                       -- S independent sums over S SEPARATE arrays
                          -> (S,); the zero-copy multi-reduce primitive
                          behind ``reduce_many`` / ``reduce_tree`` (ONE
                          launch for a whole training step's worth of
                          small reductions, with no packing concatenation
                          on the kernel backends). ``prologue`` is a name
                          or one name per part; any "moments" part widens
                          the result to (2S,).

Every reduction kind ("mean", "sumsq", "norm2", "moments") is composed from
these in ``api.py``, so a new backend (GPU wgmma, autotuned) only has to
supply them to light up the whole API; ``sum_segments``, ``sum_parts``,
``sum_parts_total`` and ``moments_all`` have correct (if staged/
multi-launch) defaults, so third-party backends inherit the batched APIs
for free.

Epilogue contract: every sum primitive also accepts a normalized scalar
``epilogue`` chain (see ``kernels.common.EPILOGUES``) applied to the
REDUCED result -- in-kernel on the Pallas backends wherever the final
combine happens inside the launch, host-side (``apply_epilogue``, the
reference semantics) on the jnp-level backends and legacy subclasses.
``sum_parts_total(parts, plan, prologue, total_chains)`` additionally
appends chain k of the *cross-part total* at slot S + k -- the one-launch
whole-tree norm/clip statistic behind ``reduce_tree(epilogue=...)`` --
and ``census=True`` widens the same row by S + 1 non-finite counts (the
guarded optimizer's NaN/Inf detector; ``sum_parts_total_with_census``
degrades pre-census subclasses to the host reference census).

Prologue contract: kernel backends (``native_prologue = True``) apply the
map INSIDE the kernel at compute precision, after the native -> compute
cast and the tail mask -- so ``reduce(kind="sumsq")`` streams the caller's
raw bf16/f16/f32 leaf exactly once, with no host-side n-sized square or
f32 staging write. The jnp-level backends apply the same map at accumulator
precision, where XLA fuses it into the reduction loop (the reference
semantics the differential harness pins the kernels against; with the
planner's f32 compute for sumsq/norm2 the two are value-identical).

Differentiation contract: backends whose primitives are plain jnp/dot code
set ``native_autodiff = True`` and support both reverse- AND forward-mode
autodiff; kernel-backed backends leave it False and ``api`` wraps their
full reductions in a ``jax.custom_vjp`` (broadcast-of-cotangent rule).
Batched row reductions are *always* executed as native dot/sum code -- the
scalar kernels have no batched form, and serializing one launch per row
would be catastrophic in training hot paths -- so axis reductions stay
forward-differentiable on every backend.

Registered here:

  xla          -- ``jnp.sum`` baseline (the paper's comparison point, and the
                  oracle the test sweep checks every other backend against).
  mma_jnp      -- the paper's hierarchical 2-MMA algorithm in pure JAX
                  (``repro.core.mma_reduce``); rows via the eq. (9) all-ones
                  dot, full reductions via the eq. (13) recurrence.
  pallas_hier  -- Pallas TPU kernel, paper-faithful multi-launch hierarchy
                  (full reductions; rows ride the same eq. (9) dot as
                  mma_jnp -- that IS the MXU-native row reduction).
  pallas_fused -- Pallas TPU kernel, single-launch C-accumulator variant,
                  striped across plan.num_cores parallel lanes with a
                  deterministic fixed-order lane combine (n/(m^2 c) + c
                  MMAs per lane; see EXPERIMENTS.md "Multi-core scaling").
  segmented    -- auto-routing registry entry for multi-reduce problems:
                  resolves the concrete executor per call
                  (``plan.segmented_backend_for``) and delegates.
"""

from __future__ import annotations

import functools as _functools
import inspect as _pyinspect
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mma_reduce as _core
from repro.kernels import common as _kcommon
from repro.kernels import scan as _scan_kernels
from repro.kernels.mma_reduce import ops as _pallas_ops
from repro.reduce.plan import ReducePlan, segmented_backend_for


def _host_prologue(x: jax.Array, plan: ReducePlan, prologue: str) -> jax.Array:
    """Reference (jnp-level) prologue semantics: the elementwise map at
    accumulator precision, fused by XLA into whatever reduction consumes
    it. Kernel backends apply the same map in-kernel instead (at compute
    precision, after the native cast); with the planner's f32 compute for
    sumsq/norm2 both routes square the same f32 value."""
    if prologue == "identity":
        return x
    return _kcommon.apply_prologue(x.astype(plan.accum_jnp), prologue)


@_functools.lru_cache(maxsize=None)
def _method_takes(backend_cls, method: str, param: str) -> bool:
    """True when this Backend subclass's ``method`` accepts ``param``
    (older third-party subclasses may predate prologue/epilogue/census)."""
    try:
        sig = _pyinspect.signature(getattr(backend_cls, method))
    except (TypeError, ValueError):  # pragma: no cover - exotic callables
        return True
    return param in sig.parameters or any(
        p.kind is _pyinspect.Parameter.VAR_KEYWORD
        for p in sig.parameters.values()
    )


def _sum_all_takes(backend_cls, param: str) -> bool:
    return _method_takes(backend_cls, "sum_all", param)


def _sum_all_takes_prologue(backend_cls) -> bool:
    return _sum_all_takes(backend_cls, "prologue")


def sum_all_with_prologue(backend, x, plan, prologue: str):
    """Invoke ``backend.sum_all`` under a prologue, degrading to the
    host-side map for pre-prologue Backend subclasses -- a legacy custom
    backend keeps serving every kind exactly as it did when api.py squared
    host-side (the identity path never even passes the parameter)."""
    if prologue == "identity":
        return backend.sum_all(x, plan)
    if _sum_all_takes_prologue(type(backend)):
        return backend.sum_all(x, plan, prologue)
    return backend.sum_all(_host_prologue(x, plan, prologue), plan)


def sum_all_with_epilogue(backend, x, plan, prologue: str, epilogue: tuple):
    """Invoke ``backend.sum_all`` under a prologue AND an epilogue chain,
    degrading gracefully for subclasses that predate either: the empty
    chain never even passes the parameter (byte-for-byte the prologue-only
    call), and a pre-epilogue subclass gets the chain applied host-side on
    its returned total -- same ``apply_epilogue`` definition, same values."""
    if not epilogue:
        return sum_all_with_prologue(backend, x, plan, prologue)
    if _sum_all_takes(type(backend), "epilogue"):
        if prologue == "identity" and not _sum_all_takes_prologue(
            type(backend)
        ):  # pragma: no cover - epilogue-only exotic subclass
            return backend.sum_all(x, plan, epilogue=epilogue)
        return backend.sum_all(x, plan, prologue, epilogue=epilogue)
    return _kcommon.apply_epilogue(
        sum_all_with_prologue(backend, x, plan, prologue), epilogue
    )


def host_nonfinite_census(parts, dtype) -> jax.Array:
    """Reference non-finite census over a parts list: ``out[s]`` counts the
    NaN/Inf elements of part s, ``out[S]`` their cross-part total -- the
    host-side semantics the in-kernel census accumulator is pinned against.
    Non-inexact parts (ints, bools) have no non-finite values by
    construction and count 0 without ever touching ``isfinite``."""
    counts = []
    for p in parts:
        if p.size and jnp.issubdtype(p.dtype, jnp.inexact):
            counts.append(
                jnp.sum(~jnp.isfinite(p.reshape(-1))).astype(dtype)
            )
        else:
            counts.append(jnp.zeros((), dtype))
    per = jnp.stack(counts) if counts else jnp.zeros((0,), dtype)
    return jnp.concatenate([per, jnp.sum(per)[None]])


def sum_parts_total_with_census(
    backend, parts, plan, prologue, total_chains, census: bool
):
    """Invoke ``backend.sum_parts_total`` with the non-finite census,
    degrading gracefully for subclasses that predate it: ``census=False``
    never even passes the parameter (byte-for-byte the old call), and a
    pre-census subclass gets the reference host census appended to its
    returned row -- same layout, same values as the in-kernel count."""
    if not census:
        return backend.sum_parts_total(parts, plan, prologue, total_chains)
    if _method_takes(type(backend), "sum_parts_total", "census"):
        return backend.sum_parts_total(
            parts, plan, prologue, total_chains, census=True
        )
    out = backend.sum_parts_total(parts, plan, prologue, total_chains)
    return jnp.concatenate([out, host_nonfinite_census(parts, out.dtype)])


class Backend:
    """Base class; subclasses override the sum primitives."""

    name: str = "?"
    # True -> primitives are jnp-level code; jvp and vjp both flow through.
    native_autodiff: bool = False
    # True -> sum_all honours plan.precision == "kahan" internally (e.g. the
    # fused kernel's in-kernel per-lane compensation row). False -> api.py
    # wraps the backend in the blocked compensated combine instead.
    native_kahan: bool = False
    # True -> the elementwise prologues (and the moments dual accumulator)
    # run INSIDE the kernel on the raw leaf: single-stream sumsq/norm2/
    # moments with zero host-side staging. False -> the map is ordinary
    # fusible jnp code at accumulator precision.
    native_prologue: bool = False

    def sum_all(
        self,
        x: jax.Array,
        plan: ReducePlan,
        prologue: str = "identity",
        epilogue: tuple = (),
    ) -> jax.Array:
        raise NotImplementedError

    def sum_axis(self, x: jax.Array, plan: ReducePlan) -> jax.Array:
        raise NotImplementedError

    def scan_axis(self, x: jax.Array, plan, inclusive: bool = True,
                  trace=None) -> jax.Array:
        """``(..., L) -> (..., L)`` prefix sum over the last axis, in the
        STORAGE dtype (``plan`` is a ``ScanPlan``; accumulation at
        plan.accum_dtype). Default implementation: ``jnp.cumsum`` at f32
        with the exclusive variant via an exact shift -- NEVER
        ``cumsum - x``, whose re-rounding breaks the contract that an
        exclusive prefix is a true prefix -- so every pre-scan subclass
        inherits correct reference semantics. Integer/bool operands
        accumulate in their own dtype (exact adds; f32 would silently
        round past 2^24). ``trace`` is the kernel backends'
        instrumentation list (ignored here)."""
        acc = (
            plan.accum_jnp
            if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype
        )
        out = jnp.cumsum(x.astype(acc), axis=-1)
        if not inclusive:
            out = jnp.concatenate(
                [jnp.zeros_like(out[..., :1]), out[..., :-1]], axis=-1
            )
        return out.astype(x.dtype)

    def moments_axis(self, x: jax.Array, plan: ReducePlan):
        """Fused (sum, sumsq) over the last axis. Default: the eq. (9)
        stacked all-ones dot -- both moments in ONE MXU pass (this is the
        LayerNorm statistics path; see row_moments_mma)."""
        return _core.row_moments_mma(
            x.astype(plan.accum_jnp),
            compute_dtype=plan.compute_jnp,
            accum_dtype=plan.accum_jnp,
        )

    def moments_all(self, x: jax.Array, plan: ReducePlan):
        """Full-array (sum, sumsq) scalar pair. Default: two ``sum_all``
        passes (identity + square) -- correct anywhere, including
        pre-prologue subclasses; the kernel backends override with the
        paired (x, x^2) dual-accumulator prologue so both statistics ride
        ONE pass over the raw leaf."""
        return (
            self.sum_all(x, plan),
            sum_all_with_prologue(self, x, plan, "square"),
        )

    def sum_segments(
        self,
        flat: jax.Array,
        offsets: Sequence[int],
        plan: ReducePlan,
        prologue: str = "identity",
        epilogue: tuple = (),
    ) -> jax.Array:
        """Independent sums ``out[s] = sum(P(flat[offsets[s]:offsets[s+1]]))``
        under the elementwise prologue P ("moments": the widened (2S,)
        vector -- sums in [0, S), sums of squares in [S, 2S)).

        ``offsets`` are *static* Python ints (len S+1, trace-time segment
        boundaries), so every slice below is a static lax.slice. Default
        implementation: one ``sum_all`` per segment -- correct for any
        subclass, but it is exactly the N-launch pattern the segmented
        engine exists to remove; the registered backends all override with
        single-pass implementations. ``epilogue`` (a normalized scalar
        chain; not with "moments") maps every per-segment total -- here via
        the host-side reference ``apply_epilogue``."""
        if prologue == "moments":
            if epilogue:
                raise ValueError(
                    "segment epilogues do not compose with "
                    "prologue='moments'"
                )
            return jnp.concatenate(
                [
                    self.sum_segments(flat, offsets, plan),
                    self.sum_segments(flat, offsets, plan, "square"),
                ]
            )
        accum = plan.accum_jnp
        outs = []
        for s in range(len(offsets) - 1):
            lo, hi = offsets[s], offsets[s + 1]
            if hi <= lo:
                outs.append(jnp.zeros((), accum))
            else:
                seg = jax.lax.slice(flat, (lo,), (hi,))
                outs.append(
                    sum_all_with_prologue(self, seg, plan, prologue).astype(
                        accum
                    )
                )
        if not outs:
            return jnp.zeros((0,), accum)
        return _kcommon.apply_epilogue(jnp.stack(outs), epilogue)

    def sum_parts(
        self,
        parts: Sequence[jax.Array],
        plan: ReducePlan,
        prologue="identity",
        epilogue: tuple = (),
    ) -> jax.Array:
        """Independent sums ``out[s] = sum(P_s(parts[s]))`` over SEPARATE
        arrays (``prologue``: one name, or one per part; any "moments"
        part widens the result to (2S,) with its sumsq in slot S + s).

        Default implementation: apply each part's map at accumulator
        precision, pack into one stream and ride ``sum_segments`` --
        correct for any subclass, and for the jnp-level backends both the
        map and the pack are ordinary fusible XLA code. Kernel backends
        override with the zero-copy parts kernel (each part enters the
        launch as its own operand, mapped in-kernel), because here the
        pack is a real n-sized concatenate+convert staging copy.
        ``epilogue`` (not with "moments") maps every per-part total."""
        accum = plan.accum_jnp
        nseg = len(parts)
        pros_probe = _kcommon.normalize_part_prologues(prologue, nseg)
        if epilogue and "moments" in pros_probe:
            raise ValueError(
                "parts epilogues do not compose with a 'moments' part"
            )
        if nseg == 0:
            return jnp.zeros((0,), accum)
        pros = pros_probe
        dual = "moments" in pros
        mapped = []
        for p, pro in zip(parts, pros):
            flat = p.reshape(-1)
            mapped.append(
                flat if pro == "moments"
                else _host_prologue(flat, plan, pro).astype(accum)
            )
        if dual:
            # widened layout: slot s sums P_s(part s); slot S + s sums the
            # square of a moments part (other square slots stay identity 0)
            mapped = [m.astype(accum) for m in mapped] + [
                _host_prologue(p.reshape(-1), plan, "square").astype(accum)
                if pro == "moments"
                else jnp.zeros((0,), accum)
                for p, pro in zip(parts, pros)
            ]
        sizes = [f.size for f in mapped]
        if sum(sizes) == 0:
            return _kcommon.apply_epilogue(
                jnp.zeros((len(mapped),), accum), epilogue
            )
        offsets = [0]
        for s in sizes:
            offsets.append(offsets[-1] + int(s))
        live = [f for f in mapped if f.size]
        flat = live[0] if len(live) == 1 else jnp.concatenate(live)
        return _kcommon.apply_epilogue(
            self.sum_segments(flat, tuple(offsets), plan), epilogue
        )

    def sum_parts_total(
        self,
        parts: Sequence[jax.Array],
        plan: ReducePlan,
        prologue="identity",
        total_chains: tuple = ((),),
        census: bool = False,
    ) -> jax.Array:
        """Per-part sums PLUS the epilogue'd cross-part total, one result:
        ``out[:S]`` = ``sum_parts`` and ``out[S + k]`` = chain k of
        ``total_chains`` applied to ``sum(out[:S])`` -- the whole-tree
        norm/clip statistic next to its per-leaf partials. Reference
        semantics here: host-side fold over the per-part sums, chains via
        ``apply_epilogue``; the Pallas backends override with the parts
        kernel's in-launch total accumulator, so the tree statistic never
        leaves the launch unfinished. Does not compose with "moments"
        parts.

        ``census=True`` widens the row by S + 1 more slots: per-part
        NON-FINITE element counts in ``out[S + K : S + K + S]`` and the
        cross-part total count last -- the guarded optimizer's NaN/Inf
        detector. Reference semantics here are the host
        ``host_nonfinite_census``; the Pallas backends count in-kernel on
        the tiles already streaming (zero extra input bytes)."""
        pros = _kcommon.normalize_part_prologues(prologue, len(parts))
        if "moments" in pros:
            raise ValueError(
                "sum_parts_total does not compose with a 'moments' part"
            )
        per = self.sum_parts(parts, plan, prologue)
        total = jnp.sum(per)
        totals = jnp.stack(
            [_kcommon.apply_epilogue(total, ch) for ch in total_chains]
        )
        pieces = [per, totals.astype(per.dtype)]
        if census:
            pieces.append(host_nonfinite_census(parts, per.dtype))
        return jnp.concatenate(pieces)

    def cross_device_combine(self, partials: jax.Array, plan: ReducePlan):
        """Combine per-device ADDITIVE partials across ``plan.mesh_axes``
        inside a shard_map body: the deterministic fixed-order all-gather
        fold (``core.collectives.fixed_order_combine``), NOT an opaque
        ``psum`` -- every device folds the identical gathered rows in the
        identical static order, so the global value is bit-identical on
        every replica at any device count. Backends targeting hardware with
        a deterministic in-network reduction may override; the contract is
        only that the result is replicated and bitwise replica-invariant."""
        if not plan.mesh_axes:
            return partials
        from repro.core import collectives as _coll  # deferred: cycle

        return _coll.fixed_order_combine(partials, plan.mesh_axes)


class XlaBackend(Backend):
    """Plain XLA reductions at accumulator precision -- the baseline/oracle."""

    name = "xla"
    native_autodiff = True

    def sum_all(self, x, plan, prologue="identity", epilogue=()):
        return _kcommon.apply_epilogue(
            jnp.sum(_host_prologue(x, plan, prologue).astype(plan.accum_jnp)),
            epilogue,
        )

    def sum_axis(self, x, plan):
        return jnp.sum(x.astype(plan.accum_jnp), axis=-1)

    def moments_axis(self, x, plan):
        xf = x.astype(plan.accum_jnp)
        return jnp.sum(xf, axis=-1), jnp.sum(xf * xf, axis=-1)

    def sum_segments(self, flat, offsets, plan, prologue="identity",
                     epilogue=()):
        # One exact segment_sum over the whole (prologue-mapped) stream
        # (the oracle the segmented test sweep pins every other backend
        # against). "moments" widens via the base-class concat of the
        # identity and square passes (XLA fuses both into one sweep).
        if prologue == "moments":
            return super().sum_segments(flat, offsets, plan, prologue,
                                        epilogue)
        sizes = np.diff(np.asarray(offsets, np.int64))
        ids = jnp.asarray(np.repeat(np.arange(sizes.size), sizes), jnp.int32)
        return _kcommon.apply_epilogue(
            jax.ops.segment_sum(
                _host_prologue(flat, plan, prologue).astype(plan.accum_jnp),
                ids,
                num_segments=int(sizes.size),
            ),
            epilogue,
        )


class MmaJnpBackend(Backend):
    """The paper's algorithm as jnp dots (runs on any backend, SPMD-safe)."""

    name = "mma_jnp"
    native_autodiff = True

    def sum_all(self, x, plan, prologue="identity", epilogue=()):
        return _kcommon.apply_epilogue(
            _core.mma_sum(
                _host_prologue(x, plan, prologue),
                m=plan.m,
                compute_dtype=plan.compute_jnp,
                accum_dtype=plan.accum_jnp,
            ),
            epilogue,
        )

    def sum_axis(self, x, plan):
        return _core.row_sum_mma(
            x.astype(plan.accum_jnp),
            compute_dtype=plan.compute_jnp,
            accum_dtype=plan.accum_jnp,
        )

    def scan_axis(self, x, plan, inclusive=True, trace=None):
        # The paper's triangular encoding as one batched chunk @ U einsum
        # plus an exact f32 strip-carry -- the algorithmic reference the
        # kernel is checked against, SPMD-safe on any backend.
        return _scan_kernels.mma_scan_jnp(
            x, inclusive=inclusive, m=plan.m,
            compute_dtype=plan.compute_jnp,
        )

    def sum_segments(self, flat, offsets, plan, prologue="identity",
                     epilogue=()):
        # Stage every segment as zero-padded rows of m, then ride ONE
        # batched eq. (9) all-ones dot over the whole padded row stream;
        # the n/m row partials combine with an exact f32 segment_sum (the
        # upper rungs of the paper's hierarchy, collapsed to one VPU pass).
        # The prologue maps the stream before the rows are built (zeros are
        # fixed points of every map, so the padding stays exact).
        if prologue == "moments":
            return super().sum_segments(flat, offsets, plan, prologue,
                                        epilogue)
        flat = _host_prologue(flat, plan, prologue)
        m = plan.m
        accum = plan.accum_jnp
        nseg = len(offsets) - 1
        rows, rcounts = [], []
        for s in range(nseg):
            lo, hi = offsets[s], offsets[s + 1]
            size = hi - lo
            r = -(-size // m) if size > 0 else 0
            rcounts.append(r)
            if r == 0:
                continue
            seg = jax.lax.slice(flat, (lo,), (hi,)).astype(accum)
            if r * m != size:
                seg = jnp.pad(seg, (0, r * m - size))
            rows.append(seg.reshape(r, m))
        if not rows:
            return _kcommon.apply_epilogue(jnp.zeros((nseg,), accum), epilogue)
        stream = jnp.concatenate(rows, 0) if len(rows) > 1 else rows[0]
        partials = _core.row_sum_mma(
            stream, compute_dtype=plan.compute_jnp, accum_dtype=accum
        )
        ids = jnp.asarray(np.repeat(np.arange(nseg), rcounts), jnp.int32)
        return _kcommon.apply_epilogue(
            jax.ops.segment_sum(partials, ids, num_segments=nseg), epilogue
        )


class _PallasBackend(Backend):
    """Shared plumbing for the two Pallas kernel modes. The kernels implement
    scalar (full) reductions; batched row reductions are the same eq. (9)
    all-ones dot the mma_jnp backend uses -- on TPU that single dot IS the
    kernel a row reduction would emit, and anything else would serialize one
    launch per row."""

    mode: str = "?"
    native_autodiff = False  # full reductions run inside pl.pallas_call
    # sumsq/norm2/moments map in-kernel on the raw leaf (single-stream).
    native_prologue = True

    @staticmethod
    def _check_m(plan):
        if plan.m != _pallas_ops.MXU:
            raise ValueError(
                f"pallas backends implement the m={_pallas_ops.MXU} MXU tile "
                f"only; got m={plan.m}. Use backend='mma_jnp' for tile-size "
                "ablations (m=2/4/16 per the paper)."
            )

    def sum_all(self, x, plan, prologue="identity", epilogue=()):
        self._check_m(plan)
        out = _pallas_ops.mma_sum_pallas(
            x,
            mode=self.mode,
            tiles_per_block=plan.tiles_per_block,
            num_cores=plan.num_cores,
            compute_dtype=plan.compute_jnp,
            kahan=self.native_kahan and plan.precision == "kahan",
            prologue=prologue,
            epilogue=epilogue,
        )
        return out.astype(plan.accum_jnp)

    def sum_axis(self, x, plan):
        return _core.row_sum_mma(
            x.astype(plan.accum_jnp),
            compute_dtype=plan.compute_jnp,
            accum_dtype=plan.accum_jnp,
        )

    def moments_axis(self, x, plan):
        # Batched ROW moments have no scalar-kernel form (one launch per
        # row would serialize the training hot path); they ride the same
        # stacked eq. (9) all-ones dot as mma_jnp -- on TPU that single dot
        # IS the MXU-native row reduction. This is a documented delegation,
        # not a silent fallback: full-array moments (axis=None) DO run the
        # in-kernel dual-accumulator prologue (``moments_all``).
        return super().moments_axis(x, plan)

    def moments_all(self, x, plan):
        # The paired (x, x^2) dual-accumulator prologue: both statistics
        # from ONE zero-copy pass over the raw leaf (single launch on the
        # fused mode; a single dual-emitting level-0 launch plus the f32
        # partial hierarchies on the hierarchical mode).
        self._check_m(plan)
        if self.native_kahan and plan.precision == "kahan":
            raise ValueError(
                "kind='moments' does not compose with precision='kahan' on "
                f"this backend (plan={plan!r}): the moments pass needs the "
                "dual (x, x^2) accumulator pair, which cannot share the "
                "kernel's in-kernel Kahan carry. Supported fallback: "
                "replan with precision='native' (e.g. reduce(x, "
                "kind='moments', precision='native')), or compensate the "
                "two sums separately via two kind='sum'/'sumsq' passes at "
                "precision='kahan'."
            )
        s, ss = _pallas_ops.mma_moments_pallas(
            x,
            mode=self.mode,
            tiles_per_block=plan.tiles_per_block,
            num_cores=plan.num_cores,
            compute_dtype=plan.compute_jnp,
        )
        return s.astype(plan.accum_jnp), ss.astype(plan.accum_jnp)

    def scan_axis(self, x, plan, inclusive=True, trace=None):
        # 1D streams take the triangular-MMA kernel: one pallas_call, native
        # ingest, block-padded prefix output, in-kernel carry chain.
        # Batched (ndim > 1) rows have no scalar-kernel form (one launch
        # per row would serialize the hot path); they ride the same batched
        # triangular einsum as mma_jnp -- a documented delegation exactly
        # like moments_axis, not a silent fallback.
        self._check_m(plan)
        if x.ndim > 1:
            return _scan_kernels.mma_scan_jnp(
                x, inclusive=inclusive, m=plan.m,
                compute_dtype=plan.compute_jnp,
            )
        return _scan_kernels.mma_scan_pallas(
            x,
            inclusive=inclusive,
            m=plan.m,
            tiles_per_block=plan.tiles_per_block,
            num_cores=plan.num_cores,
            compute_dtype=plan.compute_jnp,
            trace=trace,
        )

    def sum_segments(self, flat, offsets, plan, prologue="identity",
                     epilogue=(), census=False):
        # Both kernel modes share the single-launch segmented gather kernel:
        # the hierarchy's only distinction (relaunch on partials) is moot
        # once every boundary flushes inside one launch. The kernel reads
        # ``flat`` zero-copy through its aligned-block cover maps and maps
        # each gathered tile in-kernel; ``epilogue`` maps each flushed
        # per-segment total (in-kernel on single-lane launches, host-side
        # after the lane combine otherwise -- same chain, same values).
        # ``census=True`` widens the result to (2S,): the per-segment
        # NaN/Inf counts follow the sums, counted on the same gather (the
        # row census behind ``reduce(x, axis=-1, census=True)``).
        self._check_m(plan)
        out = _pallas_ops.mma_sum_segments_pallas(
            flat,
            tuple(offsets),
            tiles_per_block=plan.tiles_per_block,
            num_cores=plan.num_cores,
            compute_dtype=plan.compute_jnp,
            prologue=prologue,
            epilogue=epilogue,
            census=census,
        )
        return out.astype(plan.accum_jnp)

    def sum_parts(self, parts, plan, prologue="identity", epilogue=()):
        # Zero-copy multi-reduce: every part is its own launch operand, so
        # the packed-stream concatenate (and its accumulator-dtype staging
        # cast) never materializes -- and the prologue maps each part
        # in-kernel, so sumsq/norm2/moments batches stream every raw leaf
        # exactly once. The parts kernel compiles one branch and keeps one
        # VMEM block per live part, so past PARTS_KERNEL_MAX live parts the
        # staged pack (small per-part buffers, one concat, host-side maps)
        # is the better trade -- documented fallback via the base class.
        # ``epilogue`` maps each flushed per-part total in-kernel.
        self._check_m(plan)
        live = sum(1 for p in parts if p.size)
        if live > _pallas_ops.PARTS_KERNEL_MAX:
            return super().sum_parts(parts, plan, prologue, epilogue)
        out = _pallas_ops.mma_sum_parts_pallas(
            parts, compute_dtype=plan.compute_jnp, prologue=prologue,
            slot_epilogue=epilogue,
        )
        return out.astype(plan.accum_jnp)

    def sum_parts_total(self, parts, plan, prologue="identity",
                        total_chains=((),), census=False):
        # The whole-tree statistic WITHOUT leaving the launch: the parts
        # kernel's (1,) VMEM total accumulator folds every flushed per-part
        # total in static part order (its sequential grid ignores
        # plan.num_cores entirely, so this holds at ANY core count) and the
        # final flush emits each chain of the raw total into its own extra
        # output slot. reduce_tree(kind="norm2", epilogue=...) therefore
        # costs ONE launch with zero host-side sqrt/min/div eqns -- and
        # census=True counts NaN/Inf on the same in-flight tiles into S + 1
        # more slots, still one launch, still zero extra input bytes. Past
        # PARTS_KERNEL_MAX live parts: base-class host fold (documented
        # fallback, same values -- including the host reference census).
        self._check_m(plan)
        pros = _kcommon.normalize_part_prologues(prologue, len(parts))
        live = sum(1 for p in parts if p.size)
        if "moments" in pros or live > _pallas_ops.PARTS_KERNEL_MAX:
            return super().sum_parts_total(parts, plan, prologue,
                                           total_chains, census=census)
        out = _pallas_ops.mma_sum_parts_pallas(
            parts, compute_dtype=plan.compute_jnp, prologue=prologue,
            total_chains=tuple(total_chains), census=census,
        )
        return out.astype(plan.accum_jnp)


class PallasHierBackend(_PallasBackend):
    name = "pallas_hier"
    mode = "hierarchical"


class PallasFusedBackend(_PallasBackend):
    name = "pallas_fused"
    mode = "fused"
    # The fused lane carries its compensation in a second VMEM scratch row,
    # so precision="kahan" stays a SINGLE launch (api.py's blocked combine
    # would pay one launch per kahan_block).
    native_kahan = True


class SegmentedBackend(Backend):
    """The registered "segmented" auto-route.

    The planner sends multi-reduce problems here (``plan_for(...,
    segments=N)`` -> backend "segmented"); the concrete executor is resolved
    *per call* from the live problem via ``plan.segmented_backend_for`` --
    exact XLA for non-float data, the single-launch Pallas kernel for large
    streams on a real TPU, the one-dot jnp path everywhere else -- so a plan
    cached on one problem key stays valid wherever it is replayed. The
    scalar/row primitives delegate the same way, which keeps an explicitly
    pinned ``backend="segmented"`` usable with the whole ``reduce`` API."""

    name = "segmented"
    # May resolve to a kernel-backed executor, so api.py conservatively
    # wraps full/segmented reductions in the custom VJP.
    native_autodiff = False

    def _delegate(self, n: int, dtype, plan: ReducePlan):
        name = segmented_backend_for(n, dtype, plan.m)
        return get_backend(name), plan.replace(backend=name)

    def sum_all(self, x, plan, prologue="identity", epilogue=()):
        b, p = self._delegate(x.size, x.dtype, plan)
        return b.sum_all(x, p, prologue, epilogue=epilogue)

    def sum_axis(self, x, plan):
        b, p = self._delegate(x.shape[-1], x.dtype, plan)
        return b.sum_axis(x, p)

    def moments_axis(self, x, plan):
        b, p = self._delegate(x.shape[-1], x.dtype, plan)
        return b.moments_axis(x, p)

    def moments_all(self, x, plan):
        b, p = self._delegate(x.size, x.dtype, plan)
        return b.moments_all(x, p)

    def sum_segments(self, flat, offsets, plan, prologue="identity",
                     epilogue=()):
        b, p = self._delegate(flat.size, flat.dtype, plan)
        return b.sum_segments(flat, offsets, p, prologue, epilogue=epilogue)

    def sum_parts(self, parts, plan, prologue="identity", epilogue=()):
        total = sum(int(p.size) for p in parts)
        dtype = jnp.result_type(*parts) if parts else jnp.float32
        b, p = self._delegate(total, dtype, plan)
        return b.sum_parts(parts, p, prologue, epilogue=epilogue)

    def sum_parts_total(self, parts, plan, prologue="identity",
                        total_chains=((),), census=False):
        total = sum(int(p.size) for p in parts)
        dtype = jnp.result_type(*parts) if parts else jnp.float32
        b, p = self._delegate(total, dtype, plan)
        return sum_parts_total_with_census(
            b, parts, p, prologue, total_chains, census
        )


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend, name: str | None = None) -> Backend:
    """Add a backend to the registry (later PRs: gpu wgmma, autotuned)."""
    _REGISTRY[name or backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown reduce backend {name!r}; available: "
            f"{', '.join(available_backends())}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend(XlaBackend())
register_backend(MmaJnpBackend())
register_backend(PallasHierBackend())
register_backend(PallasFusedBackend())
register_backend(SegmentedBackend())
