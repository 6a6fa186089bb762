"""Reduction planning: pick a backend + tile geometry from the problem shape.

A ``ReducePlan`` is the complete, hashable description of *how* one reduction
runs: which registered backend executes it, the linear MMA tile size ``m``,
the Pallas block depth ``tiles_per_block``, the multiplier/accumulator dtypes,
and the (orthogonal) precision policy. Plans are static metadata -- they are
resolved at trace time from shapes and feed ``jax.custom_vjp`` nondiff
arguments, so every field is a plain hashable Python value (dtypes are stored
as strings, not ``jnp.dtype`` objects).

``plan_for`` is the cost-model-driven selector: it consults
``repro.core.cost_model``'s TPU roofline (eq. 16's step model extended with
HBM/VPU/MXU terms) to decide whether the paper's MMA encoding pays for a
given extent, and which implementation of it to use. The default can be
overridden per call (``reduce(..., backend=...)``), per process
(``set_default_backend``), or per environment (``REPRO_REDUCE_BACKEND``).
Segmented multi-reduce problems (``segments=N``; see ``reduce_many``) route
to the registered "segmented" backend, which resolves its concrete executor
per call through ``segmented_backend_for``.

Plan cache: ``plan_for`` is memoized (process-wide LRU of
``_PLAN_CACHE_SIZE`` entries) on the fully-normalized argument tuple --
shape, dtype, kind, axis, segment count, and every explicit override. The
mutable process default (``set_default_backend`` / $REPRO_REDUCE_BACKEND) is
resolved *before* the cache lookup, so changing the default can never serve
a stale plan. A hit returns the *same* frozen ``ReducePlan`` object with no
cost-model re-run (plans also compare equal structurally, so identity is an
optimization, not a contract callers must rely on). ``plan_cache_info()`` /
``plan_cache_clear()`` expose the cache to tests and long-running servers.

Quarantine: ``quarantine_backend(name)`` takes a backend out of AUTO
rotation (the serving circuit breaker's trip hook) -- auto selections and
the segmented per-call route degrade along pallas -> mma_jnp -> xla, and
the memoized plans are invalidated so no stale plan can resurrect the
failed backend. Explicit pins still reach a quarantined backend (half-open
probes). ``reinstate_backend`` reverses it.

Autotuning: ``autotune(shape, dtype, ...)`` is the *opt-in* empirical
counterpart to the cost model. It compiles and times every candidate
backend x ``tiles_per_block`` on the live device (best-of-``repeats``,
``block_until_ready``) and records the winner in a tuned-plan table that
``plan_for`` consults whenever the backend would otherwise be auto-selected
for that problem key. Recording a tuned plan invalidates the LRU cache, and
explicit per-call overrides (``backend=`` / ``tiles_per_block=``) always
beat the tuned entry. The table is process-local and never persisted:
timings are only valid for the device that produced them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model

# Environment override for the process-wide default backend.
BACKEND_ENV = "REPRO_REDUCE_BACKEND"

# The auto heuristic only routes through Pallas below when the extent spans at
# least this many full MXU tiles; smaller problems are not worth a kernel
# launch (interpret-mode or real).
_MIN_PALLAS_TILES = 2

# plan_for memoization depth; see module docstring ("Plan cache").
_PLAN_CACHE_SIZE = 1024

_default_backend: Optional[str] = None

# autotune()'s winners, keyed like the plan cache (shape, dtype, kind, axis,
# segments); consulted by _plan_for_cached when the backend is auto-selected.
_TUNED: Dict[Tuple, "ReducePlan"] = {}

# autotune()'s candidates that raised, same keys: ((plan, error), ...). A
# kernel the device refuses is a finding, not a silent loss of the race.
_TUNE_FAILURES: Dict[Tuple, Tuple] = {}

# Backends a circuit breaker (or operator) has taken out of AUTO rotation --
# see quarantine_backend(). Explicit pins (backend= / plan=) still select a
# quarantined backend: half-open probes need to address it directly.
_QUARANTINED: set = set()

# Degradation order when an auto-selected backend is quarantined. "xla" is
# terminal: the always-available jnp fallback is never rerouted away from.
_QUARANTINE_FALLBACK = {
    "pallas_fused": "mma_jnp",
    "pallas_hier": "mma_jnp",
    "mma_jnp": "xla",
}


@dataclasses.dataclass(frozen=True)
class ReducePlan:
    """Static description of one reduction's execution strategy.

    backend         -- registry name: "xla" | "mma_jnp" | "pallas_hier" |
                       "pallas_fused" | "segmented" (or anything registered
                       later).
    m               -- linear MMA tile size; 128 = TPU MXU, 16 = WMMA, 4 = V100.
    tiles_per_block -- (m, m) tiles staged per Pallas grid step.
    num_cores       -- lanes of the striped ("parallel", "arbitrary") Pallas
                       grid; the planner defaults it to the live device's
                       TPU core count (interpret mode / non-TPU: 1). The
                       cost model charges n/(m^2 c) + c MMAs per lane
                       (``cost_model.fused_mma_ops``). Ignored by the
                       jnp-level backends.
    compute_dtype   -- dtype fed to the MMA multipliers (string name).
    accum_dtype     -- accumulator / result dtype (string name).
    precision       -- "native" or "kahan" (compensated combine; the
                       Markidis-style refinement, orthogonal to the backend.
                       Backends with ``native_kahan`` carry the compensation
                       in-kernel; the rest use the blocked combine).
    kahan_block     -- block length for the blocked compensated combine.
    mesh_axes       -- bound shard_map mesh axis names the reduction combines
                       across AFTER the local launch (deterministic
                       fixed-order all-gather fold; see
                       ``core.collectives.fixed_order_combine``). Empty =
                       single-device semantics. Stored as a tuple of strings
                       so plans stay hashable custom_vjp nondiff arguments.
    """

    backend: str = "mma_jnp"
    m: int = cost_model.MXU_DIM
    tiles_per_block: int = 8
    num_cores: int = 1
    compute_dtype: str = "bfloat16"
    accum_dtype: str = "float32"
    precision: str = "native"
    kahan_block: int = 4096
    mesh_axes: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.m < 2:
            raise ValueError(f"m must be >= 2 (paper section V); got {self.m}")
        if self.num_cores < 1:
            raise ValueError(f"num_cores must be >= 1; got {self.num_cores}")
        if self.precision not in ("native", "kahan"):
            raise ValueError(f"unknown precision policy {self.precision!r}")
        if self.kahan_block < 1:
            raise ValueError(f"kahan_block must be >= 1; got {self.kahan_block}")
        if not isinstance(self.mesh_axes, tuple) or not all(
            isinstance(a, str) and a for a in self.mesh_axes
        ):
            raise ValueError(
                f"mesh_axes must be a tuple of axis-name strings; "
                f"got {self.mesh_axes!r}"
            )

    @property
    def compute_jnp(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    @property
    def accum_jnp(self) -> jnp.dtype:
        return jnp.dtype(self.accum_dtype)

    def replace(self, **kw) -> "ReducePlan":
        return dataclasses.replace(self, **kw)

    def hbm_bytes(
        self,
        n: int,
        dtype,
        *,
        segments: Optional[int] = None,
        prologue: str = "identity",
        epilogue: int = 0,
        census: bool = False,
    ) -> "cost_model.HbmTraffic":
        """Modeled HBM traffic of reducing ``n`` elements of ``dtype`` under
        this plan (``cost_model.hbm_bytes`` dispatched by backend).

        The Pallas paths ingest bf16/f16/f32 zero-copy (n * itemsize moved
        once); other dtypes pay the documented f32 pre-cast, modeled as the
        staged path. The jnp-level backends are modeled as one native
        stream read (XLA fuses their upcasts into the reduction loop).
        ``segments`` selects the multi-reduce models ("parts" for the
        kernel backends -- ``reduce_many``'s route -- with the exact
        per-part byte count available via ``cost_model.parts_hbm_bytes``).
        ``prologue`` is the in-kernel elementwise map: square/abs move NO
        extra bytes (that is the single-stream norm-path win this model
        exists to state -- the pre-prologue sumsq paid n*itemsize +
        2*n*4 more, see ``cost_model.staged_sumsq_hbm_bytes``); "moments"
        doubles the partial/output term (the dual accumulator).
        ``epilogue`` models the in-kernel post-combine chains, which cost
        ZERO input bytes: for multi-reduce (``segments``) it is the number
        of EXTRA finished-scalar output slots (a ``reduce_tree`` fork's K
        chains -> K more f32 slots in the one output vector); for scalar
        full reductions any truthy value marks the single-lane fused
        launch whose partials write collapses to one finished f32.
        ``census=True`` models the in-kernel non-finite census the same
        way: zero extra input bytes, ``segments + 1`` extra f32 output
        slots (per-part counts plus the total) on the multi-reduce paths.
        """
        from repro.kernels import common as _kcommon  # no circular import:
        # kernels.common depends only on jax

        dt = jnp.dtype(dtype)
        itemsize = dt.itemsize
        native = _kcommon.native_ingest_dtype(dt)
        dual = prologue == "moments"
        kernel = self.backend in ("pallas_fused", "pallas_hier", "segmented")
        census_slots = (int(segments) + 1) if census and segments else 0
        if segments is not None and kernel:
            return cost_model.hbm_bytes(
                "parts", n, itemsize if native else 4,
                segments=((2 * segments) if dual else segments)
                + int(epilogue),
                census=census_slots,
            )
        if segments is not None:
            # segmented census layout is the dual (2S,) widening: counts
            # in [S, 2S), no separate total slot
            return cost_model.hbm_bytes(
                "segmented", n, itemsize,
                segments=(2 * segments) if dual else segments,
                num_cores=self.num_cores,
                census=int(segments) if census else 0,
            )
        if self.backend == "pallas_hier":
            if native:
                path = "hier_moments" if dual else "hier"
            else:
                path = "fused_staged"
        elif kernel:
            path = "fused" if native else "fused_staged"
        else:
            # jnp-level backends: one fused stream over the native buffer
            # (4 bytes out per emitted statistic: the f32 result(s)).
            return cost_model.HbmTraffic(
                kernel_read=n * itemsize, kernel_write=8 if dual else 4
            )
        return cost_model.hbm_bytes(
            path, n, itemsize, m=self.m, num_cores=self.num_cores,
            tiles_per_block=self.tiles_per_block,
            kahan=self.precision == "kahan" and self.backend == "pallas_fused",
            dual=dual and path == "fused",
            epilogue=bool(epilogue) and path == "fused",
        )


def set_default_backend(name: Optional[str]) -> None:
    """Set the process-wide default backend (None restores auto-selection)."""
    global _default_backend
    _default_backend = name


def default_backend() -> str:
    """Resolution order: set_default_backend > $REPRO_REDUCE_BACKEND > auto."""
    if _default_backend is not None:
        return _default_backend
    return os.environ.get(BACKEND_ENV) or "auto"


def quarantine_backend(name: str) -> None:
    """Take ``name`` out of AUTO backend rotation (circuit-breaker trip).

    Every subsequent auto selection (``_auto_backend`` and the segmented
    per-call route ``segmented_backend_for``) degrades along
    pallas -> mma_jnp -> xla instead of returning a quarantined name.
    Explicit pins (``reduce(..., backend=...)`` / a prebuilt plan) still
    address the backend directly -- that is how a breaker's half-open
    probe tests it. Invalidate the memoized plans: a cached auto plan
    carrying the quarantined backend must never be served again
    (satellite of the breaker re-route; regression via
    ``plan_cache_info``). Scan plans are memoized separately and go stale
    for exactly the same reason, so both caches drop together."""
    _QUARANTINED.add(str(name))
    _plan_for_cached.cache_clear()
    _scan_plan_cached.cache_clear()


def reinstate_backend(name: str) -> None:
    """Undo ``quarantine_backend`` (breaker close); drops memoized plans so
    auto selection immediately returns to the reinstated backend."""
    _QUARANTINED.discard(str(name))
    _plan_for_cached.cache_clear()
    _scan_plan_cached.cache_clear()


def quarantined_backends() -> Tuple[str, ...]:
    """Currently quarantined backend names (sorted, for status exports)."""
    return tuple(sorted(_QUARANTINED))


def _dequarantine(name: str) -> str:
    """Walk the degradation chain until the name is out of quarantine (or
    terminal). Applied to AUTO selections only."""
    while name in _QUARANTINED:
        nxt = _QUARANTINE_FALLBACK.get(name)
        if nxt is None:
            return name  # terminal fallback: serve it even quarantined
        name = nxt
    return name


def backend_for_flags(mma: bool, use_pallas: bool = False) -> str:
    """Map the legacy config pair (cfg.mma_reductions, cfg.use_pallas) onto a
    registry name. Kept so model/optimizer code keeps honouring the flags the
    EXPERIMENTS.md ablations are defined in terms of. An explicit process
    default (``set_default_backend`` / $REPRO_REDUCE_BACKEND -- e.g. the
    launchers' ``--reduce-backend``) overrides the flag mapping."""
    override = _default_backend or os.environ.get(BACKEND_ENV)
    if override:
        return override
    if not mma:
        return "xla"
    return "pallas_fused" if use_pallas else "mma_jnp"


@functools.lru_cache(maxsize=1)
def _device_num_cores() -> int:
    """Default lane count for the striped Pallas kernels.

    The TPU core count of device 0 when running compiled (megacore chips
    report 2), else 1 -- off-TPU the kernels run under Pallas interpret
    mode, where the grid executes sequentially and extra lanes only add
    combine work. Process-constant, so caching is safe."""
    try:
        dev = jax.devices()[0]
    except Exception:  # pragma: no cover - backendless environments
        return 1
    if getattr(dev, "platform", None) != "tpu":
        return 1
    for attr in ("num_cores", "core_count"):
        v = getattr(dev, attr, None)
        if isinstance(v, int) and v >= 1:
            return v
    return 1  # pragma: no cover - TPU runtimes without a core-count attr


def _reduced_extent(shape: Sequence[int], axis) -> int:
    if axis is None:
        return int(math.prod(shape)) if shape else 1
    return int(math.prod(shape[a] for a in axis))


def segmented_backend_for(n: int, dtype, m: int) -> str:
    """Concrete executor for a segmented multi-reduce of ``n`` total elements.

    This is the call-time resolution behind the registered "segmented"
    auto-route: exact arithmetic for non-float data, the single-launch
    Pallas kernel for large streams on a real TPU (MXU tile only), and the
    one-dot-plus-exact-combine jnp path everywhere else."""
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return "xla"
    if n <= m:
        return "xla"
    if (
        jax.default_backend() == "tpu"
        and m == cost_model.MXU_DIM
        and n >= _MIN_PALLAS_TILES * m * m
    ):
        return _dequarantine("pallas_fused")
    return _dequarantine("mma_jnp")


def _auto_backend(shape, dtype, *, kind: str, axis, m: int, segments=None) -> str:
    """Cost-model-driven selection (see module docstring)."""
    n = _reduced_extent(shape, axis)
    if segments is not None:
        # N independent reductions: one launch for the whole batch. The
        # registered "segmented" backend resolves the concrete executor at
        # call time (segmented_backend_for).
        return "segmented"
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        # Integer/bool reductions want exact arithmetic; the MMA encoding
        # buys nothing there (XLA lowers them to exact integer adds).
        return "xla"
    if axis is not None:
        # Batched row reductions are a single all-ones dot (eq. 9) -- the
        # jnp algorithmic path already lands on the MXU; the Pallas scalar
        # kernels would serialize over rows.
        return "mma_jnp" if n > m else "xla"
    if n < _MIN_PALLAS_TILES * m * m:
        return "mma_jnp" if n > m else "xla"
    # Full reduction over a large extent. On a real TPU the fused
    # C-accumulator kernel wins (n/m^2 + 2 MMAs vs ~2.008 n/m^2 for the
    # hierarchical relaunch; EXPERIMENTS.md): take it whenever the roofline
    # says the MMA encoding is bandwidth-neutral, else stay paper-faithful.
    if jax.default_backend() == "tpu":
        rl = cost_model.tpu_reduction_roofline(
            n, device_kind=jax.devices()[0].device_kind
        )
        return "pallas_fused" if rl.mxu_bandwidth_neutral else "pallas_hier"
    # Off-TPU (CPU/interpret) the Pallas kernels run but only emulate; the
    # algorithmic path is the fast default. Explicit overrides still select
    # the kernels (that is how the CPU test sweep exercises them).
    return "mma_jnp"


def _problem_key(shape, dtype_s, kind, axis, segments) -> Tuple:
    return (shape, dtype_s, kind, axis, segments)


@functools.lru_cache(maxsize=_PLAN_CACHE_SIZE)
def _plan_for_cached(
    shape: Tuple[int, ...],
    dtype_s: str,
    kind: str,
    axis,
    backend: str,
    m: Optional[int],
    tiles_per_block: Optional[int],
    num_cores: Optional[int],
    compute_dtype: Optional[str],
    accum_dtype: Optional[str],
    precision: Optional[str],
    kahan_block: Optional[int],
    segments: Optional[int],
    mesh_axes: Tuple[str, ...] = (),
) -> ReducePlan:
    dt = jnp.dtype(dtype_s)
    m_ = m if m is not None else cost_model.MXU_DIM
    if backend == "auto":
        tuned = _TUNED.get(_problem_key(shape, dtype_s, kind, axis, segments))
        if tuned is not None:
            backend = tuned.backend
            if tiles_per_block is None:
                tiles_per_block = tuned.tiles_per_block
            if num_cores is None:
                num_cores = tuned.num_cores
        else:
            backend = _auto_backend(
                shape, dt, kind=kind, axis=axis, m=m_, segments=segments
            )
        # the quarantine re-route applies to ANY auto resolution (tuned
        # winners included); explicit pins bypass it by construction
        backend = _dequarantine(backend)
    if accum_dtype is None:
        accum_dtype = "float64" if dt == jnp.float64 else "float32"
    if compute_dtype is None:
        if dt == jnp.float64:
            compute_dtype = "float64"
        elif not jnp.issubdtype(dt, jnp.floating):
            compute_dtype = "float32"
        elif kind in ("sumsq", "norm2"):
            # Exactness matters for the gradient-clipping statistic.
            compute_dtype = "float32"
        else:
            compute_dtype = "bfloat16"
    return ReducePlan(
        backend=backend,
        m=m_,
        tiles_per_block=tiles_per_block if tiles_per_block is not None else 8,
        num_cores=num_cores if num_cores is not None else _device_num_cores(),
        compute_dtype=str(jnp.dtype(compute_dtype)),
        accum_dtype=str(jnp.dtype(accum_dtype)),
        precision=precision if precision is not None else "native",
        kahan_block=kahan_block if kahan_block is not None else 4096,
        mesh_axes=mesh_axes,
    )


def norm_mesh_axes(mesh_axes) -> Tuple[str, ...]:
    """Canonical hashable form of a mesh_axes argument: a bare axis name
    becomes a 1-tuple, any sequence becomes a tuple, None/empty becomes ()."""
    if mesh_axes is None:
        return ()
    if isinstance(mesh_axes, str):
        return (mesh_axes,)
    return tuple(str(a) for a in mesh_axes)


def _norm_axis_arg(axis, ndim: int):
    """Canonical cache-key form of ``axis``: sorted non-negative tuple (or
    None). Must agree with api._normalize_axis so ``autotune`` winners land
    on the same key ``reduce()`` looks up."""
    if axis is None or ndim == 0:
        return None
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return tuple(sorted(int(a) % ndim for a in axes))


def plan_for(
    shape: Sequence[int],
    dtype,
    *,
    kind: str = "sum",
    axis=None,
    backend: Optional[str] = None,
    m: Optional[int] = None,
    tiles_per_block: Optional[int] = None,
    num_cores: Optional[int] = None,
    compute_dtype=None,
    accum_dtype=None,
    precision: Optional[str] = None,
    kahan_block: Optional[int] = None,
    segments: Optional[int] = None,
    mesh_axes=None,
) -> ReducePlan:
    """Build the ReducePlan for reducing ``shape``/``dtype`` over ``axis``.

    Every field can be pinned by the caller; unset fields are chosen from the
    problem: exact-sensitive kinds ("sumsq", "norm2" -- the clipping
    statistic) multiply at f32, other float reductions at bf16 (the tensor-
    core mode the paper analyzes), f64 stays f64, non-float inputs are
    upcast to f32 before any MMA, and ``num_cores`` defaults to the live
    device's TPU core count (1 off-TPU / in interpret mode). ``segments=N``
    marks the problem as a segmented multi-reduce of N independent pieces
    (``shape`` then describes the packed stream). Results are memoized --
    see the module docstring.
    """
    shape_t = tuple(int(s) for s in shape)
    return _plan_for_cached(
        shape_t,
        str(jnp.dtype(dtype)),
        kind,
        _norm_axis_arg(axis, len(shape_t)),
        backend if backend is not None else default_backend(),
        None if m is None else int(m),
        None if tiles_per_block is None else int(tiles_per_block),
        None if num_cores is None else int(num_cores),
        None if compute_dtype is None else str(jnp.dtype(compute_dtype)),
        None if accum_dtype is None else str(jnp.dtype(accum_dtype)),
        precision,
        None if kahan_block is None else int(kahan_block),
        None if segments is None else int(segments),
        norm_mesh_axes(mesh_axes),
    )


def plan_cache_info():
    """(hits, misses, maxsize, currsize) of the plan_for memo cache."""
    return _plan_for_cached.cache_info()


def plan_cache_clear(clear_tuned: bool = False) -> None:
    """Drop every memoized plan -- reduce AND scan -- (and, optionally, the
    autotuned winners)."""
    _plan_for_cached.cache_clear()
    _scan_plan_cached.cache_clear()
    if clear_tuned:
        _TUNED.clear()
        _TUNE_FAILURES.clear()


# ------------------------------- scan plans ----------------------------------


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """Static description of one prefix-sum's execution strategy.

    The scan analogue of ``ReducePlan`` (same hashability contract: plans
    feed ``jax.custom_vjp`` nondiff arguments). Fields mirror the reduce
    plan where they mean the same thing; the one deliberate divergence is
    ``compute_dtype``: scans default to the operand's NATIVE ingest dtype
    (f32 stays f32) instead of the reduce path's bf16 demotion, because a
    scan's every partial result is consumer-visible -- the MoE/data-packing
    offset consumers rely on f32-exact integer prefixes, and demoting them
    would be a visible precision change, not an internal one.

    backend -- "xla" (jnp.cumsum at f32) | "mma_jnp" (batched triangular
    einsum) | "pallas_fused" (the triangular-MMA kernel, 1D streams).
    """

    backend: str = "mma_jnp"
    m: int = cost_model.MXU_DIM
    tiles_per_block: int = 8
    num_cores: int = 1
    compute_dtype: str = "float32"
    accum_dtype: str = "float32"

    def __post_init__(self):
        if self.m < 2:
            raise ValueError(f"m must be >= 2 (paper section V); got {self.m}")
        if self.num_cores < 1:
            raise ValueError(f"num_cores must be >= 1; got {self.num_cores}")

    @property
    def compute_jnp(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    @property
    def accum_jnp(self) -> jnp.dtype:
        return jnp.dtype(self.accum_dtype)

    def replace(self, **kw) -> "ScanPlan":
        return dataclasses.replace(self, **kw)

    def hbm_bytes(self, n: int, dtype) -> "cost_model.HbmTraffic":
        """Modeled HBM traffic of scanning ``n`` elements of ``dtype`` under
        this plan. The Pallas path is ``cost_model.scan_hbm_bytes`` (native
        single stream in, block-padded prefix array out, carry-rebuild
        refetch charged outside ``launch_io``); the jnp-level backends are
        one native read + one native write (XLA fuses the f32 upcast)."""
        from repro.kernels import common as _kcommon

        dt = jnp.dtype(dtype)
        if self.backend in ("pallas_fused", "pallas_hier"):
            native = _kcommon.native_ingest_dtype(dt)
            itemsize = dt.itemsize if native else 4
            return cost_model.scan_hbm_bytes(
                n, itemsize, m=self.m, num_cores=self.num_cores,
                tiles_per_block=self.tiles_per_block,
            )
        return cost_model.HbmTraffic(
            kernel_read=n * dt.itemsize, kernel_write=n * dt.itemsize
        )


def _auto_scan_backend(shape, dtype, *, m: int) -> str:
    """Cost-model-driven scan backend selection (quarantine-aware).

    Non-float data wants exact integer adds -> xla. Batched (ndim > 1)
    scans are a single triangular einsum already on the MXU -> mma_jnp.
    Small 1D extents are not worth a launch -> mma_jnp/xla by extent. Large
    1D streams on a real TPU take the triangular kernel; off-TPU the
    algorithmic path is the fast default (explicit pins still select the
    kernel -- the CPU test sweep's route)."""
    n = int(shape[-1]) if shape else 1
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return "xla"
    if len(shape) > 1:
        return "mma_jnp" if n > m else "xla"
    if n < _MIN_PALLAS_TILES * m * m:
        return "mma_jnp" if n > m else "xla"
    if jax.default_backend() == "tpu":
        return "pallas_fused"
    return "mma_jnp"


def _native_scan_compute(dtype_s: str) -> str:
    """The ScanPlan compute-dtype default: the operand's own ingest dtype
    (bf16 scans multiply at bf16, f32 at f32); non-native falls back to the
    documented f32 pre-cast width."""
    from repro.kernels import common as _kcommon

    dt = jnp.dtype(dtype_s)
    return dtype_s if _kcommon.native_ingest_dtype(dt) else "float32"


@functools.lru_cache(maxsize=_PLAN_CACHE_SIZE)
def _scan_plan_cached(
    shape: Tuple[int, ...],
    dtype_s: str,
    backend: str,
    m: Optional[int],
    tiles_per_block: Optional[int],
    num_cores: Optional[int],
    compute_dtype: Optional[str],
) -> ScanPlan:
    m_ = m if m is not None else cost_model.MXU_DIM
    if backend == "auto":
        backend = _dequarantine(
            _auto_scan_backend(shape, jnp.dtype(dtype_s), m=m_)
        )
    if compute_dtype is None:
        compute_dtype = _native_scan_compute(dtype_s)
    return ScanPlan(
        backend=backend,
        m=m_,
        tiles_per_block=tiles_per_block if tiles_per_block is not None else 8,
        num_cores=num_cores if num_cores is not None else _device_num_cores(),
        compute_dtype=str(jnp.dtype(compute_dtype)),
        accum_dtype="float32",
    )


def scan_plan_for(
    shape: Sequence[int],
    dtype,
    *,
    backend: Optional[str] = None,
    m: Optional[int] = None,
    tiles_per_block: Optional[int] = None,
    num_cores: Optional[int] = None,
    compute_dtype=None,
) -> ScanPlan:
    """Build the ScanPlan for scanning ``shape``/``dtype`` over the LAST
    axis (``repro.scan`` normalizes ``axis=`` before planning). Unset
    fields follow the scan defaults (see ``ScanPlan``); backend resolution
    honours the same ``set_default_backend`` / $REPRO_REDUCE_BACKEND /
    quarantine machinery as ``plan_for``. Results are memoized; the cache
    drops together with the reduce plan cache on quarantine, reinstate,
    ``plan_cache_clear`` and autotune events."""
    shape_t = tuple(int(s) for s in shape)
    return _scan_plan_cached(
        shape_t,
        str(jnp.dtype(dtype)),
        backend if backend is not None else default_backend(),
        None if m is None else int(m),
        None if tiles_per_block is None else int(tiles_per_block),
        None if num_cores is None else int(num_cores),
        None if compute_dtype is None else str(jnp.dtype(compute_dtype)),
    )


def scan_plan_cache_info():
    """(hits, misses, maxsize, currsize) of the scan_plan_for memo cache."""
    return _scan_plan_cached.cache_info()


def autotune_failures(
    shape: Sequence[int], dtype, *, kind: str = "sum", axis=None,
    segments: Optional[int] = None,
) -> Tuple:
    """``((plan, error), ...)`` for the candidates that raised in the last
    ``autotune`` of this problem (empty if none did or it never ran)."""
    shape_t = tuple(int(s) for s in shape)
    axis_t = _norm_axis_arg(axis, len(shape_t))
    return _TUNE_FAILURES.get(
        _problem_key(shape_t, str(jnp.dtype(dtype)), kind, axis_t, segments),
        (),
    )


def autotune(
    shape: Sequence[int],
    dtype,
    *,
    kind: str = "sum",
    axis=None,
    segments: Optional[int] = None,
    backends: Optional[Sequence[str]] = None,
    tiles_per_block_candidates: Sequence[int] = (2, 4, 8, 16),
    num_cores_candidates: Sequence[int] = (1, 2, 4),
    repeats: int = 3,
    seed: int = 0,
) -> ReducePlan:
    """Empirically pick the fastest plan for one problem ON THE LIVE DEVICE.

    Opt-in (never runs implicitly -- timing inside a trace would be
    meaningless): compiles ``reduce`` once per candidate backend x
    ``tiles_per_block`` x ``num_cores`` (block depth and lane count only
    swept for the Pallas kernels), times ``repeats`` runs, and records the
    best-of winner in the tuned-plan table so every later ``plan_for`` with
    an auto-selected backend for this problem returns it. With ``segments=N`` the timed workload is the real
    segmented pass -- ``reduce_many`` over ``shape`` split into N equal
    pieces -- so ``sum_segments`` boundary handling is part of what is
    measured. Returns the winning plan. A candidate that fails to compile
    or run loses the race, with a warning, and is recorded with its error
    (``autotune_failures``).
    """
    from repro.reduce import api as _api  # deferred: api imports this module
    from repro.reduce import backends as _backends  # deferred, same reason

    shape_t = tuple(int(s) for s in shape)
    axis_t = _norm_axis_arg(axis, len(shape_t))
    dt = jnp.dtype(dtype)
    if backends is None:
        backends = tuple(
            n for n in _backends.available_backends() if n != "segmented"
        )
    if jnp.issubdtype(dt, jnp.floating):
        x = jnp.asarray(
            np.random.RandomState(seed).standard_normal(shape_t), dt
        )
    else:
        x = jnp.ones(shape_t, dt)
    if segments:
        # time the REAL segmented pass: the stream split into N pieces
        x = tuple(
            jnp.asarray(c) for c in np.array_split(np.asarray(x).ravel(), segments)
        )
    best: Optional[ReducePlan] = None
    best_t = math.inf
    failures = []
    for name in backends:
        is_pallas = name.startswith("pallas")
        tpbs = tuple(tiles_per_block_candidates) if is_pallas else (None,)
        ncs = tuple(num_cores_candidates) if is_pallas else (None,)
        for tpb, nc in ((t, n) for t in tpbs for n in ncs):
            cand = plan_for(
                shape_t,
                dt,
                kind=kind,
                axis=axis_t,
                backend=name,
                tiles_per_block=tpb,
                num_cores=nc,
                segments=segments,
            )
            try:
                if segments:
                    fn = jax.jit(
                        lambda *a, p=cand: _api.reduce_many(a, kind=kind, plan=p)
                    )
                else:
                    fn = jax.jit(
                        lambda a, p=cand: _api.reduce(
                            a, axis=axis_t, kind=kind, plan=p
                        )
                    )
                jax.block_until_ready(fn(*x) if segments else fn(x))  # warm
                elapsed = math.inf
                for _ in range(max(1, repeats)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*x) if segments else fn(x))
                    elapsed = min(elapsed, time.perf_counter() - t0)
            except Exception as e:
                error = f"{type(e).__name__}: {e}"
                failures.append((cand, error))
                warnings.warn(f"autotune: candidate {cand} raised {error}")
                continue
            if elapsed < best_t:
                best, best_t = cand, elapsed
    key = _problem_key(shape_t, str(dt), kind, axis_t, segments)
    _TUNE_FAILURES[key] = tuple(failures)
    if best is None:
        raise RuntimeError(
            f"autotune: no candidate backend ran for shape={shape_t} "
            f"dtype={dt} kind={kind!r}: {[e for _, e in failures]}"
        )
    _TUNED[key] = best
    _plan_for_cached.cache_clear()  # cached auto plans may now be stale
    _scan_plan_cached.cache_clear()
    return best
