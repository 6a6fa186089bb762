"""Shared Pallas kernel utilities.

All kernels in this package target TPU (pl.pallas_call + BlockSpec VMEM
tiling): on a TPU they run compiled, and the CPU test runs validate them with
``interpret=True``. ``resolve_interpret()`` picks the mode from the default
backend and refuses any other backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MXU = 128          # MXU systolic dimension == the paper's m on TPU
LANES = 128        # vreg lane count; last-dim tiling unit
SUBLANES = 8       # vreg sublane count; second-minor tiling unit

# Dtypes the zero-copy kernels ingest directly from the caller's buffer (the
# MXU's native multiplier widths plus f32). Anything else (f64, ints, bools)
# is pre-cast to f32 by ops.py -- the one documented staging fallback.
NATIVE_INGEST_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def native_ingest_dtype(dtype) -> bool:
    """True when the Pallas kernels can read this dtype straight from HBM."""
    return any(jnp.dtype(dtype) == jnp.dtype(d) for d in NATIVE_INGEST_DTYPES)


# In-kernel elementwise prologues: the per-element map every reduction kind
# needs, applied INSIDE the kernel body -- after the compute-dtype cast and
# the tail mask, before the eq. (9) MMA -- so sumsq/norm2/moments read the
# caller's raw native-dtype leaf exactly once (single-stream; no host-side
# n-sized square pass or f32 staging write). "moments" is the paired
# (x, x^2) dual-accumulator: the kernels carry a second accumulator and
# emit both statistics from one pass over the data.
PROLOGUES = ("identity", "square", "abs", "moments")

# Prologues apply_prologue can evaluate directly; "moments" is structural
# (it selects the dual-accumulator kernel variant, not a single map).
ELEMENTWISE_PROLOGUES = ("identity", "square", "abs")


def check_prologue(prologue: str, *, allow_moments: bool = True) -> str:
    """Validate a prologue name at trace time (kernels branch statically)."""
    allowed = PROLOGUES if allow_moments else ELEMENTWISE_PROLOGUES
    if prologue not in allowed:
        raise ValueError(
            f"unknown prologue {prologue!r}; expected one of {allowed}"
        )
    return prologue


def normalize_part_prologues(prologue, nseg: int) -> tuple:
    """One validated prologue name per part, from a uniform string or a
    sequence (THE normalization rule for every sum_parts layer -- ops,
    backends, and the api VJPs all share it)."""
    if isinstance(prologue, str):
        return (check_prologue(prologue),) * nseg
    pros = tuple(check_prologue(p) for p in prologue)
    if len(pros) != nseg:
        raise ValueError(f"got {len(pros)} part prologues for {nseg} parts")
    return pros


def apply_prologue(xv: jax.Array, prologue: str) -> jax.Array:
    """Elementwise prologue at compute precision (identity adds NO ops, so
    the kind="sum" path stays op-identical -- and therefore bit-identical --
    to the prologue-free kernels). A masked/padded zero is a fixed point of
    every map here, so tail lanes still contribute exact zeros."""
    if prologue == "identity":
        return xv
    if prologue == "square":
        return xv * xv
    if prologue == "abs":
        return jnp.abs(xv)
    raise ValueError(
        f"prologue {prologue!r} is not elementwise (moments selects the "
        f"dual-accumulator kernel variant); expected one of "
        f"{ELEMENTWISE_PROLOGUES}"
    )


# In-kernel scalar EPILOGUES: the post-combine chain applied to a REDUCED
# result inside the same launch -- the consumer-side dual of the prologues.
# Where a prologue maps every element before the eq. (9) MMA, an epilogue
# maps the one f32 scalar the reduction produced (sqrt for a norm, the AdamW
# clip coefficient, a mean's 1/n scale), so consumers like the optimizer
# read their statistic straight out of the reduction launch with no host-
# side sqrt/minimum/divide eqns on an n-derived scalar. A chain is a tuple
# of steps; each step is ``(name, *float_params)`` -- fully hashable, so
# chains ride the custom_vjp nondiff arguments exactly like plans do.
EPILOGUES = ("identity", "sqrt", "scale", "rsqrt", "add_eps", "clip_coeff")

# steps that take no parameters / their required parameter counts
_EPILOGUE_ARITY = {
    "identity": (0,),
    "sqrt": (0,),
    "scale": (1,),        # scale(a): t * a
    "rsqrt": (0, 1),      # rsqrt(eps=0): 1 / sqrt(t + eps)
    "add_eps": (1,),      # add_eps(eps): t + eps
    "clip_coeff": (1, 2),  # clip_coeff(max_norm, eps=0): min(1, max/max(t,eps))
}


def _normalize_step(step) -> tuple:
    """One epilogue step -> canonical hashable ``(name, *float_params)``."""
    if isinstance(step, str):
        step = (step,)
    step = tuple(step)
    if not step or not isinstance(step[0], str):
        raise ValueError(f"epilogue step must start with a name: {step!r}")
    name, params = step[0], step[1:]
    if name not in EPILOGUES:
        raise ValueError(
            f"unknown epilogue {name!r}; expected one of {EPILOGUES}"
        )
    if len(params) not in _EPILOGUE_ARITY[name]:
        raise ValueError(
            f"epilogue {name!r} takes {_EPILOGUE_ARITY[name]} parameter(s); "
            f"got {step!r}"
        )
    return (name,) + tuple(float(p) for p in params)


def normalize_epilogue(spec) -> tuple:
    """Canonical hashable chain for one epilogue spec.

    Accepts ``None`` / ``"identity"`` / ``()`` (-> the empty chain: no
    epilogue, the reduction's PR-5 code path byte-for-byte), a single step
    (a name string or a ``(name, *params)`` tuple), or a tuple of steps.
    The empty chain is THE no-epilogue marker every layer branches on."""
    if spec is None or spec == "identity" or spec == ():
        return ()
    if isinstance(spec, str):
        steps = (spec,)
    elif isinstance(spec, tuple) and spec and isinstance(spec[0], str):
        steps = (spec,)  # a single (name, *params) step
    else:
        steps = tuple(spec)
    chain = tuple(_normalize_step(s) for s in steps)
    return tuple(s for s in chain if s[0] != "identity")


def normalize_epilogue_fork(spec) -> tuple:
    """Canonical tuple of chains for a MULTI-OUTPUT epilogue.

    A Python list marks the fork: ``[chain_a, chain_b]`` asks the reduction
    to emit ``len(spec)`` scalars from one launch, chain k applied to the
    same reduced total (the AdamW consumer forks ``[(), clip_coeff]`` into
    ``(gnorm, clip)``). Anything else is a single chain."""
    if isinstance(spec, list):
        if not spec:
            raise ValueError("an epilogue fork needs at least one chain")
        return tuple(normalize_epilogue(c) for c in spec)
    return (normalize_epilogue(spec),)


def apply_epilogue(t: jax.Array, chain: tuple) -> jax.Array:
    """Evaluate an epilogue chain on a reduced f32 scalar (or a vector of
    per-slot totals -- every step is elementwise). Pure jnp scalar math, so
    the SAME definition runs inside a Pallas kernel body (post-flush) and
    host-side (the jnp-level backends' reference semantics); chain params
    are Python floats, which weak-type against the operand and never upcast
    it."""
    for step in chain:
        name, params = step[0], step[1:]
        if name == "sqrt":
            t = jnp.sqrt(t)
        elif name == "scale":
            t = t * params[0]
        elif name == "rsqrt":
            eps = params[0] if params else 0.0
            t = 1.0 / jnp.sqrt(t + eps)
        elif name == "add_eps":
            t = t + params[0]
        elif name == "clip_coeff":
            max_norm = params[0]
            eps = params[1] if len(params) > 1 else 0.0
            t = jnp.minimum(1.0, max_norm / jnp.maximum(t, eps))
        elif name != "identity":  # pragma: no cover - normalize_* rejects
            raise ValueError(f"unknown epilogue {name!r}")
    return t


@functools.lru_cache(maxsize=None)
def ones_tile(m: int, dtype_s: str):
    """The all-ones (m, m) MMA operand of eqs. (9)-(12) as a CACHED host
    constant -- for host-side code (the deterministic lane combines), which
    hands the same numpy object to every trace (jnp ops lift it as a
    constant per trace). It must stay numpy: any jnp array built during a
    jit trace is a tracer, and caching a tracer leaks it into later traces.
    Pallas kernel BODIES additionally must not capture concrete arrays at
    all (pallas rejects closed-over constants), so they use ``ones_mma``
    below -- the same single definition, materialized trace-locally."""
    import numpy as np

    return np.ones((m, m), jnp.dtype(dtype_s))


def ones_mma(m: int, dtype) -> jax.Array:
    """Trace-local all-ones (m, m) MMA operand: the one definition kernel
    bodies draw from (safe inside pallas; never captured)."""
    return jnp.ones((m, m), jnp.dtype(dtype))


@functools.lru_cache(maxsize=None)
def triu_tile(m: int, dtype_s: str, k: int = 0):
    """Upper-triangular ones (m, m) MMA operand as a CACHED host constant:
    the scan encoding's prefix matrix (Dakkak et al. -- x @ U turns each
    tile row into its running inclusive prefix; ``k=1`` is the strictly-
    upper variant for EXCLUSIVE prefixes). numpy for the same reason as
    ``ones_tile``: a cached jnp array would leak a tracer across traces."""
    import numpy as np

    return np.triu(np.ones((m, m), jnp.dtype(dtype_s)), k=k)


@functools.lru_cache(maxsize=None)
def tril_tile(m: int, dtype_s: str, k: int = 0):
    """Lower-triangular ones (m, m) host constant; ``k=-1`` (strict) is the
    scan encoding's carry-down matrix: Ls @ R replicates, into row i, the
    fold of rows < i."""
    import numpy as np

    return np.tril(np.ones((m, m), jnp.dtype(dtype_s)), k=k)


def triu_mma(m: int, dtype, k: int = 0) -> jax.Array:
    """Trace-local upper-triangular ones operand (safe inside pallas kernel
    bodies, which must not capture concrete arrays): built from two iotas,
    exactly how the tail masks are built."""
    row = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    return (row + k <= col).astype(jnp.dtype(dtype))


def tril_mma(m: int, dtype, k: int = 0) -> jax.Array:
    """Trace-local lower-triangular ones operand (see ``triu_mma``)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    return (row + k >= col).astype(jnp.dtype(dtype))


def mma_precision(*dtypes):
    """Contraction precision of an engine MMA over operands of ``dtypes``.

    ``HIGHEST`` when any operand is f32: by default the TPU's matrix unit
    (in kernels and in XLA alike) rounds f32 operands to bf16, which would
    make an f32 compute dtype a bf16 one; the CPU ignores the setting.
    16-bit operands keep the single native pass."""
    wide = any(jnp.dtype(d) == jnp.float32 for d in dtypes)
    return jax.lax.Precision.HIGHEST if wide else None


def mma(a: jax.Array, b: jax.Array, dimension_numbers,
        accum_dtype=jnp.float32) -> jax.Array:
    """``dot_general`` accumulating in ``accum_dtype`` at the precision its
    operands ask for (``mma_precision``): every engine MMA."""
    return jax.lax.dot_general(
        a, b, dimension_numbers,
        precision=mma_precision(a.dtype, b.dtype),
        preferred_element_type=accum_dtype,
    )


def resolve_interpret(interpret: bool | None) -> bool:
    """interpret=None -> compiled on a TPU, interpreted on the CPU (the test
    setting), and an error anywhere else: a kernel never falls back to the
    interpreter in silence on a device it was not written for."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}"
    )


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def pad_to(x: jax.Array, size: int, axis: int = 0, value=0) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def compiler_params(dimension_semantics: tuple[str, ...]):
    """TPU compiler params for a grid; harmless under interpret mode."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def vmem_scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)
