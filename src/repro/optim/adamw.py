"""AdamW with decoupled weight decay, cosine schedule and MMA global-norm
clipping. Hand-rolled (no optax dependency); state is a pytree mirroring the
params so the sharding rules apply verbatim (m/v inherit the param sharding
-- ZeRO-style partitioned optimizer state for free under FSDP).

The gradient-clipping statistic -- the largest full reduction in a training
step -- routes through the unified reduction engine. On the Pallas backends
the whole-pytree norm is SINGLE-STREAM: every raw grad leaf (bf16 included)
enters one parts-kernel launch as its own zero-copy operand and is squared
IN-KERNEL (the square prologue), and the norm's sqrt AND the clip
coefficient's min/max/div finish inside the same launch as an EPILOGUE fork
(``reduce_tree(kind="norm2", epilogue=[(), ("clip_coeff", ...)])`` ->
``(gnorm, clip)`` from one pallas_call, zero host-side scalar eqns --
``inspect.assert_epilogue_free`` gates exactly this in
benchmarks/check_bench.py). The jnp-level backends keep the sharding-safe
per-leaf row-partial route with the same chain applied host-side.

``fused_second_moment`` (olmax-style) keeps ONE SCALAR second-moment EMA
per leaf instead of a full elementwise ``v`` tensor: the per-leaf sumsq
slots of the SAME norm launch feed ``nu <- b2 nu + (1-b2) E[g^2]``, and the
update multiplies by the scalar reciprocal ``1/(sqrt(nuhat)+eps)`` -- so a
grad leaf makes ONE HBM trip per step (norm+stats+update) instead of
three, and the n-sized sqrt/divide of the elementwise path disappears.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import reduce as R
from repro.configs.base import TrainConfig

# Gradient-norm floor for the clip coefficient: clip = min(1, c/max(g, EPS)).
# A Python float stays WEAK-TYPED: it folds into the epilogue chain's kernel
# constants and, host-side, binds to gnorm's dtype instead of materializing
# an f32 literal that would upcast the statistic under a bf16 policy (the
# old inline ``jnp.maximum(gnorm, 1e-9)`` pitfall).
GNORM_EPS = 1e-9

# Adam denominator fuzz (the standard 1e-8); same weak-typing rationale.
ADAM_EPS = 1e-8


@dataclasses.dataclass(frozen=True)
class AdamWState:
    step: Any
    m: Any
    v: Any


jax.tree_util.register_dataclass(
    AdamWState, data_fields=["step", "m", "v"], meta_fields=[]
)


def init_state(params, *, fused_second_moment: bool = False) -> AdamWState:
    """Optimizer state. ``fused_second_moment=True`` replaces each leaf's
    elementwise ``v`` tensor with ONE f32 scalar (the olmax-style E[g^2]
    EMA fed by the norm launch's per-leaf sumsq slots) -- the state
    shrinks by ~half and the update loses its n-sized sqrt/divide."""
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    second = (
        (lambda p: jnp.zeros((), jnp.float32)) if fused_second_moment
        else zeros
    )
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(second, params),
    )


def cosine_lr(cfg: TrainConfig, step):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    return cfg.learning_rate * warm * 0.5 * (1 + jnp.cos(jnp.pi * prog))


def global_norm(
    grads,
    *,
    mma: bool = True,
    backend: Optional[str] = None,
    num_cores: Optional[int] = None,
    mesh_axes=None,
):
    """L2 norm over the gradient pytree via the reduction engine. ``backend``
    overrides the legacy ``mma`` flag when given; on the Pallas backends the
    leaves stream zero-copy through the in-kernel square prologue (one
    launch, one read per gradient byte). ``num_cores`` stripes the kernel
    lanes (planner default when None). ``mesh_axes`` (inside a shard_map
    body) makes the norm GLOBAL over the sharded tree via the deterministic
    fixed-order combine -- bit-identical on every replica."""
    if backend is None:
        backend = R.backend_for_flags(mma)
    return R.reduce_tree(grads, kind="norm2", backend=backend,
                         num_cores=num_cores, mesh_axes=mesh_axes)


def global_norm_and_clip(
    grads,
    max_norm,
    *,
    mma: bool = True,
    backend: Optional[str] = None,
    num_cores: Optional[int] = None,
    return_per_leaf: bool = False,
    census: bool = False,
    mesh_axes=None,
):
    """``(gnorm, clip)`` from ONE reduction launch: the epilogue fork
    finishes both the norm's sqrt and ``clip = min(1, max_norm /
    max(gnorm, GNORM_EPS))`` inside the launch that reduced the leaves
    (kernel backends -- zero host-side sqrt/min/div eqns; jnp backends
    apply the identical chain host-side). ``return_per_leaf=True``
    additionally returns the raw per-leaf sumsq slots first, from the same
    single launch -- the fused second-moment feed. ``census=True`` appends
    the (S + 1,) non-finite counts vector (per-leaf counts then their
    total), counted by the SAME launch on the tiles it already streams --
    the guarded step's NaN/Inf detector at zero extra input bytes.
    ``mesh_axes`` (inside a shard_map body, over SHARDED grads) makes norm,
    clip, per-leaf slots AND census global across the mesh through the
    deterministic fixed-order combine: every replica sees the identical
    bits, so a skip decision keyed off any of them is provably in
    lockstep."""
    if backend is None:
        backend = R.backend_for_flags(mma)
    fork = [(), ("clip_coeff", float(max_norm), GNORM_EPS)]
    out = R.reduce_tree(
        grads, kind="norm2", backend=backend, num_cores=num_cores,
        epilogue=fork, return_per_leaf=return_per_leaf, census=census,
        mesh_axes=mesh_axes,
    )
    if return_per_leaf:
        if census:
            per_leaf, fork_out, counts = out
            return per_leaf, fork_out[0], fork_out[1], counts
        per_leaf, fork_out = out
        return per_leaf, fork_out[0], fork_out[1]
    if census:
        fork_out, counts = out
        return fork_out[0], fork_out[1], counts
    return out[0], out[1]


def _adamw_core(
    params,
    grads,
    state: AdamWState,
    cfg: TrainConfig,
    *,
    clip,
    per_leaf=None,
    fused_second_moment: bool = False,
):
    """The AdamW update arithmetic given an already-computed clip
    coefficient (and, for the fused second moment, the per-leaf sumsq
    slots): returns ``(new_params, new_state, lr)``. Split out so
    ``apply_updates`` and ``guarded_apply_updates`` share one code path --
    an unskipped guarded step is BITWISE the unguarded step."""
    step = state.step + 1
    lr = cosine_lr(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1**step.astype(jnp.float32)
    bc2 = 1 - b2**step.astype(jnp.float32)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)

    if fused_second_moment:

        def upd(p, g, m, nu, sumsq):
            n = max(int(g.size), 1)
            # scalar EMA of E[(clip g)^2]; all moment math is size-1
            nu_new = b2 * nu + (1 - b2) * (clip * clip) * (sumsq / n)
            rcp = 1.0 / (jnp.sqrt(nu_new / bc2) + ADAM_EPS)  # scalar
            gf = g.astype(jnp.float32) * clip
            m_new = b1 * m + (1 - b1) * gf
            # n-sized ops: multiplies and adds only (the scalar coefficient
            # carries the sqrt/divide) -- no elementwise sqrt/div pass
            pf = p.astype(jnp.float32)
            new_p = pf - (lr * rcp / bc1) * m_new - (lr * cfg.weight_decay) * pf
            return new_p.astype(p.dtype), m_new, nu_new

        out = [
            upd(p, g, m, nu, per_leaf[i])
            for i, (p, g, m, nu) in enumerate(
                zip(flat_p, flat_g, flat_m, flat_v)
            )
        ]
    else:

        def upd(p, g, m, v):
            gf = g.astype(jnp.float32) * clip
            m_new = b1 * m + (1 - b1) * gf
            v_new = b2 * v + (1 - b2) * gf * gf
            mhat = m_new / bc1
            vhat = v_new / bc2
            delta = mhat / (jnp.sqrt(vhat) + ADAM_EPS) + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m_new, v_new

        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamWState(step=step, m=new_m, v=new_v), lr


@jax.named_scope("optimizer")
def apply_updates(
    params,
    grads,
    state: AdamWState,
    cfg: TrainConfig,
    *,
    mma: bool = True,
    reduce_backend: Optional[str] = None,
    fused_second_moment: bool = False,
    mesh_axes=None,
):
    """One AdamW step. Returns (new_params, new_state, metrics).

    ``fused_second_moment`` must match the ``init_state`` that built
    ``state`` (scalar-v leaves). On the kernel backends one reduction
    launch feeds everything the step needs from the grads: the per-leaf
    sumsq slots (fused second moment) plus the (gnorm, clip) epilogue
    fork -- a grad leaf makes ONE HBM trip per step."""
    if fused_second_moment:
        per_leaf, gnorm, clip = global_norm_and_clip(
            grads, cfg.grad_clip, mma=mma, backend=reduce_backend,
            return_per_leaf=True, mesh_axes=mesh_axes,
        )
    else:
        per_leaf = None
        gnorm, clip = global_norm_and_clip(
            grads, cfg.grad_clip, mma=mma, backend=reduce_backend,
            mesh_axes=mesh_axes,
        )
    new_p, new_state, lr = _adamw_core(
        params, grads, state, cfg, clip=clip, per_leaf=per_leaf,
        fused_second_moment=fused_second_moment,
    )
    metrics = {"grad_norm": gnorm, "lr": lr, "clip": clip}
    return new_p, new_state, metrics


# Unsigned views for the bitwise keep/advance blend, by itemsize.
_BLEND_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _bitwise_keep(keep_old, old, new):
    """Branchless, donation-safe select: ``old`` where ``keep_old`` (a
    traced bool scalar) else ``new`` -- by integer bit-blend, NOT
    ``jnp.where``. ``select_n`` at leaf size is exactly what the guarded
    step's lowering contract forbids (``inspect.CENSUS_PRIMITIVES``); the
    blend lowers to and/or/broadcast on an unsigned view, bitcast back, so
    the kept side is BITWISE identical to its input (NaN payloads, -0.0,
    bf16 bits -- everything survives untouched). The mask is the unsigned
    wraparound ``0 - flag``: all-ones when keeping, all-zeros when
    advancing."""
    old = jnp.asarray(old)
    new = jnp.asarray(new)
    dtype = old.dtype
    itype = _BLEND_UINT[dtype.itemsize]
    mask = jnp.zeros((), itype) - keep_old.astype(itype)
    ob = jax.lax.bitcast_convert_type(old, itype)
    nb = jax.lax.bitcast_convert_type(new, itype)
    return jax.lax.bitcast_convert_type((ob & mask) | (nb & ~mask), dtype)


@dataclasses.dataclass(frozen=True)
class GuardState:
    """Loss-spike detector state: a rolling window of the last W ACCEPTED
    (non-skipped, finite) losses, how many of its slots are valid, and the
    cumulative skipped-step counter. A registered pytree so it jits and
    donates like the optimizer state."""

    window: Any  # (W,) f32 recent accepted losses
    filled: Any  # int32 valid slots (spike detection waits for a full W)
    skipped: Any  # int32 cumulative skipped steps


jax.tree_util.register_dataclass(
    GuardState, data_fields=["window", "filled", "skipped"], meta_fields=[]
)


def init_guard_state(window: int = 16) -> GuardState:
    return GuardState(
        window=jnp.zeros((int(window),), jnp.float32),
        filled=jnp.zeros((), jnp.int32),
        skipped=jnp.zeros((), jnp.int32),
    )


def _sorted_median(v):
    """Median via one sort + static slots -- jnp.median's quantile path can
    lower a select_n, which the guarded lowering contract forbids."""
    s = jnp.sort(v)
    w = v.shape[0]
    return 0.5 * (s[(w - 1) // 2] + s[w // 2])


def _finite_scalar(x):
    """is_finite without the ``is_finite`` primitive: finite iff x - x == 0
    (NaN - NaN = NaN, Inf - Inf = NaN; both compare unequal). Keeps the
    guarded lowering free of the primitives its own audit forbids."""
    return (x - x) == jnp.zeros((), x.dtype)


def _loss_spike(guard: GuardState, loss, spike_z: float):
    """Robust z-score spike test against the accepted-loss window: spike
    iff the window is full, the loss is finite (a NON-finite loss is the
    census/guard's business, not the spike detector's), and
    ``loss - median > spike_z * scale`` with the MAD-based scale
    ``1.4826 * mad + 1e-6 * |median| + 1e-12`` (the relative floor keeps a
    flat window from flagging float noise)."""
    w = guard.window.shape[0]
    med = _sorted_median(guard.window)
    mad = _sorted_median(jnp.abs(guard.window - med))
    scale = 1.4826 * mad + 1e-6 * jnp.abs(med) + 1e-12
    full = guard.filled >= w
    return full & _finite_scalar(loss) & ((loss - med) > spike_z * scale)


@jax.named_scope("optimizer")
def guarded_apply_updates(
    params,
    grads,
    state: AdamWState,
    cfg: TrainConfig,
    *,
    loss=None,
    guard: Optional[GuardState] = None,
    spike_z: float = 6.0,
    mma: bool = True,
    reduce_backend: Optional[str] = None,
    fused_second_moment: bool = False,
    mesh_axes=None,
):
    """One GUARDED AdamW step: the same single-launch statistic as
    ``apply_updates`` plus the in-launch non-finite census, and a
    branchless skip -- if any grad element is NaN/Inf (or the windowed
    loss-spike detector fires) the params AND the optimizer state pass
    through BITWISE unchanged. Returns
    ``(new_params, new_state, new_guard, metrics)``.

    Jit/donation-safe by construction: no ``lax.cond`` (both sides are one
    fused region; the update arithmetic is cheap next to the grad
    computation), no ``select_n`` and no host ``is_finite`` anywhere in
    the lowering (``inspect.assert_census_free`` gates this) -- the census
    count comes out of the reduction launch and the keep/advance choice is
    an integer bit-blend per leaf. An unskipped step is bitwise identical
    to ``apply_updates``; a skipped step's only state change is the guard
    bookkeeping.

    ``loss``/``guard`` feed the spike detector (either None disables it):
    the window records ACCEPTED finite losses only, so one spike cannot
    poison the statistic it is judged against. ``metrics['skipped']`` is
    this step's skip flag (0/1 f32) -- the supervisor's consecutive-bad-
    step counter keys off it; ``metrics['nonfinite']`` the census total.

    ``mesh_axes`` (inside a shard_map body, params/grads/state SHARDED
    along the mesh) runs the guarded step distributed: the statistic,
    census and clip come out of the fixed-order cross-device combine
    bit-identical on every replica, so the skip flag -- and therefore the
    bit-blend, the guard bookkeeping, and a supervisor's rollback counter
    keyed off ``metrics['skipped']`` -- is provably in lockstep on all
    hosts while each device touches only its own shard. The caller's
    ``loss`` must already be replicated (e.g. psum'd/combined by the loss
    computation) for the spike detector to agree.
    """
    if fused_second_moment:
        per_leaf, gnorm, clip, counts = global_norm_and_clip(
            grads, cfg.grad_clip, mma=mma, backend=reduce_backend,
            return_per_leaf=True, census=True, mesh_axes=mesh_axes,
        )
    else:
        per_leaf = None
        gnorm, clip, counts = global_norm_and_clip(
            grads, cfg.grad_clip, mma=mma, backend=reduce_backend,
            census=True, mesh_axes=mesh_axes,
        )
    nonfinite = counts[-1]
    bad = nonfinite > 0
    if loss is not None and guard is not None:
        spike = _loss_spike(guard, jnp.asarray(loss, jnp.float32), spike_z)
    else:
        spike = jnp.zeros((), bool)
    skip = bad | spike

    cand_p, cand_state, lr = _adamw_core(
        params, grads, state, cfg, clip=clip, per_leaf=per_leaf,
        fused_second_moment=fused_second_moment,
    )
    new_p = jax.tree.map(
        lambda old, new: _bitwise_keep(skip, old, new), params, cand_p
    )
    new_state = jax.tree.map(
        lambda old, new: _bitwise_keep(skip, old, new), state, cand_state
    )

    new_guard = guard
    if guard is not None:
        accept = ~skip
        record = (
            accept & _finite_scalar(jnp.asarray(loss, jnp.float32))
            if loss is not None
            else jnp.zeros((), bool)
        )
        if loss is not None:
            rolled = jnp.roll(guard.window, -1).at[-1].set(
                jnp.asarray(loss, jnp.float32)
            )
            window = _bitwise_keep(~record, guard.window, rolled)
        else:
            window = guard.window
        new_guard = GuardState(
            window=window,
            filled=jnp.minimum(
                guard.filled + record.astype(jnp.int32),
                guard.window.shape[0],
            ),
            skipped=guard.skipped + skip.astype(jnp.int32),
        )

    metrics = {
        "grad_norm": gnorm,
        "lr": lr,
        "clip": clip,
        "nonfinite": nonfinite,
        "skipped": skip.astype(jnp.float32),
        "spike": spike.astype(jnp.float32),
    }
    return new_p, new_state, new_guard, metrics
