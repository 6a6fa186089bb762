"""Jit-able training / serving steps with explicit shardings.

make_train_step: microbatched (gradient-accumulation lax.scan, f32 grad
accumulators), remat'd forward, MMA-clipped AdamW update. One function serves
single-pod and multi-pod meshes -- the mesh only changes the shardings.

make_prefill_step / make_decode_step: the serving pair. decode performs one
token step for the whole batch against resident caches (greedy sampling).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro import reduce as R
from repro.configs.base import ModelConfig, TrainConfig
from repro.launch import sharding as SH
from repro.launch.mesh import batch_axes
from repro.models import decode_step as model_decode
from repro.models import make_caches, prefill
from repro.models.model import forward_hidden
from repro.models.losses import lm_loss_chunked


def _split_batch(tokens, n_micro: int):
    gb = tokens.shape[0]
    assert gb % n_micro == 0, (gb, n_micro)
    return tokens.reshape((n_micro, gb // n_micro) + tokens.shape[1:])


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh=None,
    param_shardings=None,
    reduce_backend: str | None = None,
):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch: {"tokens": (GB, S[, K]) int32[, "image_embeds": (GB, N, d)]}.
    param_shardings (optional): NamedSharding tree; the f32 gradient
    accumulators are constrained to it so ZeRO partitioning extends to the
    accumulation buffers (otherwise GSPMD may leave them replicated).
    reduce_backend (optional): repro.reduce backend name for the optimizer's
    clipping statistic; defaults to the cfg flags' mapping.
    """
    if reduce_backend is None:
        reduce_backend = R.backend_for_flags(cfg.mma_reductions, cfg.use_pallas)
    bspec = None
    if mesh is not None:
        ba = batch_axes(mesh)
        bspec = ba if len(ba) > 1 else (ba[0] if ba else None)

    compute_grads = _make_grads_fn(cfg, tcfg, mesh, param_shardings, bspec)

    def train_step(params, opt_state, batch):
        grads, mean_loss = compute_grads(params, batch)
        new_params, new_opt, metrics = optim.apply_updates(
            params, grads, opt_state, tcfg, reduce_backend=reduce_backend,
            fused_second_moment=tcfg.fused_second_moment,
        )
        metrics = dict(metrics, loss=mean_loss)
        return new_params, new_opt, metrics

    return train_step


def _make_grads_fn(cfg, tcfg, mesh, param_shardings, bspec):
    """The microbatched (scan-accumulated, remat'd) gradient computation
    shared by the plain and the guarded train steps:
    ``compute_grads(params, batch) -> (grads, mean_loss)``."""

    # the scope names the forward pass in the compiled program's op names;
    # its backward is ``transpose(jvp(forward))``
    @jax.named_scope("forward")
    def loss_fn(params, tokens, ctx):
        h, aux = forward_hidden(params, cfg, tokens[:, :-1], ctx)
        labels = tokens[:, 1:]  # (B, S-1[, K]); chunked CE handles codebooks
        loss, parts = lm_loss_chunked(params, cfg, h, labels, aux)
        return loss, parts

    def compute_grads(params, batch):
        tokens = batch["tokens"]
        ctx = batch.get("image_embeds")
        n_micro = tcfg.microbatches
        mtoks = _split_batch(tokens, n_micro)
        mctx = _split_batch(ctx, n_micro) if ctx is not None else None
        if mesh is not None:
            mtoks = jax.lax.with_sharding_constraint(
                mtoks, NamedSharding(mesh, P(None, bspec))
            )
        if n_micro == 1:
            # nothing to accumulate: the grads keep the dtype the backward
            # pass produced (the optimizer upcasts per element). An f32
            # accumulator copy would be materialized whole for a kernel
            # that reads every leaf in one launch -- 4.7 GB at olmo-1b.
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mtoks[0], None if mctx is None else mctx[0]
            )
            if param_shardings is not None:
                grads = jax.tree.map(
                    jax.lax.with_sharding_constraint, grads, param_shardings
                )
            return grads, loss

        grad_zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        if param_shardings is not None:
            grad_zero = jax.tree.map(
                jax.lax.with_sharding_constraint, grad_zero, param_shardings
            )

        def micro(carry, xs):
            gacc, lacc = carry
            mb = xs if mctx is None else xs[0]
            cx = None if mctx is None else xs[1]
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb, cx
            )
            if param_shardings is not None:
                # reshard dW to the accumulator layout in the PRODUCED dtype
                # (bf16) BEFORE the f32 upcast -- otherwise GSPMD hoists the
                # upcast and moves the reshard traffic in f32 (2x wire;
                # Perf iteration 2b)
                grads = jax.tree.map(
                    jax.lax.with_sharding_constraint, grads, param_shardings
                )
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads
            )
            return (gacc, lacc + loss), None

        xs = mtoks if mctx is None else (mtoks, mctx)
        (grads, loss_sum), _ = jax.lax.scan(
            micro, (grad_zero, jnp.zeros((), jnp.float32)), xs
        )
        grads = jax.tree.map(lambda g: g / n_micro, grads)
        return grads, loss_sum / n_micro

    return compute_grads


def make_guarded_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh=None,
    param_shardings=None,
    reduce_backend: str | None = None,
    spike_z: float = 6.0,
    mesh_axes=None,
):
    """Returns guarded_step(params, opt_state, guard_state, batch) ->
    (params, opt_state, guard_state, metrics): the same microbatched
    gradient computation as ``make_train_step``, finished by
    ``optim.guarded_apply_updates`` -- the clip statistic's launch also
    counts NaN/Inf grad elements (in-launch census) and a poisoned or
    loss-spiking step passes params and optimizer state through BITWISE
    unchanged (``metrics['skipped']`` flags it for the supervisor's
    rollback counter). ``guard_state`` is ``optim.init_guard_state(W)``.

    ``mesh_axes`` is for calling the returned step INSIDE a shard_map body
    with params/grads sharded along those axes: the clip statistic,
    census, and skip decision then come out of the deterministic
    fixed-order cross-device combine, bit-identical on every replica.
    """
    if reduce_backend is None:
        reduce_backend = R.backend_for_flags(cfg.mma_reductions, cfg.use_pallas)
    bspec = None
    if mesh is not None:
        ba = batch_axes(mesh)
        bspec = ba if len(ba) > 1 else (ba[0] if ba else None)

    compute_grads = _make_grads_fn(cfg, tcfg, mesh, param_shardings, bspec)

    def guarded_step(params, opt_state, guard_state, batch):
        batch = dict(batch)
        # chaos drill hook: a scalar the injector drives to NaN/Inf on a
        # scheduled step; multiplying by 1.0 is bitwise identity otherwise
        scale = batch.pop("chaos_scale", None)
        grads, mean_loss = compute_grads(params, batch)
        if scale is not None:
            s = jnp.reshape(scale, (-1,))[0]
            grads = jax.tree.map(lambda g: g * s.astype(g.dtype), grads)
        new_params, new_opt, new_guard, metrics = optim.guarded_apply_updates(
            params, grads, opt_state, tcfg, loss=mean_loss,
            guard=guard_state, spike_z=spike_z,
            reduce_backend=reduce_backend,
            fused_second_moment=tcfg.fused_second_moment,
            mesh_axes=mesh_axes,
        )
        metrics = dict(metrics, loss=mean_loss)
        return new_params, new_opt, new_guard, metrics

    return guarded_step


def make_mesh_guarded_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh,
    reduce_backend: str | None = None,
    spike_z: float = 6.0,
):
    """Data-parallel guarded step under ``shard_map`` with a DETERMINISTIC
    gradient exchange: each device computes grads on its batch shard, the
    cross-device mean goes through ``fixed_order_combine`` (bit-identical
    on every replica, unlike ``psum`` whose reduction order is opaque), and
    the guarded update then runs on bit-identical inputs everywhere -- so
    the skip flag, the guard bookkeeping, and the supervisor's rollback
    counter are provably in lockstep across hosts. ``mesh`` is a 1-D data
    mesh (``make_data_mesh``); the batch's leading dim must divide its
    size.

    The batch may carry a ``chaos_scale`` array of shape (world,), sharded
    along the mesh axis like everything else: each device multiplies its
    LOCAL grads by its entry. Driving exactly one entry to NaN models one
    host's shard going bad -- the cross-device census must still skip
    EVERY host identically. Omit the key (or pass ones) for clean steps.

    Compiled with donation on (params, opt_state, guard_state).
    """
    from repro.core import collectives as coll

    if reduce_backend is None:
        reduce_backend = R.backend_for_flags(cfg.mma_reductions, cfg.use_pallas)
    (axis,) = mesh.axis_names
    compute_grads = _make_grads_fn(cfg, tcfg, None, None, None)

    def mean_over_mesh(g):
        """The data-parallel mean of one grad leaf: gathered at its own
        width, folded in f32 in device order, rounded once back (an f32
        mean of every leaf would be held whole for the optimizer's
        one-launch statistic). Stacked layer leaves go one layer at a time,
        so a gathered copy is one layer's size, not the stack's."""
        def fold(x):
            world = coll.mesh_world_size((axis,))
            total = coll.fixed_order_combine(
                x, (axis,), accum_dtype=jnp.float32
            )
            return (total / world).astype(x.dtype)

        return jax.lax.map(fold, g) if g.ndim >= 3 else fold(g)

    def body(params, opt_state, guard_state, batch):
        batch = dict(batch)
        scale = batch.pop("chaos_scale", None)
        grads, loss = compute_grads(params, batch)
        if scale is not None:
            s = jnp.reshape(scale, (-1,))[0]
            grads = jax.tree.map(lambda g: g * s.astype(g.dtype), grads)
        grads = jax.tree.map(mean_over_mesh, grads)
        world = coll.mesh_world_size((axis,))
        loss = coll.fixed_order_combine(loss, (axis,)) / world
        new_p, new_opt, new_guard, metrics = optim.guarded_apply_updates(
            params, grads, opt_state, tcfg, loss=loss, guard=guard_state,
            spike_z=spike_z, reduce_backend=reduce_backend,
            fused_second_moment=tcfg.fused_second_moment,
        )
        metrics = dict(metrics, loss=loss)
        return new_p, new_opt, new_guard, metrics

    rep = P()
    sharded = coll.shard_map_unchecked(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, P(axis)),
        out_specs=(rep, rep, rep, rep),
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2))


def make_jitted_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh=None,
    param_shardings=None,
    reduce_backend: str | None = None,
):
    """``make_train_step`` compiled with BUFFER DONATION on (params,
    opt_state): XLA reuses their device buffers for the same-shaped outputs
    instead of allocating a second copy of every weight and moment tensor,
    so the step's update writes land in place -- the other half of the
    one-HBM-trip step (the epilogue fork removes the extra norm reads; the
    donation removes the extra update writes). Callers must rebind
    ``params, opt_state = step_fn(params, opt_state, batch)`` -- the donated
    inputs are dead after the call (jax enforces this)."""
    return jax.jit(
        make_train_step(cfg, tcfg, mesh, param_shardings, reduce_backend),
        donate_argnums=(0, 1),
    )


def make_jitted_guarded_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh=None,
    param_shardings=None,
    reduce_backend: str | None = None,
    spike_z: float = 6.0,
    mesh_axes=None,
):
    """``make_guarded_train_step`` compiled with donation on (params,
    opt_state, guard_state). Safe even on skipped steps: the bitwise
    keep/advance blend writes the (unchanged) bits back into the donated
    buffers -- there is no branch whose untaken side would need the dead
    input alive."""
    return jax.jit(
        make_guarded_train_step(
            cfg, tcfg, mesh, param_shardings, reduce_backend, spike_z,
            mesh_axes,
        ),
        donate_argnums=(0, 1, 2),
    )


def make_prefill_step(cfg: ModelConfig, s_max: int):
    @jax.named_scope("model")
    def prefill_step(params, tokens, ctx=None):
        with jax.named_scope("kv_cache"):
            caches = make_caches(cfg, tokens.shape[0], s_max)
        logits, caches = prefill(params, cfg, tokens, caches, ctx)
        return logits, caches

    return prefill_step


def make_decode_step(cfg: ModelConfig, greedy: bool = True,
                     with_stats: bool = False):
    """``decode_one(params, caches, token, pos[, ctx]) -> (next token or
    logits, caches)``, and with ``with_stats`` the step's counters
    (``models.model.decode_step``) as a third item."""
    def decode_one(params, caches, token, pos, ctx=None):
        with jax.named_scope("model"):
            out = model_decode(params, cfg, token, caches, pos, ctx,
                               with_stats=with_stats)
        logits, caches = out[:2]
        if greedy:
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            nxt = logits
        return (nxt, caches) + tuple(out[2:])

    return decode_one
