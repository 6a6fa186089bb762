"""Activation-sharding context.

GSPMD's solver, given FSDP-sharded weights and no activation constraints, is
free to replicate the batch and shard activations on d_model -- valid but
catastrophic (it turns data parallelism into redundant compute; caught by
the dry-run's collective analysis). The launcher pins the intended layout
here before tracing; `constrain` is a no-op when unset (CPU tests, 1
device). Model code calls `constrain(h)` at unit boundaries -- GSPMD
propagates the layout through block internals from there.
"""

from __future__ import annotations

import jax

_ACT_SHARDING = None  # NamedSharding for (batch, seq, d_model) activations


def set_activation_sharding(sharding) -> None:
    global _ACT_SHARDING
    _ACT_SHARDING = sharding


def get_activation_sharding():
    return _ACT_SHARDING


def constrain(h: jax.Array) -> jax.Array:
    if _ACT_SHARDING is None or h.ndim != 3:
        return h
    return jax.lax.with_sharding_constraint(h, _ACT_SHARDING)


def shard_map_specs(fn, in_specs, out_specs):
    """shard_map under the active mesh context (None if no context). Used to
    bypass GSPMD's gather/scatter partitioner (which falls back to full
    replication for vmapped gathers -- 'involuntary full rematerialization')
    with explicitly-local dispatch/combine regions."""
    if _ACT_SHARDING is None:
        return None
    from repro.core.collectives import shard_map_unchecked

    return shard_map_unchecked(
        fn, mesh=_ACT_SHARDING.mesh, in_specs=in_specs, out_specs=out_specs
    )


def batch_axis_entry():
    """The PartitionSpec entry for the batch dim (None if unsharded)."""
    if _ACT_SHARDING is None:
        return None
    return _ACT_SHARDING.spec[0] if len(_ACT_SHARDING.spec) else None


def constrain_moe_dispatch(t: jax.Array) -> jax.Array:
    """Pin the (B, E, C, d) expert-dispatch layout: batch over the data axes,
    experts over model (EP). Without this GSPMD reshards the vmapped gather
    through full replication (its 'involuntary full rematerialization' path;
    caught by the dry-run on the multi-pod mesh)."""
    if _ACT_SHARDING is None or t.ndim != 4:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _ACT_SHARDING.mesh
    bspec = _ACT_SHARDING.spec[0] if len(_ACT_SHARDING.spec) else None
    espec = "model" if t.shape[1] % mesh.shape["model"] == 0 else None
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(bspec, espec, None, None))
    )
