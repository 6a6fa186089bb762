"""Logical-axes -> mesh sharding rules (GSPMD, hybrid FSDP + TP + EP).

Parameters carry logical axis names from models/params.py; RULES maps them
onto mesh axes. The default is the hybrid used by production LM stacks:

  tensor-parallel  : ffn / heads / kv_heads / experts / inner / vocab -> "model"
  FSDP (ZeRO-3)    : embed (the d_model dim present in every matrix) -> "data"
                     -- parameter storage is sharded over the data axis and
                     all-gathered per layer by GSPMD; optimizer state (which
                     mirrors param sharding) is likewise partitioned.
  pod axis         : pure data parallelism (params replicated across pods;
                     gradients all-reduced over "pod").

Caches and activations: batch -> all data axes; head/state dims -> "model".

Rules are a plain dict so the perf loop can swap them (e.g. seq-parallel
variants) without touching model code.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES: dict[str, str | tuple | None] = {
    "vocab": "model",
    "embed": "data",
    "ffn": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    "inner": "model",
}

# Pure-TP variant (no FSDP): used by the perf loop for small models where
# per-layer all-gathers cost more than the replicated storage.
TP_ONLY_RULES = dict(DEFAULT_RULES, embed=None)

# 100B+ models (dbrx): FSDP over the pod axis as well -- params + optimizer
# state shard over all 512 chips instead of replicating across pods. On the
# single-pod mesh the absent "pod" axis is skipped automatically.
BIG_MODEL_RULES = dict(DEFAULT_RULES, embed=("pod", "data"))

# <3B models: DP+FSDP only. TP=16 over-parallelizes small layers -- the
# per-layer Megatron activation all-reduces dominate the step (olmo train:
# 144.8 GB -> 25-47 GB wire/device/step; EXPERIMENTS.md Perf iteration 4).
# Experts keep EP (capacity), vocab keeps the sharded CE head.
SMALL_MODEL_RULES = dict(
    DEFAULT_RULES, ffn=None, heads=None, kv_heads=None, inner=None
)


def _is_axes_leaf(a) -> bool:
    return a is None or (
        isinstance(a, tuple) and all(x is None or isinstance(x, str) for x in a)
    )


def spec_for(axes, rules: dict[str, str | None], mesh: Mesh, shape=None):
    """One logical-axes tuple -> PartitionSpec (skipping absent mesh axes).

    With ``shape`` given, a partition is dropped when the dim is smaller than
    the mesh axis (GSPMD cannot shard dim < n_shards; non-divisible-but-
    larger dims are allowed and padded)."""
    if axes is None:
        return P()
    used = set()
    parts = []
    for i, name in enumerate(axes):
        m = rules.get(name) if name else None
        if isinstance(m, str):
            m = (m,)
        cand = tuple(
            ax for ax in (m or ()) if ax in mesh.axis_names and ax not in used
        )
        deg = 1
        for ax in cand:
            deg *= mesh.shape[ax]
        # jit in_shardings require dims divisible by the mesh axes (e.g.
        # mamba2's vocab 50280 % 16 != 0 -> embed falls back to d_model/FSDP)
        if cand and (shape is None or shape[i] % deg == 0):
            parts.append(cand if len(cand) > 1 else cand[0])
            used.update(cand)
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def batch_partition(mesh: Mesh, global_batch: int):
    """Batch PartitionSpec entry over the data axes, or None when the batch
    does not divide them (long_500k batch=1 stays replicated)."""
    from repro.launch.mesh import batch_axes

    ba = batch_axes(mesh)
    deg = 1
    for ax in ba:
        deg *= mesh.shape[ax]
    if not ba or global_batch % deg != 0:
        return None
    return ba if len(ba) > 1 else ba[0]


def param_shardings(axes_tree, mesh: Mesh, rules=None, shapes_tree=None):
    """axes tree (+ optional matching ShapeDtypeStruct tree) -> NamedShardings."""
    rules = rules or DEFAULT_RULES
    if shapes_tree is None:
        return jax.tree.map(
            lambda a: NamedSharding(mesh, spec_for(a, rules, mesh)),
            axes_tree,
            is_leaf=_is_axes_leaf,
        )
    flat_axes = jax.tree.flatten(axes_tree, is_leaf=_is_axes_leaf)[0]
    flat_shapes, treedef = jax.tree.flatten(shapes_tree)
    out = [
        NamedSharding(mesh, spec_for(a, rules, mesh, s.shape))
        for a, s in zip(flat_axes, flat_shapes)
    ]
    return jax.tree.unflatten(treedef, out)


def like_tree(tree, sharding_tree):
    """Broadcast a sharding tree over a same-structure value tree (e.g.
    optimizer m/v mirror the params)."""
    return jax.tree.map(lambda _, s: s, tree, sharding_tree)


# ----------------------------- activations/caches ----------------------------


def batch_spec(mesh: Mesh, extra: tuple = ()) -> P:
    from repro.launch.mesh import batch_axes

    ba = batch_axes(mesh)
    return P(ba if len(ba) > 1 else (ba[0] if ba else None), *extra)


def cache_shardings(caches_shape, cfg, mesh: Mesh):
    """PartitionSpec tree for decode caches, keyed on leaf names.

    k/v:   (B, Hkv, S, D)   -> (batch, model*, None, None)
    ckv:   (B, S, R)        -> (batch, None, None)      [MLA latent]
    k_rope: (B, S, dr)      -> (batch, None, None)      [MLA rope key]
    conv:  (B, K-1, C)      -> (batch, None, model)
    state: (B, H, P, N)     -> (batch, model, None, None)  [SSD]
    h:     (B, W)           -> (batch, model)              [RG-LRU]
    slot_pos: replicated
    (* only when the head count divides the model axis -- MQA kv=1 and
     dbrx kv=8 fall back to replicated-or-padded per GSPMD.)
    Stacked caches (under ``units``) carry a leading layer axis that is
    never sharded; the unstacked ``lead`` (leading dense layers) and
    ``tail`` caches have none.
    """
    from repro.launch.mesh import batch_axes

    ba = batch_axes(mesh)
    b = ba if len(ba) > 1 else (ba[0] if ba else None)

    model_n = mesh.shape["model"]
    from repro.launch.mesh import batch_axes as _ba
    data_n = 1
    for ax in _ba(mesh):
        data_n *= mesh.shape[ax]

    def leaf_spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        stacked = any(
            getattr(k, "key", None) == "units" for k in path
        )
        shape = leaf.shape[1:] if stacked else leaf.shape
        bspec = b if (shape and shape[0] % data_n == 0) else None

        def mdl(dim_ix):
            return "model" if shape[dim_ix] % model_n == 0 else None

        if name in ("k", "v") and len(shape) == 4:
            # prefer head sharding (softmax stays device-local); fall back to
            # SEQUENCE sharding (split-KV decode: per-shard partial softmax,
            # small cross-model AR) when Hkv does not divide the model axis.
            # Never shard d_head -- contracting a sharded minor dim makes
            # GSPMD replicate the cache in f32 (dry-run: 12.9 GB on musicgen
            # decode_32k; see EXPERIMENTS.md Perf iteration 3).
            if mdl(1):
                s = P(bspec, "model", None, None)
            else:
                s = P(bspec, None, mdl(2), None)
        elif name in ("ckv", "k_rope"):
            # MLA latent: split-KV over sequence (attention contracts s)
            s = P(bspec, mdl(1), None)
        elif name == "conv":
            s = P(bspec, None, mdl(2))
        elif name == "state":
            s = P(bspec, mdl(1), None, None)
        elif name == "h":
            s = P(bspec, mdl(1))
        elif name == "slot_pos":
            s = P(*([None] * len(shape)))
        else:
            s = P(*([bspec] + [None] * (len(shape) - 1)))
        parts = ([None] if stacked else []) + list(s)
        parts = parts[: len(leaf.shape)] + [None] * (len(leaf.shape) - len(parts))
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(leaf_spec, caches_shape)
