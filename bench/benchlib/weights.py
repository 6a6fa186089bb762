"""Weights made by the benchmark from the seed, in the program's parameter
layout.

The program's tree layout (leaf paths and shapes) is read with
``jax.eval_shape`` of its initialiser; no value of the program's is used.
Each leaf, and each layer of a stacked leaf, gets its own key from the
seed and its path, so the plain reference regenerates any one layer alone:

- norm scales: ones;
- the embedding table ``(vocab, d)``: normal / sqrt(d);
- a matrix ``(in, out)``: normal / sqrt(in).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def leaf_paths(model) -> list:
    """``[(path, ShapeDtypeStruct)]`` of the program's parameter tree."""
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                model)[0])
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(jax.tree_util.keystr(p), s) for p, s in flat]


def _stacked(path: str) -> bool:
    return path.startswith("['units']")


def _scale(path: str, shape) -> float | None:
    if path.endswith("['scale']") or path.endswith("['bias']"):
        return None
    if "['embed']" in path:
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5


def layer_value(key, path: str, shape, dtype):
    """One leaf (or one layer of a stacked leaf) from its own key."""
    scale = _scale(path, shape)
    if scale is None:
        fill = 0.0 if path.endswith("['bias']") else 1.0
        return jnp.full(shape, fill, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def leaf_value(key, path: str, shape, dtype):
    if not _stacked(path):
        return layer_value(key, path, shape, dtype)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(shape[0]))
    return jax.vmap(lambda k: layer_value(k, path, shape[1:], dtype))(keys)


def make_params(model, key):
    """The whole tree, on the device, in one jitted call."""
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                model)[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        leaves = [leaf_value(k, jax.tree_util.keystr(p), s.shape, s.dtype)
                  for p, s in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)


def layer_of(key, path: str, shape, dtype, layer: int | None):
    """Layer ``layer`` of a stacked leaf (or the whole unstacked leaf), as
    ``make_params`` made it."""
    if layer is None:
        return layer_value(key, path, shape, dtype)
    return layer_value(jax.random.fold_in(key, layer), path, shape[1:], dtype)
