"""Seeded float32 weights, made on the device in one jitted call.

The benchmark makes the weights itself, in the checkpoint layout the
program loads (taken from the shapes of ``model.init``, never its values),
so the reference and the program start from the same numbers and the
reference uses nothing the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf(name: str, shape, key) -> jax.Array:
    last = name.rsplit("/", 1)[-1]
    if last == "table":                                   # embedding
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if last == "w":                                       # linear (d_in, d_out)
        bound = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if last == "scale":                                   # norm gain
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if last in ("b", "bias"):                             # biases
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(f"no initialiser for weight {name!r}")


def leaf_names(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def make_weights(shape_tree, seed: int, device=None):
    """Weights for every leaf of ``shape_tree`` (``ShapeDtypeStruct``s),
    drawn from ``seed``; placed on ``device`` (default: the first)."""
    names = leaf_names(shape_tree)
    flat, treedef = jax.tree_util.tree_flatten(shape_tree)

    def build(key):
        return treedef.unflatten([
            _leaf(n, s.shape, jax.random.fold_in(key, i))
            for i, (n, s) in enumerate(zip(names, flat))])

    key = jax.random.PRNGKey(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
