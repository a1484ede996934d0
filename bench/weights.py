"""Seeded random weights, made on the device in one jitted call.

The tree has the layout of the program's parameters (its ``abstract_params``
gives the shapes); the values are the benchmark's own: normal(0, 0.02) for
every matrix and 1 + normal(0, 0.3) for every norm scale (so that a scale the
program drops, misplaces or transposes changes its logits), in the dtype they
are served in.
Each leaf, and each layer of a stacked leaf, draws from its own key, so the
reference can make the same tree again after the program's state is freed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02
NORM_STD = 0.3


def key_for(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the rest
    is folded in, so seeds that differ only above bit 32 still differ."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _is_norm(path) -> bool:
    return getattr(path[-1], "key", None) == "scale"


def _is_stacked(path) -> bool:
    return any(getattr(p, "key", None) == "groups" for p in path)


def make_weights(abstract, seed: int):
    """Materialise ``abstract`` (a ShapeDtypeStruct tree) from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def draw(key, shape, dtype, norm):
        x = jax.random.normal(key, shape, jnp.float32)
        return (1.0 + NORM_STD * x if norm else STD * x).astype(dtype)

    def leaf(key, path, sds):
        norm = _is_norm(path)
        if _is_stacked(path):
            return jax.lax.map(
                lambda i: draw(jax.random.fold_in(key, i), sds.shape[1:], sds.dtype, norm),
                jnp.arange(sds.shape[0]))
        return draw(key, sds.shape, sds.dtype, norm)

    def gen(key):
        vals = [leaf(jax.random.fold_in(key, i), path, sds)
                for i, (path, sds) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(gen)(key_for(seed))
