"""The benchmark's weights: random, made on the device from the seed.

The layout is the program's parameter tree, read from the program by
shape only (``jax.eval_shape`` of its initialiser), so the system under
test takes the weights as they are. The values are the benchmark's own,
drawn in one jitted call: every matrix normal with the configuration's
``initializer_range`` as its standard deviation (0.02 in Qwen3's
config.json), every norm scale 1. The reference reads these same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number, negative or past 32 bits. The ``rbg``
    generator (XLA's RngBitGenerator) draws the 600 million values in a
    fraction of the time threefry takes on the TPU."""
    s = seed % 2 ** 64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF, impl="rbg"),
                              s >> 32)


def make(layout, seed: int, std: float):
    """``layout``: a pytree of ShapeDtypeStructs; returns arrays of it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)

    @jax.jit
    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = getattr(path[-1], "key", None)
            if name == "g":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                      jnp.float32) * std
                out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return draw(seed_key(seed))
