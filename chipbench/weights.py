"""Random weights from the seed, made on the device in one jitted call.

The layout -- which arrays, their shapes and types -- is read off the
program's own parameter initialiser with ``jax.eval_shape``; the values
come from here: norm scales 1, biases 0, the embedding N(0, 0.02**2),
every other matrix N(0, 1/fan_in) with fan_in its second-to-last axis.
The same seed gives the same weights in every process, so the reference
can make them again after the program's state is freed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int, stream: int) -> np.ndarray:
    """Raw threefry key data for ``seed`` (an integer of any size) and a
    stream number; ``jax.random.PRNGKey`` keeps only 32 bits of it."""
    return np.random.SeedSequence(
        [int(seed) % 2**64, stream]).generate_state(2).astype(np.uint32)


def make(init_params, seed: int):
    """Weights shaped as ``init_params(key)`` would make them."""
    shapes = jax.eval_shape(init_params, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(key, path, s):
        name = getattr(path[-1], "key", None)
        if name == "scale":
            return jnp.ones(s.shape, s.dtype)
        if name == "bias":
            return jnp.zeros(s.shape, s.dtype)
        std = 0.02 if name == "e" else 1.0 / math.sqrt(s.shape[-2])
        return (jax.random.normal(key, s.shape, jnp.float32)
                * std).astype(s.dtype)

    @jax.jit
    def gen(raw):
        key = jax.random.wrap_key_data(raw, impl="threefry2x32")
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.random.fold_in(key, i), path, s)
            for i, (path, s) in enumerate(leaves)])

    return gen(key_data(seed, 0))
