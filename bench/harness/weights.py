"""Seeded random weights, made on the device in one jitted call.

The tree is the architecture's ``shapes(dims)`` (``archs/<arch>.py``), in
the layout the serving engine reads (stacked layers, heads as their own
axes), and is made by the benchmark, not by the program, so the
reference and the engine are handed the same arrays and the reference
takes nothing the program made.  Matrices are normal with variance
1/fan_in, norm scales are 1 + 0.1 * normal, all in the configuration's
bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the high bits are folded in)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make(shapes: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weights of the tree ``shapes`` for ``seed``, on the default
    device.  Each leaf of ``shapes`` is ``(shape, fan)``: ``fan`` is the
    fan-in, ``"norm"`` for a norm scale, or None for a unit normal."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_spec)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if fan == "norm":
                w = 1.0 + 0.1 * z
            elif fan is None:
                w = z
            else:
                w = z * (1.0 / fan) ** 0.5
            out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return init(seed_key(seed))
