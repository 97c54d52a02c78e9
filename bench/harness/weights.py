"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving engine reads (stacked layers, heads as
their own axes) and is made by the benchmark, not by the program, so the
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


def shapes(d) -> dict:
    """Leaf shape and fan-in of every weight, as the engine lays them out."""
    L, D, H, K, hd, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                            d.head_dim, d.d_ff, d.vocab)
    return {
        "embed": ((V, D), None),
        "final_norm": ((D,), "norm"),
        "lm_head": ((D, V), D),
        "layers": {
            "ln1": ((L, D), "norm"),
            "ln2": ((L, D), "norm"),
            "attn": {"wq": ((L, D, H, hd), D), "wk": ((L, D, K, hd), D),
                     "wv": ((L, D, K, hd), D), "wo": ((L, H, hd, D), H * hd)},
            "mlp": {"w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
                    "w_down": ((L, F, D), F)},
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make(d, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weights of ``d`` for ``seed``, on the default device."""
    spec = shapes(d)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)

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
