"""What every family's seeded weights share.

The benchmark makes the weights, not the program, so that the plain
reference starts from the same values without taking anything the program
made.  Each family (``families/<family>.py``) draws its tree in the
program's layout with ``make_params(m, key)``; ``params_fn`` makes it one
jitted call on the device, in the dtype the configuration trains it in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that tells apart every whole-number seed (``PRNGKey``
    alone keeps only the low 32 bits of a large one)."""
    key = jax.random.PRNGKey(1 if seed < 0 else 0)
    s = abs(int(seed))
    while True:
        key = jax.random.fold_in(key, s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            return key


def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


# leaves the program keeps in float32 whatever its param dtype
F32_LEAVES = ("A_log", "D", "dt_bias")


def cast_params(tree, dtype):
    """``tree`` in ``dtype``, but for the leaves named in ``F32_LEAVES``."""
    dtype = jnp.dtype(dtype)

    def cast(path, x):
        name = getattr(path[-1], "key", None)
        return x if name in F32_LEAVES else x.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, tree)


def params_fn(family, m: dict):
    """``key -> params`` of ``family`` at model sizes ``m``, jitted: the
    whole tree in one device call."""
    return jax.jit(lambda key: family.make_params(m, key))
