"""Seeded weights in the parameter layout the program trains.

The benchmark makes the weights, not the program, so that the plain
reference (``reference.py``) starts from the same values without taking
anything the program made.  One jitted call builds the whole tree on the
device, in the dtype the configuration trains it in.

Layout (the program's): ``embed`` (tied head), ``final_norm``, ``units`` --
one dict per block kind of the pattern, each leaf stacked over the
``num_layers`` layers -- and ``rem`` (empty: both families repeat a one-kind
pattern).
"""
from __future__ import annotations

import math

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


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _dense_block(key, m: dict, layers: int) -> dict:
    d, dh = m["d_model"], m["head_dim"]
    hq, hk, f = m["num_heads"] * dh, m["num_kv_heads"] * dh, m["d_ff"]
    ks = jax.random.split(key, 7)
    s_d, s_q, s_f = 1 / math.sqrt(d), 1 / math.sqrt(hq), 1 / math.sqrt(f)
    attn = {"wq": _normal(ks[0], (layers, d, hq), s_d),
            "wk": _normal(ks[1], (layers, d, hk), s_d),
            "wv": _normal(ks[2], (layers, d, hk), s_d),
            "wo": _normal(ks[3], (layers, hq, d), s_q)}
    if m["qkv_bias"]:
        attn.update(bq=jnp.zeros((layers, hq)), bk=jnp.zeros((layers, hk)),
                    bv=jnp.zeros((layers, hk)))
    if m["mlp_variant"] != "swiglu":
        raise ValueError(f"mlp_variant {m['mlp_variant']!r} not supported")
    return {"ln1": {"scale": jnp.ones((layers, d))}, "attn": attn,
            "ln2": {"scale": jnp.ones((layers, d))},
            "mlp": {"wi": _normal(ks[4], (layers, d, f), s_d),
                    "wg": _normal(ks[5], (layers, d, f), s_d),
                    "wo": _normal(ks[6], (layers, f, d), s_f)}}


def _ssd_block(key, m: dict, layers: int) -> dict:
    """Mamba-2 block with the published initialisation of the SSM
    parameters: A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1]
    (through the inverse softplus of ``dt_bias``), D = 1."""
    d, ns = m["d_model"], m["ssm_state"]
    di = m["ssm_expand"] * d
    nh = di // m["ssm_head_dim"]
    conv_dim, w = di + 2 * ns, m["ssm_conv_width"]
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[3], (layers, nh), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (layers, nh), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {"ln1": {"scale": jnp.ones((layers, d))},
            "ssd": {"in_proj": _normal(ks[0], (layers, d, 2 * di + 2 * ns + nh),
                                       1 / math.sqrt(d)),
                    "conv": {"w": _normal(ks[1], (layers, w, conv_dim),
                                          1 / math.sqrt(w)),
                             "b": jnp.zeros((layers, conv_dim))},
                    "A_log": jnp.log(a),
                    "D": jnp.ones((layers, nh)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "out_norm": {"scale": jnp.ones((layers, di))},
                    "out_proj": _normal(ks[2], (layers, di, d),
                                        1 / math.sqrt(di))}}


# leaves the program keeps in float32 whatever its param dtype
_F32_LEAVES = ("A_log", "D", "dt_bias")


def make_params(m: dict, key: jax.Array) -> dict:
    """The parameter tree of model sizes ``m`` (a configuration file's
    ``model`` section), drawn from ``key``."""
    if not m["tie_embeddings"]:
        raise ValueError("only tied embeddings are supported")
    kinds = tuple(m["block_pattern"])
    if len(kinds) != 1:
        raise ValueError(f"block pattern {kinds} not supported")
    block = {"global": _dense_block, "ssd": _ssd_block}[kinds[0]]
    k_emb, k_blk = jax.random.split(key)
    tree = {"embed": _normal(k_emb, (m["vocab_size"], m["d_model"]), 0.02),
            "final_norm": {"scale": jnp.ones((m["d_model"],))},
            "units": (block(k_blk, m, m["num_layers"]),),
            "rem": ()}
    dtype = jnp.dtype(m["param_dtype"])

    def cast(path, x):
        name = getattr(path[-1], "key", None)
        return x if name in _F32_LEAVES else x.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, tree)


def params_fn(m: dict):
    """``key -> params``, jitted: the whole tree in one device call."""
    return jax.jit(lambda key: make_params(m, key))
