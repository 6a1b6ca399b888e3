"""Qwen2 (arXiv:2407.10671): pre-norm decoder, RMSNorm, grouped-query causal
attention with RoPE (rotate-half form) and q/k/v biases, SwiGLU MLP, tied
embedding head.

The family's seeded weights in the program's layout, its plain float32 loss
and its training FLOPs per token.  Layout: ``embed`` (the tied head),
``final_norm``, ``units`` -- one dict of ``global`` blocks, each leaf stacked
over the ``num_layers`` layers -- and ``rem`` (empty).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import training_flops
from chipbench.reference import rmsnorm
from chipbench.weights import cast_params, normal


def _dense_block(key, m: dict, layers: int) -> dict:
    d, dh = m["d_model"], m["head_dim"]
    hq, hk, f = m["num_heads"] * dh, m["num_kv_heads"] * dh, m["d_ff"]
    ks = jax.random.split(key, 7)
    s_d, s_q, s_f = 1 / math.sqrt(d), 1 / math.sqrt(hq), 1 / math.sqrt(f)
    attn = {"wq": normal(ks[0], (layers, d, hq), s_d),
            "wk": normal(ks[1], (layers, d, hk), s_d),
            "wv": normal(ks[2], (layers, d, hk), s_d),
            "wo": normal(ks[3], (layers, hq, d), s_q)}
    if m["qkv_bias"]:
        attn.update(bq=jnp.zeros((layers, hq)), bk=jnp.zeros((layers, hk)),
                    bv=jnp.zeros((layers, hk)))
    if m["mlp_variant"] != "swiglu":
        raise ValueError(f"mlp_variant {m['mlp_variant']!r} not supported")
    return {"ln1": {"scale": jnp.ones((layers, d))}, "attn": attn,
            "ln2": {"scale": jnp.ones((layers, d))},
            "mlp": {"wi": normal(ks[4], (layers, d, f), s_d),
                    "wg": normal(ks[5], (layers, d, f), s_d),
                    "wo": normal(ks[6], (layers, f, d), s_f)}}


def make_params(m: dict, key: jax.Array) -> dict:
    """The parameter tree of model sizes ``m`` (a configuration file's
    ``model`` section), drawn from ``key``."""
    if not m["tie_embeddings"] or tuple(m["block_pattern"]) != ("global",):
        raise ValueError("qwen2: a tied head and one block kind, 'global'")
    k_emb, k_blk = jax.random.split(key)
    tree = {"embed": normal(k_emb, (m["vocab_size"], m["d_model"]), 0.02),
            "final_norm": {"scale": jnp.ones((m["d_model"],))},
            "units": (_dense_block(k_blk, m, m["num_layers"]),),
            "rem": ()}
    return cast_params(tree, m["param_dtype"])


def rope(x, theta):
    """x: (B, S, H, Dh); rotate-half RoPE at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dense_layer(m, p, x):
    b, s, _ = x.shape
    nh, nkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rmsnorm(x, p["ln1"]["scale"], m["norm_eps"])
    q = (h @ a["wq"] + a.get("bq", 0.0)).reshape(b, s, nh, dh)
    k = (h @ a["wk"] + a.get("bk", 0.0)).reshape(b, s, nkv, dh)
    v = (h @ a["wv"] + a.get("bv", 0.0)).reshape(b, s, nkv, dh)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    kv_of = np.arange(nh) // (nh // nkv)          # query head -> kv head
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * dh)
    x = x + o @ a["wo"]
    h = rmsnorm(x, p["ln2"]["scale"], m["norm_eps"])
    f = p["mlp"]
    return x + (jax.nn.silu(h @ f["wg"]) * (h @ f["wi"])) @ f["wo"]


def loss(m, params, batch):
    """Mean next-token cross-entropy of one worker's batch."""
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda x, p: (dense_layer(m, p, x), None))
    x, _ = jax.lax.scan(body, x, params["units"][0])
    logits = rmsnorm(x, params["final_norm"]["scale"],
                     m["norm_eps"]) @ params["embed"].T
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def _dense_layer_macs(m: dict, seq: int) -> int:
    d, dh = m["d_model"], m["head_dim"]
    hq, hk = m["num_heads"] * dh, m["num_kv_heads"] * dh
    proj = d * hq + 2 * d * hk + hq * d + 3 * d * m["d_ff"]
    attn = 2 * seq * hq            # q.k over the sequence, then probs.v
    return proj + attn


def flops_per_token(m: dict, seq: int) -> float:
    """Training FLOPs per token at sequence ``seq``: every weight matrix,
    the tied head included, and attention's scores and weighted sum over
    the whole sequence (PaLM's convention: the causal half is counted)."""
    return training_flops(m["num_layers"] * _dense_layer_macs(m, seq)
                          + m["d_model"] * m["vocab_size"])
