"""Mamba-2 (arXiv:2405.21060): pre-norm SSD blocks -- in-projection to
(z, x, B, C, dt), causal depthwise convolution and SiLU, the selective
state-space map, gating by SiLU(z), RMSNorm, out-projection -- and a tied
embedding head.

The family's seeded weights in the program's layout, its plain float32 loss
and its training FLOPs per token.  The reference computes the state-space
map in its quadratic (dual) form over the whole sequence,
y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t;
the program computes it by chunks, so this is the independent way to the
same numbers.  Layout: ``embed`` (the tied head), ``final_norm``,
``units`` -- one dict of ``ssd`` blocks stacked over the ``num_layers``
layers -- and ``rem`` (empty).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.flops import training_flops
from chipbench.reference import rmsnorm
from chipbench.weights import cast_params, normal


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
            "ssd": {"in_proj": normal(ks[0], (layers, d, 2 * di + 2 * ns + nh),
                                      1 / math.sqrt(d)),
                    "conv": {"w": normal(ks[1], (layers, w, conv_dim),
                                         1 / math.sqrt(w)),
                             "b": jnp.zeros((layers, conv_dim))},
                    "A_log": jnp.log(a),
                    "D": jnp.ones((layers, nh)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "out_norm": {"scale": jnp.ones((layers, di))},
                    "out_proj": normal(ks[2], (layers, di, d),
                                       1 / math.sqrt(di))}}


def make_params(m: dict, key: jax.Array) -> dict:
    """The parameter tree of model sizes ``m`` (a configuration file's
    ``model`` section), drawn from ``key``."""
    if not m["tie_embeddings"] or tuple(m["block_pattern"]) != ("ssd",):
        raise ValueError("mamba2: a tied head and one block kind, 'ssd'")
    k_emb, k_blk = jax.random.split(key)
    tree = {"embed": normal(k_emb, (m["vocab_size"], m["d_model"]), 0.02),
            "final_norm": {"scale": jnp.ones((m["d_model"],))},
            "units": (_ssd_block(k_blk, m, m["num_layers"]),),
            "rem": ()}
    return cast_params(tree, m["param_dtype"])


def ssd_layer(m, p, x):
    b, s, d = x.shape
    ns, ph = m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * d
    nh = di // ph
    q = p["ssd"]
    h = rmsnorm(x, p["ln1"]["scale"], m["norm_eps"]) @ q["in_proj"]
    z, xbc, dt = h[..., :di], h[..., di:2 * di + 2 * ns], h[..., 2 * di + 2 * ns:]
    w = q["conv"]["w"]                              # (width, channels)
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = q["conv"]["b"] + sum(pad[:, i:i + s] * w[i] for i in range(width))
    xbc = jax.nn.silu(conv)
    xs, B, C = xbc[..., :di], xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt + q["dt_bias"])         # (b, s, nh)
    A = -jnp.exp(q["A_log"])
    xh = xs.reshape(b, s, nh, ph)
    cs = jnp.cumsum(dt * A, axis=1)                 # (b, s, nh)
    seg = cs[:, :, None, :] - cs[:, None, :, :]     # (b, t, s, nh)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", C, B)
    y = jnp.einsum("bts,btsh,bsh,bshp->bthp", cb, decay, dt, xh)
    y = (y + xh * q["D"][:, None]).reshape(b, s, di) * jax.nn.silu(z)
    y = rmsnorm(y, q["out_norm"]["scale"], m["norm_eps"])
    return x + y @ q["out_proj"]


def loss(m, params, batch):
    """Mean next-token cross-entropy of one worker's batch."""
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda x, p: (ssd_layer(m, p, x), None))
    x, _ = jax.lax.scan(body, x, params["units"][0])
    logits = rmsnorm(x, params["final_norm"]["scale"],
                     m["norm_eps"]) @ params["embed"].T
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def _ssd_layer_macs(m: dict, seq: int) -> int:
    d, ns, ph, q = (m["d_model"], m["ssm_state"], m["ssm_head_dim"],
                    m["ssm_chunk"])
    di = m["ssm_expand"] * d
    nh = di // ph
    proj = d * (2 * di + 2 * ns + nh) + di * d
    conv = m["ssm_conv_width"] * (di + 2 * ns)
    chunk = q * ns + q * di + 2 * di * ns   # C.B, intra output, state, inter
    return proj + conv + chunk


def flops_per_token(m: dict, seq: int) -> float:
    """Training FLOPs per token: every weight matrix, the tied head
    included, the depthwise convolution, and the chunked SSD algorithm's
    terms at the configuration's chunk size (C.B within a chunk, the
    intra-chunk output, each chunk's state and the inter-chunk output)."""
    return training_flops(m["num_layers"] * _ssd_layer_macs(m, seq)
                          + m["d_model"] * m["vocab_size"])
