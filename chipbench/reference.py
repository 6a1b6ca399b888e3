"""Plain float32 reference of what one global round of H-SGD computes.

Written from the published descriptions, in straightforward ``jax.numpy``
at ``highest`` matmul precision, with no kernel, cache or worker batching,
and importing nothing of the program:

* qwen2 (arXiv:2407.10671): pre-norm decoder, RMSNorm, grouped-query
  causal attention with RoPE (rotate-half form) and q/k/v biases, SwiGLU
  MLP, tied embedding head;
* mamba2 (arXiv:2405.21060): pre-norm SSD blocks -- in-projection to
  (z, x, B, C, dt), causal depthwise convolution and SiLU, the selective
  state-space map in its quadratic (dual) form over the whole sequence,
  y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,
  gating by SiLU(z), RMSNorm, out-projection; tied embedding head.  The
  program computes the same map by chunks; the quadratic form is the
  independent way to the same numbers.

The loss is the mean next-token cross-entropy.  ``first_global_round``
runs every worker's SGD steps and each level's average over the first
steps of the outermost level's period, as the hierarchy prescribes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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


LAYERS = {"global": dense_layer, "ssd": ssd_layer}


def loss(m, params, batch):
    """Mean next-token cross-entropy of one worker's batch."""
    layer = LAYERS[m["block_pattern"][0]]
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda x, p: (layer(m, p, x), None))
    x, _ = jax.lax.scan(body, x, params["units"][0])
    logits = rmsnorm(x, params["final_norm"]["scale"],
                     m["norm_eps"]) @ params["embed"].T
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def sync_level(t: int, periods) -> int:
    """Level whose average follows step ``t`` (0-based), the most global
    one first; 0 for none."""
    for level, period in enumerate(periods, 1):
        if (t + 1) % period == 0:
            return level
    return 0


def group_leaders(n: int, group_sizes, level: int) -> np.ndarray:
    """For each worker, the first worker of its level-``level`` group: the
    groups are consecutive blocks of ``prod(group_sizes[level-1:])``."""
    size = int(np.prod(group_sizes[level - 1:]))
    return (np.arange(n) // size) * size


def _norm(x):
    # no flattening: a reshape of a large tiled array to one long axis
    # makes the TPU compiler split it into tens of thousands of pieces
    return jnp.sqrt(jnp.sum(jnp.square(x)))


@jax.jit
def leaf_norms(tree):
    return jnp.stack([_norm(x) for x in jax.tree.leaves(tree)])


@jax.jit
def delta_norms(tree, base):
    return jnp.stack([_norm(x - y) for x, y in
                      zip(jax.tree.leaves(tree), jax.tree.leaves(base))])


def first_global_round(m, group_sizes, periods, lr, params0, batches):
    """Run the first ``len(batches)`` steps of the outermost level's period
    from ``params0`` (float32).

    ``batches[t]`` holds step ``t``'s ``(n_workers, batch, seq)`` tokens and
    targets.  Returns ``(losses, first_grad_norms, deltas)``: the workers'
    mean loss at each step, the per-leaf gradient norms of worker 0's first
    step, and for every step that ends in an average ``{step: (n, leaves)
    array}`` of each worker's per-leaf ``|params - params0|`` after it."""
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(lambda p, b: loss(m, p, b)))
        step = jax.jit(lambda p, g: jax.tree.map(lambda a, b: a - lr * b,
                                                 p, g))
        mean = jax.jit(lambda *ts: jax.tree.map(
            lambda *xs: sum(xs) / len(xs), *ts))
        n = int(np.prod(group_sizes))
        workers = [params0] * n
        losses, grad0, deltas = [], None, {}
        for t in range(len(batches)):
            ls = []
            for w in range(n):
                row = {k: v[w] for k, v in batches[t].items()}
                val, g = vg(workers[w], row)
                if grad0 is None:
                    grad0 = np.asarray(leaf_norms(g))
                workers[w] = step(workers[w], g)
                ls.append(float(val))
                del g
            losses.append(float(np.mean(ls)))
            level = sync_level(t, periods)
            if level:
                lead = group_leaders(n, group_sizes, level)
                avg = {int(i): mean(*[workers[w] for w in range(n)
                                      if lead[w] == i])
                       for i in np.unique(lead)}
                workers = [avg[int(lead[w])] for w in range(n)]
                deltas[t + 1] = np.stack([np.asarray(delta_norms(x, params0))
                                          for x in workers])
        return losses, grad0, deltas
