"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: Optional[int] = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, H, D) (GQA repeat done by caller).
    Causal alignment assumes q position i == k position i (Sq == Sk)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sequential (non-chunked) SSD recurrence — the ground truth.

    x: (Bt, S, H, P); dt: (Bt, S, H) >= 0; A: (H,) negative; B, C: (Bt, S, N).
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;  y_t = C_t . h_t
    """
    bt, s, h, p = x.shape
    n = B.shape[-1]

    def step(carry, inp):
        xt, dtt, Bt, Ct = inp
        dA = jnp.exp(dtt * A)                                   # (Bt,H)
        carry = carry * dA[..., None, None] + \
            jnp.einsum("bh,bn,bhp->bhpn", dtt, Bt, xt)
        y = jnp.einsum("bn,bhpn->bhp", Ct, carry)
        return carry, y

    init = jnp.zeros((bt, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(B.astype(jnp.float32), 1, 0),
          jnp.moveaxis(C.astype(jnp.float32), 1, 0))
    final, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def _blocked(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """(R, C) -> (R, nb, block) zero-padded view, plus nb."""
    r, c = x.shape
    nb = -(-c // block)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, nb * block - c)))
    return xp.reshape(r, nb, block), nb


def int8_ref(x: jax.Array, block: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-block max-scale int8 oracle: (q (R, C), scale (R, nb), roundtrip)."""
    r, c = x.shape
    xb, nb = _blocked(x, block)
    scale = jnp.abs(xb).max(axis=-1) / 127.0                    # (R, nb)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(xb * inv[..., None]), -127, 127)
    rt = (q * scale[..., None]).reshape(r, nb * block)[:, :c]
    return q.astype(jnp.int8).reshape(r, nb * block)[:, :c], scale, rt


def int8_scale_quant_ref(x: jax.Array, scale: jax.Array,
                         block: int) -> jax.Array:
    """Shared-scale int8 quantization oracle: q = clip(round(x / scale))
    per block, with a zero scale mapping to q = 0."""
    r, c = x.shape
    xb, nb = _blocked(x, block)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(xb * inv[..., None]), -127, 127)
    return q.astype(jnp.int8).reshape(r, nb * block)[:, :c]


def topk_reduce_ref(vals: jax.Array, idx: jax.Array, size: int) -> jax.Array:
    """Scatter-add oracle for the top-k decode-reduce: (M, K) sparse
    payloads summed into one dense (size,) f32 buffer."""
    return jnp.zeros((size,), jnp.float32).at[idx.ravel()].add(
        vals.ravel().astype(jnp.float32))


def sign_ref(x: jax.Array, block: int) -> Tuple[jax.Array, jax.Array]:
    """1-bit sign oracle: (scale (R, nb) = mean|x| over real entries,
    roundtrip (R, C) = +-scale by sign(x), zeros counted as +)."""
    r, c = x.shape
    xb, nb = _blocked(x, block)
    counts = np.full((nb,), block, np.float32)
    counts[-1] = c - (nb - 1) * block
    scale = jnp.abs(xb).sum(axis=-1) / counts                   # (R, nb)
    rt = jnp.where(xb >= 0, scale[..., None], -scale[..., None])
    return scale, rt.reshape(r, nb * block)[:, :c]


def rglru_ref(a: jax.Array, b: jax.Array,
              h0: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.
    a, b: (B, S, W). Returns (h_1..h_S stacked, h_S)."""
    def step(carry, inp):
        at, bt = inp
        carry = at * carry + bt
        return carry, carry

    bt = a.shape[0]
    w = a.shape[-1]
    init = jnp.zeros((bt, w), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    final, hs = jax.lax.scan(
        step, init, (jnp.moveaxis(a.astype(jnp.float32), 1, 0),
                     jnp.moveaxis(b.astype(jnp.float32), 1, 0)))
    return jnp.moveaxis(hs, 0, 1), final
