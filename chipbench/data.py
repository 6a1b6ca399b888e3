"""Seeded training tokens, made on the device in one call.

``lm_rows`` is a copy of the program's synthetic generator
(``repro.data.synthetic.synth_lm_batch``): Markov-ish tokens whose row for
(seed, step, worker) is a pure function of the three, so every worker and
every step gets rows of its own.  ``pool_fn`` draws a pool of per-step
``(n_workers, batch, seq)`` batches in one jitted call; the harness cycles
through it, which stands for a loader that prefetches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lm_rows(key: jax.Array, batch: int, seq_len: int, vocab: int) -> dict:
    """``batch`` rows of ``seq_len`` tokens and their next-token targets:
    each token follows ``(prev * 7 + 1) % vocab`` with probability 0.75 and
    is uniform otherwise."""
    k1, k2 = jax.random.split(key)
    rand = jax.random.randint(k1, (batch, seq_len + 1), 0, vocab)
    keep = jax.random.bernoulli(k2, 0.75, (batch, seq_len))

    def step(tok, inp):
        k, r = inp
        nxt = jnp.where(k, (tok * 7 + 1) % vocab, r)
        return nxt, nxt

    _, rest = jax.lax.scan(step, rand[:, 0], (keep.T, rand[:, 1:].T))
    toks = jnp.concatenate([rand[:, :1], rest.T], axis=1)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def make_pool(key: jax.Array, steps: int, n_workers: int, batch: int,
              seq_len: int, vocab: int):
    """A tuple of ``steps`` batches, each ``{"tokens", "targets"}`` of shape
    ``(n_workers, batch, seq_len)``; step ``t``, worker ``w`` is drawn from
    ``fold_in(fold_in(key, t), w)``."""
    def one(t, w):
        k = jax.random.fold_in(jax.random.fold_in(key, t), w)
        return lm_rows(k, batch, seq_len, vocab)

    ts = jnp.arange(steps)
    ws = jnp.arange(n_workers)
    rows = jax.vmap(lambda t: jax.vmap(lambda w: one(t, w))(ws))(ts)
    return tuple({k: v[t] for k, v in rows.items()} for t in range(steps))


def pool_fn(steps: int, n_workers: int, batch: int, seq_len: int,
            vocab: int):
    """``key -> pool``, jitted: the whole pool in one device call."""
    return jax.jit(lambda key: make_pool(key, steps, n_workers, batch,
                                         seq_len, vocab))
