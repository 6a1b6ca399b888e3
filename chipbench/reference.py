"""Plain float32 reference of what one global round of H-SGD computes.

Written from the published descriptions, in straightforward ``jax.numpy``
at ``highest`` matmul precision, with no kernel, cache or worker batching,
and importing nothing of the program.  Each family's layers and loss (the
mean next-token cross-entropy) are in ``families/<family>.py``; what is
here is what every family shares: ``first_global_round`` runs every
worker's SGD steps on a family's loss and each level's average over the
first steps of the outermost level's period, as the hierarchy prescribes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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


def first_global_round(loss, m, group_sizes, periods, lr, params0, batches):
    """Run the first ``len(batches)`` steps of the outermost level's period
    from ``params0`` (float32), on the family's ``loss(m, params, batch)``.

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
