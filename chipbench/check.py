"""The comparison that decides ``correct``.

Set-up drives the program's own state through its first global round with
the window's call (``run_rounds``), split at each average so the state can
be read after every sync, on the window's compiled round programs and the
window's batch pool.  ``program_readings`` takes per-worker, per-leaf norms
on the device after each such call, where the state lies: on a mesh each
chip reads its own worker.  After the window the plain reference
(``reference.first_global_round``) replays the same round from the same
weights and batches, and ``compare`` reduces both to three numbers, each
held to its limit from ``limits/<workload>.json``:

* ``loss_rel``: the largest relative gap between the program's and the
  reference's loss over the round's steps (mean over workers);
* ``update_gap``: the largest gap, over every average of the round, worker
  and leaf, between the norm of the program's change of that leaf since
  the start and the reference's, over the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``sync_spread``: the largest distance, after each average, between a
  worker's leaf and its group's first worker's, on the same scale.

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's are left out of the last two: they move by rounding alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NUMBERS = ("loss_rel", "update_gap", "sync_spread")


def program_readings(params, params0, leaders):
    """(delta, spread), each ``(n_workers, leaves)``: the norm of worker
    ``w``'s change of each leaf since ``params0``, and of its distance to
    worker ``leaders[w]``; computed where ``params`` lie (on a mesh,
    ``params0`` replicated on it)."""
    return readings_fn(params, leaders)(params, params0)


def readings_fn(params, leaders):
    """The jitted readings of ``params``'s placement: on one device over the
    stacked workers; on a mesh under ``params``'s own sharding, each chip
    reading its own worker and taking its group leader's copy from it."""
    leaders = tuple(int(x) for x in leaders)
    sharding = jax.tree.leaves(params)[0].sharding
    if len(sharding.device_set) == 1:
        return lambda p, p0: _readings(p, p0, leaders)
    return _sharded_readings(sharding.mesh, sharding.spec[0], leaders)


def _norm(x, axis=None):
    # no flattening: a reshape of a large tiled array to one long axis
    # makes the TPU compiler split it into tens of thousands of pieces
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis))


@functools.partial(jax.jit, static_argnums=2)
def _readings(params, params0, leaders):
    delta, spread = [], []
    for x, x0 in zip(jax.tree.leaves(params), jax.tree.leaves(params0)):
        x = x.astype(jnp.float32)
        delta.append(_norm(x - x0.astype(jnp.float32)[None],
                           axis=tuple(range(1, x.ndim))))
        spread.append(jnp.stack([_norm(x[w] - x[lead])
                                 for w, lead in enumerate(leaders)]))
    return jnp.stack(delta, 1), jnp.stack(spread, 1)


@functools.cache
def _sharded_readings(mesh, axes, leaders):
    """One worker per chip along the mesh axes ``axes``: every leader sends
    its block to its group's other members, one member per
    ``ppermute``."""
    members = {}
    for w, lead in enumerate(leaders):
        if w != lead:
            members.setdefault(lead, []).append(w)
    rounds = [[(lead, ws[r]) for lead, ws in members.items() if r < len(ws)]
              for r in range(max(map(len, members.values()), default=0))]
    is_lead = np.array([w == lead for w, lead in enumerate(leaders)])

    def shard(params, params0):
        idx = 0
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)

        delta, spread = [], [jnp.zeros((1,))]
        for x, x0 in zip(jax.tree.leaves(params), jax.tree.leaves(params0)):
            # one leaf at a time: its copies are sent once the last leaf's
            # readings are done (the barrier's outputs are both used), so a
            # chip holds one leaf's copies at most
            x, spread[-1] = jax.lax.optimization_barrier(
                (x.astype(jnp.float32), spread[-1]))
            got = jnp.zeros_like(x)
            for perm in rounds:
                got = got + jax.lax.ppermute(x, axes, perm)
            lead = jnp.where(jnp.asarray(is_lead)[idx], x, got)
            rest = tuple(range(1, x.ndim))
            delta.append(_norm(x - x0.astype(jnp.float32)[None], axis=rest))
            spread.append(_norm(x - lead, axis=rest))
        return jnp.stack(delta, 1), jnp.stack(spread[1:], 1)

    spec = P(axes)
    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(spec, P()),
                                 out_specs=(spec, spec)))


def compare(prog_losses, prog, ref_losses, ref_grad0, ref_deltas, limits):
    """``prog`` maps each averaging step to its ``(delta, spread)``
    readings; the ``ref_*`` arguments are ``first_global_round``'s results.
    Returns ``(correct, checks)`` with ``checks[name] = {"value", "limit"}``.
    """
    pl, rl = np.asarray(prog_losses), np.asarray(ref_losses)
    if pl.shape != rl.shape or set(prog) != set(ref_deltas):
        raise ValueError(f"readings do not line up: losses {pl.shape} vs "
                         f"{rl.shape}, steps {sorted(prog)} vs "
                         f"{sorted(ref_deltas)}")
    keep = ref_grad0 >= 1e-3 * np.median(ref_grad0)
    update_gap = sync_spread = 0.0
    for step, (delta, spread) in prog.items():
        ref = ref_deltas[step][:, keep]
        scale = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
        update_gap = max(update_gap, float(np.max(
            np.abs(np.asarray(delta)[:, keep] - ref) / scale)))
        sync_spread = max(sync_spread, float(np.max(
            np.asarray(spread)[:, keep] / scale)))
    values = {"loss_rel": float(np.max(np.abs(pl - rl) / np.abs(rl))),
              "update_gap": update_gap, "sync_spread": sync_spread}
    # a NaN fails every limit
    checks = {k: {"value": values[k], "limit": float(limits[k])}
              for k in NUMBERS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks
