"""In-graph divergence probes: the paper's telemetry measured ON device.

The paper's whole analysis runs through the eq. (10) partition — global
parameter divergence = upward (between level-ℓ subtree means) + downward
(within subtrees) — yet until now the repo could only measure it out of
band, in a separate host pass over recomputed gradients.  This module puts
the measurement inside the jitted round body instead:

* a :class:`Metrics` plan (``HSGD(..., metrics=...)``, resolved through
  :func:`make_metrics` exactly like comms/runtime: None = off, the
  bitwise-identical default) decides WHAT is probed — per-level parameter
  divergences at every :class:`~repro.core.topology.SyncEvent`, and a
  per-step ``grad_norm`` channel folded into the local-update metrics;
* a :class:`MetricBuffer` ring (carried in ``HSGDState.metrics`` alongside
  ``comms``) accumulates one probe row per sync event on device, so the
  round body stays host-free (analysis rule R3) — ``run_rounds`` drains it
  in ONE device→host transfer at eval boundaries / before overflow / at the
  end, and reconstructs each row's (step, level) from the static schedule;
* the probe itself has two lowerings that the executors keep in lockstep
  with their aggregation paths: :meth:`Metrics.sim_row_fn` evaluates the
  fused eq. (10) partition (:func:`repro.core.divergence.
  partition_divergences`, tested against the naive host-oracle formulas)
  on the in-array worker block (vmap backend), :meth:`Metrics.mesh_row_fn` is the
  named-axis form — per-level ``pmean`` group means plus one final stacked
  pmean, L+2 collectives per sync for L internal levels (shard_map
  backend).  Sim and mesh values agree to accumulation rounding; the
  eq. (10) identity ``up_ℓ + down_ℓ == global`` holds per level (tested).

The probe measures PARAM divergences on the pre-aggregation worker params
(the states already resident when the sync fires) — the live counterpart of
the paper's analysis object, at zero extra passes.  Gradient-divergence
telemetry at a common point stays available via the host path
(:func:`repro.core.divergence.per_worker_grads`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.divergence import partition_divergences_tree
from repro.obs.spans import PROBE


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MetricBuffer:
    """On-device ring of probe rows: ``rows`` is (capacity, k) float32,
    ``count`` the number of pushes since the last drain.  Rows don't carry
    their step/level — the drain reconstructs both from the static schedule
    (one fewer on-device write per push, and nothing to keep replicated
    under the mesh executor beyond the rows themselves)."""
    rows: jax.Array    # (capacity, k) f32
    count: jax.Array   # scalar int32

    @classmethod
    def zeros(cls, capacity: int, k: int) -> "MetricBuffer":
        return cls(jnp.zeros((capacity, max(k, 1)), jnp.float32),
                   jnp.zeros((), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    def push(self, row: jax.Array) -> "MetricBuffer":
        """Append one probe row (jit-safe; wraps at capacity — the engine
        drains before that ever happens)."""
        with jax.named_scope(PROBE):
            idx = self.count % self.rows.shape[0]
            row = jnp.reshape(row, (-1,)).astype(self.rows.dtype)
            rows = jax.lax.dynamic_update_index_in_dim(self.rows, row, idx,
                                                       0)
            return MetricBuffer(rows, self.count + 1)

    def reset(self) -> "MetricBuffer":
        """Post-drain buffer: same storage, count back to zero (rows are
        overwritten by later pushes; no device work to clear them)."""
        return MetricBuffer(self.rows, jnp.zeros((), jnp.int32))


@dataclasses.dataclass(frozen=True)
class Metrics:
    """The resolved observability plan, bound per engine
    (``HSGD(..., metrics=...)`` through :func:`make_metrics`).

    divergences: push the per-level divergence row at every sync event.
    grad_norm:   add a per-worker-mean gradient-l2-norm channel to the
                 per-step training metrics (rides the existing metric
                 transfer; no extra device→host traffic).
    capacity:    probe-buffer rows between forced drains.
    staleness:   append the async stale-sync channel to the probe row
                 (``div_staleness``): at every stale level-l boundary that
                 folds, the worker-mean ‖applied − fresh‖² between the
                 delta-corrected params and the codec-free barrier
                 aggregate of the pre-fold params — the divergence-style
                 price of applying the aggregate one period late, next to
                 the eq. (10) partition.  0 at fresh events, warm-up and
                 flush boundaries.  Engines with ``async_levels`` enable it
                 automatically when divergence probing is on.
    """
    divergences: bool = True
    grad_norm: bool = True
    capacity: int = 256
    staleness: bool = False

    def __post_init__(self):
        assert self.capacity >= 1, self

    # -- channel layout ------------------------------------------------------
    def levels(self, topology) -> Tuple[int, ...]:
        """The internal levels probed (keys of ``level_groupings``)."""
        return tuple(sorted(topology.level_groupings()))

    def channels(self, topology) -> Tuple[str, ...]:
        """Probe-row layout: global divergence first, then (upward,
        downward) per internal level, matching eq. (10)'s partition —
        plus the trailing ``staleness`` channel when enabled (the
        executors compose the scalar into the row at push time; the
        ``*_row_fn`` lowerings stay staleness-free)."""
        out = ["global"]
        for lvl in self.levels(topology):
            out += [f"up_L{lvl}", f"down_L{lvl}"]
        if self.staleness:
            out.append("staleness")
        return tuple(out)

    def history_keys(self, topology) -> Tuple[str, ...]:
        """The per-step history keys the drained rows merge in under."""
        return tuple(f"div_{c}" for c in self.channels(topology))

    def init_buffer(self, topology) -> MetricBuffer:
        return MetricBuffer.zeros(self.capacity,
                                  len(self.channels(topology)))

    # -- the two probe lowerings --------------------------------------------
    def sim_row_fn(self, topology) -> Callable[[Any], jax.Array]:
        """In-array probe for the vmap backend: the fused eq. (10)
        partition evaluated leaf-by-leaf on the (n, ...) worker params
        (:func:`repro.core.divergence.partition_divergences_tree` — one
        pass per leaf plus one group-mean contraction per leaf x level, no
        flatten/concat copy).  Equal to the naive per-term host oracle
        :func:`repro.core.divergence.all_divergences` up to f32
        accumulation rounding (tested)."""
        groupings = topology.level_groupings()
        ordered = [groupings[lvl] for lvl in self.levels(topology)]

        @jax.named_scope(PROBE)
        def row(params) -> jax.Array:
            return partition_divergences_tree(params, ordered)

        return row

    def mesh_row_fn(self, topology,
                    rep_axes: Tuple[str, ...]) -> Callable[[Any], jax.Array]:
        """Named-axis probe for the shard_map backend (uniform hierarchies:
        the level-ℓ subtree mean IS ``pmean`` over the mesh axes of levels
        > ℓ).  Per sync: one global-mean pmean, one pmean per internal
        level, and one final pmean of the stacked squared norms — L+2
        collectives, every output fully replicated.  Grouped topologies
        have no per-level axis structure; probe them on the simulator."""
        if getattr(topology, "spec", None) is None:
            raise NotImplementedError(
                f"{type(topology).__name__} has no named-axis level "
                "structure for the divergence probe; run it on the "
                "simulator (HSGD(..., executor='sim')) or disable "
                "divergence probing (Metrics(divergences=False))")
        levels = self.levels(topology)
        assert len(rep_axes) == len(levels) + 1, (rep_axes, levels)

        @jax.named_scope(PROBE)
        def row(params) -> jax.Array:
            # this shard's whole replica as one flat f32 vector
            x = jnp.concatenate(
                [jnp.reshape(l, (-1,)).astype(jnp.float32)
                 for l in jax.tree.leaves(params)])
            xbar = jax.lax.pmean(x, rep_axes)
            sq = lambda d: jnp.sum(d * d)
            parts = [sq(x - xbar)]
            for lvl in levels:
                # level-ℓ subtree mean: workers sharing axes[:ℓ] coordinates
                gm = jax.lax.pmean(x, rep_axes[lvl:])
                parts += [sq(gm - xbar), sq(x - gm)]
            # worker means of every squared norm in one stacked collective
            return jax.lax.pmean(jnp.stack(parts), rep_axes)

        return row

    # -- the R6 overhead contract -------------------------------------------
    def op_budget(self, backend: str, topology, n_param_leaves: int) -> int:
        """Max extra aggregation/probe ops a metrics-on round body may add
        vs its metrics-off twin (rule R6; measured by the audit engine).

        mesh: the divergence probe is exactly L+2 collectives per sync
        (L internal levels) and the ``grad_norm`` channel one extra metric
        pmean.  sim: the leaf-by-leaf partition lowers to 3 in-array
        reduces per leaf for the global term (worker mean, squared-norm
        row sum, worker mean of those) and 3 per leaf x level (group-mean
        contraction, squared-norm row sum, weighted sum) — 3·leaves·(1+L)
        — plus one sum-of-squares reduce per param leaf for
        ``grad_norm``."""
        L = len(self.levels(topology))
        budget = 0
        if backend == "mesh":
            if self.divergences:
                budget += L + 2
            if self.grad_norm:
                budget += 1
            if self.staleness:
                # per stale boundary: one fresh shard_aggregate collective
                # per param leaf + the final worker-mean pmean
                budget += n_param_leaves + 1
        else:
            if self.divergences:
                budget += 3 * n_param_leaves * (1 + L)
            if self.grad_norm:
                budget += n_param_leaves + 1
            if self.staleness:
                # per stale boundary: the codec-free fresh aggregate (one
                # in-array mean per leaf), a squared-norm row sum per leaf,
                # and the final worker mean
                budget += 2 * n_param_leaves + 1
        return budget


MetricsLike = Union[Metrics, str, bool, None]


def make_metrics(spec: MetricsLike = None, **kwargs):
    """Resolve the ``HSGD(..., metrics=...)`` argument: None/False = off
    (the bitwise-identical default — no buffer in the state, no probe in
    the round body, same lowered jaxpr), ``True``/``"on"`` = the default
    :class:`Metrics` plan, or a ready instance."""
    if spec is None or spec is False:
        assert not kwargs, "kwargs only apply when constructing a plan"
        return None
    if isinstance(spec, Metrics):
        assert not kwargs, "kwargs only apply when constructing a plan"
        return spec
    assert spec is True or (isinstance(spec, str) and spec.lower() == "on"), \
        f"metrics must be a Metrics plan, 'on', True or None; got {spec!r}"
    return Metrics(**kwargs)
