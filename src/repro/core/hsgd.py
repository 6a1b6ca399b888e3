"""The H-SGD engine (paper Algorithm 1 and multi-level Algorithm D.1).

The engine is split into two layers:

* **plan layer** (this module) — everything backend-agnostic: schedule
  compilation (``compile_schedule`` folds the event schedule into ``Round``s),
  gradient accumulation, history/eval bookkeeping, and the typed-event
  dispatch.  ``HSGD`` owns the plan and never touches devices directly.
* **executor layer** (:mod:`repro.core.executors`) — how a round body runs on
  hardware.  ``SimExecutor`` (default) vmaps over a leading worker axis on
  one device and aggregates with in-array segment means; ``MeshExecutor``
  runs the same round body under ``shard_map`` on a device mesh whose replica
  axes mirror the hierarchy levels, so each ``SyncEvent(level=ℓ)`` lowers to
  a ``lax.pmean`` over exactly the mesh axes of levels >= ℓ (local sync =
  fast intra-pod ICI; global sync additionally crosses the slow pod axis).

State layout: every worker owns a full model replica; ``params`` and
``opt_state`` carry a leading worker axis of size n (sharded over the replica
mesh axes under the mesh executor, a plain array dimension under sim).

Which workers average when — and by what rule — lives entirely in the
:class:`~repro.core.topology.Topology` / ``Aggregator`` layer; the engine
only dispatches on typed :class:`~repro.core.topology.SyncEvent`s.  Because
the periods are static, each distinct event is its own jitted function — no
lax.cond around collectives, so the lowered HLO per step kind is exact (the
roofline reads it).  ``run_rounds`` goes further: it compiles the event
schedule into rounds and fuses each pure-local block into a single jitted
``lax.scan``, removing the per-step Python dispatch entirely.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.topology import SyncEvent, Topology
from repro.obs.spans import LOCAL_FWD, LOCAL_UPDATE
from repro.optim.optimizers import Optimizer


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Typed engine configuration: every pluggable subsystem in one frozen
    object instead of the kwarg sprawl ``HSGD(..., comms=..., runtime=...,
    metrics=..., executor=...)`` grew across PRs 2–8.

    Each subsystem field takes whatever its ``make_*`` factory accepts —
    None, a registered name, or an instance: ``executor``
    (:func:`repro.core.executors.make_executor`), ``comms``
    (:func:`repro.comms.make_comms`), ``runtime``
    (:func:`repro.runtime.make_runtime`), ``metrics``
    (:func:`repro.obs.make_metrics`), ``population``
    (:func:`repro.population.make_population` — binding one switches the
    engine into the sampled-participation regime, see
    :meth:`HSGD.run_sampled`).  The scalar engine options
    (``aggregate_opt_state`` / ``jit`` / ``accum_steps``) live here too so
    one object round-trips a full engine setup (the train CLI echoes it
    into the JSONL header).

    The legacy keywords still work via a deprecation shim (tested), so
    ``HSGD(loss, opt, topo, comms="topk")`` and
    ``HSGD(loss, opt, topo, EngineConfig(comms="topk"))`` build the same
    engine.
    """
    executor: Any = None
    comms: Any = None
    runtime: Any = None
    metrics: Any = None
    population: Any = None
    aggregate_opt_state: bool = True
    jit: bool = True
    accum_steps: int = 1
    # async (stale-sync) execution: {level: staleness} — a level-l aggregate
    # is applied `staleness` level-l boundaries late (DESIGN.md §9).
    # staleness=0 entries are dropped at construction, so {1: 0} is
    # contractually the barrier path (bitwise-identical; tested).  Must be
    # hashable-friendly: a plain dict is fine (EngineConfig is never used
    # as a jit key), the compiled Rounds carry the static per-boundary ops.
    async_levels: Any = None

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary (the train CLI's JSONL ``config`` line)."""
        def show(v):
            if v is None or isinstance(v, (str, int, float, bool)):
                return v
            if isinstance(v, dict):
                return {str(k): show(x) for k, x in v.items()}
            d = getattr(v, "describe", None)
            return d() if callable(d) else repr(v)
        return {f.name: show(getattr(self, f.name))
                for f in dataclasses.fields(self)}


_UNSET = object()
# kwargs the shim still accepts; the subsystem ones warn, the scalar ones
# (plain engine options, no sprawl history) fold in silently
_SUBSYSTEM_KWARGS = ("executor", "comms", "runtime", "metrics", "population")
_SCALAR_KWARGS = ("aggregate_opt_state", "jit", "accum_steps")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HSGDState:
    params: Any      # leading worker axis n
    opt_state: Any   # leading worker axis n
    step: jax.Array  # scalar int32
    comms: Any = None  # codec state (error-feedback residuals), worker axis n
    metrics: Any = None  # on-device probe buffer (repro.obs.MetricBuffer),
    #   replicated — None (default) contributes no leaves, so the lowered
    #   programs are identical to the pre-observability engine
    pending: Any = None  # stale-sync slots ({level: StaleSlot}) under async
    #   execution — None (default) contributes no leaves, same contract as
    #   metrics: the barrier engine's lowered programs are untouched


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StaleSnap:
    """One deferred sync payload, aggregated AT POSTING TIME: the post-fold
    worker params (and, when the engine aggregates optimizer state, the
    moments) captured at a stale level-l boundary, together with the level-l
    aggregate computed over that very payload.  Storing the aggregate with
    its baseline makes the later fold purely elementwise
    (``live += agg - params``) — no cross-worker op runs at fold time, so a
    worker dropped at an intermediate boundary folds exactly the aggregate
    it actually holds, however old, instead of a revisionist recombination
    of mixed-vintage slots.  Leaves keep the leading worker axis n, so
    snapshots shard exactly like the live state under the mesh executor."""
    params: Any
    opt: Any       # moments dict ({} when nothing rides the sync)
    agg: Any       # level-l aggregate of ``params``, posted with this snap
    agg_opt: Any   # level-l aggregate of ``opt`` ({} when empty)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StaleSlot:
    """One async level's pending state: a rolling tuple of ``staleness``
    snapshots (oldest last-minus-warm — see :class:`StaleOp`) plus the
    level's OWN error-feedback residual chain.  Codec residuals stay with
    the snapshot (DESIGN.md §9): the live ``HSGDState.comms`` residual is
    never consumed by a stale fold, so fresh and stale syncs keep disjoint
    error-feedback streams."""
    snaps: Tuple[Any, ...]
    residual: Any = None


# ---------------------------------------------------------------------------
# schedule compilation (for run_rounds)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StaleOp:
    """One async level's static work at a round-ending sync boundary
    (computed by :func:`compile_schedule` from the schedule alone, so it is
    part of the hashable ``Round`` jit-cache key):

    * fold the ``n_fold`` OLDEST outstanding snapshots of ``level`` into
      the live state (delta-corrected and purely elementwise:
      ``live += snap.agg − snap.params``, the aggregate having been
      computed when the snapshot was posted; DESIGN.md §9) — ``warm`` is
      the number of snapshots outstanding BEFORE this op, so the oldest
      lives at slot index ``staleness − warm`` of the rolling tuple;
    * then, when ``snapshot`` (the boundary is a level-``level`` event),
      run the level-``level`` aggregation on the post-fold payload and
      capture both payload and aggregate into the newest slot.

    A boundary of a MORE global event (event.level < level) is a flush:
    ``n_fold == warm`` outstanding snapshots fold, nothing is captured,
    and the warm-up restarts."""
    level: int
    n_fold: int
    warm: int
    snapshot: bool


@dataclasses.dataclass(frozen=True)
class Round:
    """``n_local`` local updates, the last one followed by ``event`` (None
    for a round that ends between syncs — a schedule tail, or a cut forced
    by ``cut_every``).  ``stale`` carries the boundary's static async ops
    (:class:`StaleOp`, outermost-last so flushes of deeper levels apply
    first); empty for every barrier-synchronous schedule."""
    n_local: int
    event: Optional[SyncEvent]
    stale: Tuple[StaleOp, ...] = ()


def _boundary_ops(event: SyncEvent, async_levels: Dict[int, int],
                  warm: Dict[int, int]) -> Tuple[StaleOp, ...]:
    """The static stale ops one sync event triggers, updating the warm-up
    counters in place.  Deeper (more local) levels first: their outstanding
    folds are strictly older information, and the event's own op — the only
    one that snapshots — always comes last."""
    ops: List[StaleOp] = []
    for lvl in sorted(async_levels, reverse=True):
        if event.level > lvl:
            continue               # not a level-lvl boundary
        s = async_levels[lvl]
        if event.level < lvl:      # more global event: flush, restart warmup
            if warm[lvl]:
                ops.append(StaleOp(lvl, warm[lvl], warm[lvl], False))
                warm[lvl] = 0
        else:                      # the level's own boundary
            if event.groups is not None or event.weights is not None:
                raise ValueError(
                    f"async level {lvl} requires full level-{lvl} events; "
                    f"got a partial/weighted event {event} — stale folds "
                    "have no per-boundary group structure to replay")
            nf = 1 if warm[lvl] >= s else 0
            ops.append(StaleOp(lvl, nf, warm[lvl], True))
            warm[lvl] += 1 - nf
    return tuple(ops)


def async_warmup(schedule, async_levels: Dict[int, int]) -> Dict[int, int]:
    """Replay a schedule prefix's warm-up counters (how many snapshots each
    async level has outstanding after those events) — what ``run_rounds``
    uses to resume a trajectory at t0 > 0 with the pending slots already
    carrying state."""
    warm = {lvl: 0 for lvl in async_levels}
    for ev in schedule:
        if ev is not None:
            _boundary_ops(ev, async_levels, warm)
    return warm


def compile_schedule(schedule, cut_every: int = 0, t0: int = 0,
                     async_levels: Optional[Dict[int, int]] = None,
                     warm0: Optional[Dict[int, int]] = None
                     ) -> Tuple[Round, ...]:
    """Fold a per-step event schedule into maximal pure-local rounds.

    ``cut_every`` additionally ends a round at every absolute step that is a
    multiple of it (``t0`` = absolute step of ``schedule[0]``) even without a
    sync event, so ``run_rounds`` eval points always land on a round boundary
    regardless of how they align with the sync periods.

    ``async_levels`` ({level: staleness}, entries all >= 1) annotates each
    sync round with its stale-apply offsets (:class:`StaleOp`): the first
    ``staleness`` level-l boundaries only snapshot (warm-up), every later
    one folds the oldest snapshot and captures a new one, and any more
    global event flushes the level's outstanding snapshots first.  ``warm0``
    seeds the warm-up counters when the schedule is a suffix
    (:func:`async_warmup` over the prefix)."""
    rounds: List[Round] = []
    alv = dict(async_levels) if async_levels else {}
    warm = {lvl: 0 for lvl in alv}
    if warm0:
        warm.update(warm0)
    k = 0
    for i, ev in enumerate(schedule):
        k += 1
        if ev is not None or (cut_every and (t0 + i + 1) % cut_every == 0):
            stale = _boundary_ops(ev, alv, warm) \
                if (alv and ev is not None) else ()
            rounds.append(Round(k, ev, stale))
            k = 0
    if k:
        rounds.append(Round(k, None))
    return tuple(rounds)


class HSGD:
    """The plan layer.  loss_fn(params, batch) -> (loss, metrics-dict).
    Batch passed to ``step`` must carry a leading worker axis of size n.

    ``executor`` picks the execution backend: ``"sim"`` (default; vmap on one
    device), ``"mesh"`` (shard_map over a hierarchy-shaped device mesh), an
    :class:`~repro.core.executors.Executor` instance, or a registered name.

    ``comms`` selects the communication plan (:func:`repro.comms.make_comms`):
    None (default) keeps the leaf-wise aggregation path bitwise-identical to
    before; a codec name ("identity" | "int8" | "sign" | "topk") or a
    :class:`~repro.comms.Comms` routes every sync through fused flat-buffer
    payloads + that wire codec, and turns on per-level wire accounting
    (:meth:`wire_stats`; :meth:`run_rounds` history records additionally
    carry ``wire_bytes`` — the per-step :meth:`step` path does not).

    ``runtime`` selects the simulated-time model
    (:func:`repro.runtime.make_runtime`): None (default) is bitwise-identical
    to no runtime at all; a :class:`~repro.runtime.RuntimeModel` threads an
    event-driven :class:`~repro.runtime.SimClock` through :meth:`run_rounds`
    (per-worker straggler clocks, per-level link costs priced by the comms
    payload bytes), adds ``sim_time_s``/``sim_sync_s`` to every history
    record, and — with an elastic policy — converts missed sync deadlines
    into runtime-mask drops on either executor (the mesh backend lowers the
    mask as a per-worker collective weight; the per-step :meth:`step` path
    ignores the runtime, pass masks there yourself).

    ``metrics`` selects the observability plan
    (:func:`repro.obs.make_metrics`): None (default) is bitwise-identical
    to no observability at all — no buffer in the state, no probe in the
    round body, same lowered jaxpr; ``"on"`` / a :class:`~repro.obs.Metrics`
    carries an on-device :class:`~repro.obs.MetricBuffer` in the state and
    pushes the per-level parameter divergences (paper eq. (10): global =
    upward + downward) at EVERY sync event inside the jitted round body,
    plus a per-step ``grad_norm`` channel; :meth:`run_rounds` drains the
    buffer in one device→host transfer at eval boundaries and merges the
    values into history as ``div_*`` keys (the per-step :meth:`step` path
    pushes too — drain with :meth:`drain_metrics`).
    """

    def __init__(self, loss_fn: Callable, optimizer: Optimizer,
                 topology: Topology, config: Optional[EngineConfig] = None,
                 *, aggregate_opt_state=_UNSET, jit=_UNSET,
                 accum_steps=_UNSET, executor=_UNSET, comms=_UNSET,
                 runtime=_UNSET, metrics=_UNSET, population=_UNSET):
        """Subsystems come from ``config`` (an :class:`EngineConfig`); the
        pre-config keywords still work through a deprecation shim but may
        not be mixed with ``config``.

        accum_steps > 1: each H-SGD iteration accumulates gradients over
        that many microbatches (scan) before the single optimizer update —
        same semantics as one large-batch step (SGD is linear in the
        gradient; tested), peak activation memory divided by accum_steps."""
        overrides = {k: v for k, v in [
            ("aggregate_opt_state", aggregate_opt_state), ("jit", jit),
            ("accum_steps", accum_steps), ("executor", executor),
            ("comms", comms), ("runtime", runtime), ("metrics", metrics),
            ("population", population)] if v is not _UNSET}
        if overrides and config is not None:
            raise TypeError(
                f"HSGD got both config= and the keyword(s) "
                f"{sorted(overrides)}; move them into "
                f"EngineConfig({', '.join(sorted(overrides))}=...)")
        if config is None:
            legacy = sorted(k for k in overrides if k in _SUBSYSTEM_KWARGS)
            if legacy:
                warnings.warn(
                    f"HSGD({', '.join(k + '=...' for k in legacy)}) keyword"
                    f"{'s are' if len(legacy) > 1 else ' is'} deprecated; "
                    f"pass HSGD(loss_fn, optimizer, topology, "
                    f"EngineConfig({', '.join(k + '=...' for k in legacy)}))",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**overrides)
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.topology = topology
        self.config = config
        self.aggregate_opt_state = config.aggregate_opt_state
        self._jit = config.jit
        self.accum_steps = config.accum_steps
        self.async_levels = self._normalize_async(config.async_levels)
        # local imports: executors imports this module for HSGDState/Round,
        # and comms/runtime reach back into core.topology
        from repro.comms import make_comms
        self.comms = make_comms(config.comms)
        from repro.runtime import make_runtime
        self.runtime = make_runtime(config.runtime)
        from repro.obs import make_metrics
        self.metrics = make_metrics(config.metrics)
        if self.async_levels and self.metrics is not None \
                and self.metrics.divergences and not self.metrics.staleness:
            # async engines get the staleness channel next to the eq. (10)
            # partition automatically (‖applied − fresh‖² per stale fold)
            self.metrics = dataclasses.replace(self.metrics, staleness=True)
        from repro.population import make_population
        self.population = make_population(config.population)
        self._population_engine = None
        self._last_clock = None
        from repro.core.executors import make_executor
        self.executor = make_executor(config.executor)
        self.executor.bind(self)

    def _normalize_async(self, raw) -> Dict[int, int]:
        """Validate ``EngineConfig.async_levels`` against the topology:
        integer levels within the hierarchy, staleness >= 0, and — the
        staleness=0 contract — zero entries dropped entirely, so they are
        bitwise the barrier path by construction (same Rounds, same
        compiled bodies, no pending leaves in the state)."""
        if not raw:
            return {}
        out: Dict[int, int] = {}
        num_levels = len(self.topology.periods)
        for lvl, s in dict(raw).items():
            lvl, s = int(lvl), int(s)
            if not 1 <= lvl <= num_levels:
                raise ValueError(
                    f"async_levels level {lvl} outside the hierarchy "
                    f"(levels 1..{num_levels})")
            if s < 0:
                raise ValueError(f"async_levels[{lvl}] = {s}: staleness "
                                 "must be >= 0")
            if s == 0:
                continue  # contractually the barrier path
            if self.topology.participants(SyncEvent(level=lvl)) is not None:
                raise ValueError(
                    f"async level {lvl} requires every level-{lvl} event to "
                    f"cover all workers; {type(self.topology).__name__} "
                    "scopes them to a static subset (partial-group events "
                    "have no stale-fold semantics)")
            out[lvl] = s
        return out

    # -- participation (one protocol over the grown surfaces) ---------------
    def participation(self, clock=None, extra=None):
        """This engine's composed :class:`~repro.population.Participation`
        view: the topology's static event masks, plus the elastic adapter
        when a live clock is passed, plus ``extra`` (e.g. the population
        engine's per-round pinned sampler)."""
        from repro.population import (ElasticParticipation,
                                      StaticParticipation, compose)
        return compose(StaticParticipation(self.topology), extra,
                       ElasticParticipation(clock)
                       if clock is not None else None)

    # -- init ---------------------------------------------------------------
    def init(self, key, model_init: Callable[[jax.Array], Any]) -> HSGDState:
        """All workers start from the SAME w̄^0 (paper input)."""
        params0 = model_init(key)
        n = self.topology.n
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params0)
        opt0 = self.optimizer.init(params0)
        opt_state = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), opt0)
        cstate = self.comms.init_state(params) if self.comms else None
        mbuf = self.metrics.init_buffer(self.topology) if self.metrics \
            else None
        state = HSGDState(params, opt_state, jnp.zeros((), jnp.int32), cstate,
                          mbuf, self._init_pending(params, opt_state))
        return self.executor.place(state)

    def _init_pending(self, params, opt_state) -> Optional[Dict]:
        """Zero-filled stale slots, one :class:`StaleSlot` per async level:
        ``staleness`` payload snapshots (params + the moments the sync would
        ship) and the level's own error-feedback residual.  Warm-up
        guarantees the zeros are never READ before the first real snapshot
        lands (the fold count in each :class:`StaleOp` is static), they
        only size the carry."""
        if not self.async_levels:
            return None
        zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)
        moments = _moments_only(opt_state) if self.aggregate_opt_state else {}
        pending: Dict[int, StaleSlot] = {}
        for lvl, s in sorted(self.async_levels.items()):
            snaps = tuple(StaleSnap(zeros(params), zeros(moments),
                                    zeros(params), zeros(moments))
                          for _ in range(s))
            res = self.comms.init_state(params) if self.comms else None
            pending[lvl] = StaleSlot(snaps, res)
        return pending

    # -- building blocks ------------------------------------------------------
    @property
    def update_path(self) -> str:
        """``"seeded"`` where the optimizer's update is a scalar times the
        gradient (:attr:`~repro.optim.Optimizer.seed`), so the local step
        pulls that scalar back through the loss and gets the update itself;
        ``"grad"`` where it forms the gradient and calls ``update``."""
        return "seeded" if self.optimizer.seed is not None else "grad"

    def local_update_fn(self):
        """(params, opt_state, batch) -> (params, opt_state, metrics) for ONE
        worker — the pure per-worker half of the plan (with gradient
        accumulation folded in); executors map it over the worker axis
        (vmap under sim, one worker per mesh replica under mesh).

        On the seeded path (:attr:`update_path`) the backward is seeded
        with the optimizer's scale instead of 1, so it returns the update
        and ``local/update`` holds only the add; the ``grad_norm`` channel
        reads ‖update‖ / |scale| (not finite at a zero step size)."""
        def loss(params, batch):
            # the backward pass carries this scope under ``transpose(...)``
            with jax.named_scope(LOCAL_FWD):
                return self.loss_fn(params, batch)

        def pullback(params, batch, scale):
            """(scale · ∇loss, aux); the plain gradient for scale None."""
            if scale is None:
                return jax.grad(loss, has_aux=True)(params, batch)
            out, vjp, metrics = jax.vjp(lambda p: loss(p, batch), params,
                                        has_aux=True)
            # ones_like keeps the loss's type (its mesh axes under shard_map)
            (upd,) = vjp(jnp.ones_like(out) * scale.astype(out.dtype))
            return upd, metrics

        accum = self.accum_steps

        def accumulate(params, batch, scale):
            """The microbatch mean of ``pullback``: one large-batch step."""
            if accum == 1:
                return pullback(params, batch, scale)
            # seeded: the 1/accum rides on the seed, the sum is the mean
            micro_scale = None if scale is None else scale / accum

            def micro(acc, mb):
                g, m = pullback(params, mb, micro_scale)
                return jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g), m

            mbs = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            gsum, ms = jax.lax.scan(micro, zero, mbs)
            if scale is None:
                gsum = jax.tree.map(lambda g: g / accum, gsum)
            out = jax.tree.map(lambda g, p: g.astype(p.dtype), gsum, params)
            return out, jax.tree.map(lambda m: m.mean(0), ms)

        grad_norm = self.metrics is not None and self.metrics.grad_norm
        seed = self.optimizer.seed

        def local_update(params, opt_state, batch):
            scale = None
            if seed is not None:
                scale, opt_state = seed(opt_state)
            pulled, metrics = accumulate(params, batch, scale)
            if grad_norm:
                # per-worker gradient l2 norm; executors mean it over the
                # worker axis like every other per-step metric channel
                metrics = dict(metrics)
                norm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(pulled)))
                metrics["grad_norm"] = norm if scale is None \
                    else norm / jnp.abs(scale)
            with jax.named_scope(LOCAL_UPDATE):
                updates = pulled
                if scale is None:
                    updates, opt_state = self.optimizer.update(
                        pulled, opt_state, params)
                params = jax.tree.map(jnp.add, params, updates)
            return params, opt_state, metrics

        return local_update

    # -- executor delegation ---------------------------------------------------
    def step_fn(self, event: Optional[SyncEvent], masked: bool = False):
        """The executor's compiled function for one '``event`` step'."""
        return self.executor.step_fn(event, masked)

    def round_fn(self, rnd: Round, masked: bool = False):
        """The executor's compiled function for one round; ``masked=True``
        builds the elastic-drop variant (every worker still runs its local
        updates; workers masked out of the round's sync neither contribute
        to nor receive the aggregate — they were still computing when the
        barrier closed)."""
        return self.executor.round_fn(rnd, masked)

    def step(self, state: HSGDState, batch,
             mask=None) -> Tuple[HSGDState, Dict]:
        """mask: optional (n,) bool — partial worker participation (held
        fixed by the caller within a round, re-drawn per round).  NOTE: pays
        a host sync per call (``int(state.step)``); prefer run_rounds."""
        if self.async_levels:
            raise NotImplementedError(
                "the per-step path has no stale-apply offsets (they are "
                "compiled per Round); run async engines through "
                "run_rounds, or drop async_levels from the EngineConfig")
        event = self.topology.event_at(int(state.step))
        if mask is None:
            return self.step_fn(event)(state, batch)
        return self.step_fn(event, masked=True)(state, batch, jnp.asarray(mask))

    # -- schedule-compiled round executor --------------------------------------
    def run_rounds(self, state: HSGDState, batch_fn: Callable[[int], Any],
                   T: int, *, eval_every: int = 0,
                   eval_fn: Optional[Callable[[HSGDState, int], Dict]] = None,
                   trace=None, participation=None
                   ) -> Tuple[HSGDState, List[Dict]]:
        """Run T steps through the schedule-compiled executor.

        Precomputes ``topology.schedule(T)``, folds it into rounds
        (``compile_schedule``) and executes each as one fused call on the
        bound executor.  The trajectory is identical to T calls of
        :meth:`step` (tested); distinct ``Round`` signatures are compiled
        once and reused.

        History records per-step training metrics for EVERY step; when
        ``eval_every`` is set, the schedule is additionally cut at every
        ``eval_every``-th step so ``eval_fn(state, t)`` fires exactly there
        (plus at t+1 == T), and its results are merged into the matching
        record — within a round the intermediate states never materialize,
        which is where the speed comes from.

        With comms enabled, every record additionally carries ``wire_bytes``
        — the bytes the step's sync event moved (0 between syncs), computed
        statically from the payload specs (no device work).

        With a runtime model bound, every record additionally carries
        ``sim_time_s`` (the cumulative simulated makespan — the slowest
        worker's clock after that step, barrier included) and ``sim_sync_s``
        (cumulative per-level barrier link seconds, ``{"L1": ..., ...}``) —
        all host-side numpy next to the static ``wire_bytes``.  An elastic
        policy's deadline drops route the affected rounds through the
        masked executor variant; :meth:`runtime_report` has the final
        breakdown.

        With metrics enabled (``HSGD(..., metrics="on")``), the in-graph
        divergence probe pushes one row per sync event into the on-device
        :class:`~repro.obs.MetricBuffer`; this loop drains the buffer in ONE
        device→host transfer at eval boundaries (plus before the ring could
        wrap, and at the end), reattaches each row's (step, level) from the
        static schedule, and merges the values into the matching records as
        ``div_global`` / ``div_up_Lℓ`` / ``div_down_Lℓ``.  With a runtime
        bound, sync-step records also carry ``dropped`` (workers the policy
        cut from that barrier).  Records are linted against the metrics bus
        (:func:`repro.obs.validate_record`).

        ``trace`` accepts a :class:`~repro.obs.TraceRecorder`: the runtime
        clock emits per-worker compute/wait spans and per-level sync spans
        in simulated time, and drained probe rows become divergence counter
        tracks; without a runtime, spans fall back to step-index time.

        ``participation`` accepts an extra
        :class:`~repro.population.Participation` composed with the engine's
        own (topology static masks + the elastic clock): each executed
        sync consults ``round_mask`` once, and a non-None mask routes the
        round through the masked executor variant — this is how the
        population engine masks a draw's empty slots out of every sync.

        On the profiler's clock the call is the host span
        ``hsgd.run_rounds`` (argument ``update``, the :attr:`update_path`),
        with ``hsgd.schedule``, one ``hsgd.dispatch`` per round,
        ``hsgd.drain`` and ``hsgd.history`` inside it
        (:mod:`repro.obs.spans`)."""
        from repro.obs import span
        t0 = int(state.step)
        with span("hsgd.run_rounds", call=t0, update=self.update_path):
            return self._run_rounds(state, batch_fn, T, t0, eval_every,
                                    eval_fn, trace, participation)

    def _run_rounds(self, state: HSGDState, batch_fn, T: int, t0: int,
                    eval_every: int, eval_fn, trace, participation):
        from repro.obs import span
        cut = eval_every if (eval_fn is not None and eval_every) else 0
        with span("hsgd.schedule", call=t0):
            full = self.topology.schedule(t0 + T)
            schedule = full[t0:]
            warm0 = async_warmup(full[:t0], self.async_levels) \
                if (self.async_levels and t0) else None
            rounds = compile_schedule(schedule, cut_every=cut, t0=t0,
                                      async_levels=self.async_levels or None,
                                      warm0=warm0)
            wire = None
            if self.comms is not None:
                ws = self.wire_stats(state)
                wire = [ws.bytes_for_event(ev) for ev in schedule]
        clock = None
        sim: List[Tuple[float, Dict[str, float]]] = []  # per-step snapshots
        if self.runtime is not None:
            clock = self.runtime.clock(self.topology,
                                       self._payload_nbytes(state),
                                       recorder=trace,
                                       async_levels=self.async_levels or None)
            self._last_clock = clock
        parts = self.participation(clock=clock, extra=participation) \
            if (clock is not None or participation is not None) else None
        probes = (self.metrics is not None and self.metrics.divergences
                  and state.metrics is not None)
        div_keys = self.metrics.history_keys(self.topology) if probes else ()
        cap = state.metrics.capacity if probes else 0
        pending: List[Tuple[int, int]] = []  # (step, level) since last drain
        probe_vals: Dict[int, Dict[str, float]] = {}
        drops: Dict[int, int] = {}

        def ts_of(step_no: int) -> float:
            return sim[step_no - t0 - 1][0] if clock is not None \
                else float(step_no)

        def drain(st: HSGDState) -> HSGDState:
            # one device→host transfer for everything pushed since the last
            # drain; rows get their (step, level) back from the schedule
            if not pending:
                return st
            with span("hsgd.drain", call=t0):
                mb = jax.device_get(st.metrics)
                k = int(mb.count)
                assert k == len(pending) <= cap, (k, len(pending), cap)
                for (step_no, lvl), row in zip(pending, mb.rows[:k]):
                    vals = {key: float(v) for key, v in zip(div_keys, row)}
                    probe_vals[step_no] = vals
                    if trace is not None:
                        trace.divergences(step_no, lvl, ts_of(step_no), vals)
                pending.clear()
                return dataclasses.replace(st, metrics=st.metrics.reset())

        raw: List[Tuple[int, int, Dict]] = []  # (t_end, n_local, metrics)
        evals: Dict[int, Dict] = {}
        t = t0
        for rnd in rounds:
            level = rnd.event.level if rnd.event is not None else 0
            with span("hsgd.dispatch", call=t0, n_local=rnd.n_local,
                      level=level):
                batches = tuple(batch_fn(t + i) for i in range(rnd.n_local))
                mask = None
                if clock is not None:
                    for i in range(rnd.n_local):
                        clock.advance(t + i)
                        sim.append((clock.time_s, clock.level_seconds()))
                    if rnd.event is not None:
                        mask = parts.round_mask(rnd.event)
                        # the sync belongs to the round's last step
                        sim[-1] = (clock.time_s, clock.level_seconds())
                elif parts is not None and rnd.event is not None:
                    mask = parts.round_mask(rnd.event)
                if clock is None and trace is not None:
                    # no runtime: keep the trace well-formed in step-index time
                    trace.name_process(0, "engine")
                    trace.name_thread(0, 0, "rounds (step-index time)")
                    trace.complete(f"round x{rnd.n_local}", float(t),
                                   float(rnd.n_local), pid=0, tid=0)
                    if rnd.event is not None:
                        trace.sync_span(
                            rnd.event.level, float(t + rnd.n_local), 0.0,
                            payload_bytes=wire[t + rnd.n_local - t0 - 1]
                            if wire is not None else 0)
                if probes and rnd.event is not None and len(pending) >= cap:
                    state = drain(state)   # never let the ring wrap
                if mask is None:
                    state, metrics = self.round_fn(rnd)(state, batches)
                else:
                    state, metrics = self.round_fn(rnd, masked=True)(
                        state, batches, jnp.asarray(mask))
            t += rnd.n_local
            raw.append((t, rnd.n_local, metrics))
            if rnd.event is not None:
                if probes:
                    pending.append((t, rnd.event.level))
                if clock is not None:
                    drops[t] = 0 if mask is None else int((~mask).sum())
            if eval_fn is not None and eval_every and \
                    (t % eval_every == 0 or t == t0 + T):
                if probes:
                    state = drain(state)
                evals[t] = eval_fn(state, t - 1)
        if probes:
            state = drain(state)
        # metrics stay on device until here so rounds dispatch back-to-back;
        # one bulk transfer at the end instead of a sync per step
        with span("hsgd.history", call=t0):
            history: List[Dict] = []
            for t_end, n_local, metrics in raw:
                metrics = jax.device_get(metrics)
                for i in range(n_local):
                    step_no = t_end - n_local + i + 1
                    rec = {"t": step_no,
                           **{k: float(v[i]) for k, v in metrics.items()}}
                    if wire is not None:
                        rec["wire_bytes"] = wire[step_no - t0 - 1]
                    if clock is not None:
                        time_s, sync_s = sim[step_no - t0 - 1]
                        rec["sim_time_s"] = round(time_s, 6)
                        rec["sim_sync_s"] = sync_s
                        if step_no in drops:
                            rec["dropped"] = drops[step_no]
                    rec.update(probe_vals.get(step_no, {}))
                    rec.update(evals.get(step_no, {}))
                    history.append(rec)
            if self.metrics is not None:
                from repro.obs import validate_record
                for rec in history:
                    errs = validate_record(rec)
                    if errs:
                        raise ValueError(
                            "metrics-bus violations in run_rounds history at "
                            f"t={rec.get('t')}: " + "; ".join(errs))
        return state, history

    def drain_metrics(self, state: HSGDState
                      ) -> Tuple[HSGDState, List[Dict[str, float]]]:
        """Drain the probe buffer outside :meth:`run_rounds` (the per-step
        :meth:`step` path pushes rows but never drains): one device→host
        transfer, returns ``(state-with-reset-buffer, rows)`` where each row
        is a ``{div_*: value}`` dict in push order.  If more than
        ``Metrics.capacity`` rows were pushed since the last drain, only the
        most recent ``capacity`` survive (the ring wrapped)."""
        if self.metrics is None or state.metrics is None:
            return state, []
        mb = jax.device_get(state.metrics)
        k = int(mb.count)
        cap = mb.rows.shape[0]
        order = range(k) if k <= cap \
            else [i % cap for i in range(k - cap, k)]
        keys = self.metrics.history_keys(self.topology)
        rows = [{key: float(v) for key, v in zip(keys, mb.rows[i])}
                for i in order]
        return dataclasses.replace(state, metrics=state.metrics.reset()), rows

    # -- population regime -----------------------------------------------------
    def population_engine(self):
        """The lazily-built :class:`~repro.population.PopulationEngine`
        behind :meth:`run_sampled` (requires ``config.population``)."""
        if self.population is None:
            raise ValueError(
                "no population bound — construct the engine with "
                "EngineConfig(population=Population(cells=...)) to use the "
                "sampled-participation regime")
        if self._population_engine is None:
            from repro.population import PopulationEngine
            self._population_engine = PopulationEngine(self)
        return self._population_engine

    def init_server(self, key, model_init: Callable):
        """Single-replica :class:`~repro.population.ServerState` (the
        population regime's counterpart of :meth:`init` — no worker axis;
        peak state memory in this regime is bounded by k = topology.n)."""
        return self.population_engine().init_server(key, model_init)

    def run_sampled(self, server, batch_fn, rounds: int, *, sizes=None,
                    eval_every: int = 0, eval_fn=None):
        """Run ``rounds`` sampling rounds of the population regime: each
        draws k = topology.n virtual clients (hierarchically, pure in
        ``(seed, round)``), hydrates them into the (k, ...) state, runs one
        global period on the unchanged round executor, and folds the
        results back into the server model with dataset-size × staleness
        weights (``sizes``: optional ``client_id -> dataset size``, e.g.
        ``PopulationShards.client_size``).  ``batch_fn(client_ids, t)``
        returns the global step t's batch for the drawn clients (leading
        axis k).  Returns ``(ServerState, per-round history)``; each record
        carries the ``participation`` channel."""
        return self.population_engine().run(
            server, batch_fn, rounds, sizes=sizes, eval_every=eval_every,
            eval_fn=eval_fn)

    # -- inspection ------------------------------------------------------------
    def wire_stats(self, state: HSGDState):
        """Static per-level wire accounting for this engine's sync payloads
        (:class:`repro.comms.WireStats`), or None with comms disabled.
        Counts everything a sync actually ships: params, plus the optimizer
        moments when ``aggregate_opt_state`` puts them on the wire."""
        if self.comms is None:
            return None
        from repro.comms import WireArray, WireStats
        parts = [("params", state.params)]
        if self.aggregate_opt_state:
            moments = _moments_only(state.opt_state)
            if jax.tree.leaves(moments):
                parts.append(("moments", moments))
        payload: List[Any] = []
        n_elements = 0
        for name, tree in parts:
            arrays, n = self.comms.payload_spec(tree)
            payload += [WireArray(f"{name}.{a.name}", a.shape, a.dtype)
                        for a in arrays]
            n_elements += n
        return WireStats(self.topology, tuple(payload), n_elements)

    def audit(self, state: HSGDState, batch_fn: Optional[Callable] = None,
              *, T: Optional[int] = None, config: str = "", waivers=(),
              run: bool = True):
        """Static audit of this engine's lowered sync plan
        (:func:`repro.analysis.audit_engine`): traces every distinct
        SyncEvent's aggregation subprogram — and, with ``batch_fn``, every
        distinct Round's fused program — over one global period (or ``T``
        steps) and lints the result (rule catalog in DESIGN.md "Analysis
        layer").  ``run=False`` skips the run_rounds execution pass (retrace
        detection then has no jit-cache numbers — tracing only).  Returns a
        :class:`~repro.analysis.SyncPlanReport`."""
        from repro.analysis import audit_engine
        return audit_engine(self, state, batch_fn, T=T, config=config,
                            waivers=waivers, run=run)

    def _payload_nbytes(self, state: HSGDState) -> int:
        """Per-worker bytes ONE sync payload puts on the wire — the encoded
        codec payload with comms on (so compression buys simulated time),
        else the raw dtype-true bytes of everything a sync ships (params +
        aggregated optimizer moments)."""
        if self.comms is not None:
            return self.wire_stats(state).payload_bytes
        parts = [state.params]
        if self.aggregate_opt_state:
            parts.append(_moments_only(state.opt_state))
        return sum(x.nbytes // x.shape[0]
                   for tree in parts for x in jax.tree.leaves(tree))

    def runtime_report(self, state: Optional[HSGDState] = None):
        """The last :meth:`run_rounds` clock's breakdown (simulated makespan,
        per-level sync seconds, drop counts, ...), or None before any
        runtime-enabled run.  ``state`` is accepted for symmetry with
        :meth:`wire_stats` and unused."""
        if self._last_clock is None:
            return None
        return self._last_clock.breakdown()

    def mean_params(self, state: HSGDState):
        """w̄^t (the analysis object; observable only at t = aG)."""
        return jax.tree.map(
            lambda x: x.mean(0, dtype=jnp.float32).astype(x.dtype), state.params)

    def worker_params(self, state: HSGDState, j: int):
        return jax.tree.map(lambda x: x[j], state.params)


def _moments_only(opt_state):
    return {k: v for k, v in opt_state.items() if k in ("m", "v")}


def _merge_moments(opt_state, agg):
    out = dict(opt_state)
    out.update(agg)
    return out


# ---------------------------------------------------------------------------
# convenience: run T steps with a data source
# ---------------------------------------------------------------------------
def run(engine: HSGD, state: HSGDState, batch_fn: Callable[[int], Any],
        T: int, eval_every: int = 0,
        eval_fn: Optional[Callable[[HSGDState, int], Dict]] = None):
    """batch_fn(t) -> batch with leading worker axis. Returns (state, history).

    History gets one record per step with the training metrics (previously it
    was silently empty unless ``eval_every`` was set); ``eval_fn`` results are
    merged into the matching step's record every ``eval_every`` steps."""
    history = []
    for t in range(T):
        state, metrics = engine.step(state, batch_fn(t))
        rec = {"t": t + 1, **{k: float(v) for k, v in metrics.items()}}
        if eval_every and (t + 1) % eval_every == 0 and eval_fn is not None:
            rec.update(eval_fn(state, t))
        history.append(rec)
    return state, history
