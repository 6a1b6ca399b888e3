"""The executor layer: HOW a compiled H-SGD round runs on hardware.

The plan layer (:mod:`repro.core.hsgd`) decides *what* happens — n_local
local updates, then a typed :class:`~repro.core.topology.SyncEvent` — and
hands each :class:`~repro.core.hsgd.Round` to an ``Executor`` that owns the
device mapping and the lowering of the sync collective:

* :class:`SimExecutor` — the reproduction backend.  One device; ``params``
  carry a leading worker axis that is vmapped for the local updates and
  aggregated with in-array segment/reshape means via ``topology.aggregate``.
  Bitwise-identical to the paper experiments (it IS the old single-path
  engine, extracted).
* :class:`MeshExecutor` — the deployment backend.  The round body runs under
  ``jax.shard_map`` on a mesh whose replica axes mirror the hierarchy levels
  (``launch.mesh.make_hsgd_mesh``: outermost axis = level 1 = the slow
  DCI/pod fabric), one worker per replica coordinate.  Each
  ``SyncEvent(level=ℓ)`` lowers to a ``lax.pmean`` over exactly the mesh
  axes of levels >= ℓ (``topology.level_axes`` names them, the aggregator's
  ``axis_aggregate`` supplies the encode/pmean/decode rule) — what the
  engine docstring always promised, now emitted explicitly instead of left
  to GSPMD luck.  ``GroupedTopology`` lowers over the FLAT worker axis with
  one-hot membership weights, and runtime participation masks (Algorithm-1
  partial participation, elastic-deadline drops) thread in as per-worker
  collective weights — every scenario the simulator runs also runs here.

Both backends implement the same **masked-round contract** (what a worker
excluded from a sync keeps — see :class:`MeshExecutor` for the table) and
the same ``exact=True``-replayable reduce, so sim is always the bitwise
reference for mesh verification.  DESIGN.md §2 is the full lowering
contract.

Executors are constructed via :func:`make_executor` ("sim" | "mesh" | an
instance) and bound to one engine; compiled step/round functions are cached
per (event, masked) / per Round exactly as before.
"""
from __future__ import annotations

import abc
import math
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comms.reduce import ExactWireOps, MeshWireOps, SimWireOps
from repro.core.aggregators import Aggregator, flat_worker_index
from repro.core.hsgd import (HSGDState, Round, StaleSnap, StaleSlot,
                             _merge_moments, _moments_only)
from repro.core.topology import SyncEvent
from repro.obs.spans import COLLECTIVE, LOCAL, SYNC_FOLD, sync_scope


class Executor(abc.ABC):
    """Backend contract: build (and cache) the compiled step/round bodies
    for one bound plan-layer engine."""

    def __init__(self):
        self.plan = None
        self._step_fns: Dict[Any, Any] = {}
        self._round_fns: Dict[Any, Any] = {}

    # -- lifecycle ----------------------------------------------------------
    def bind(self, plan) -> "Executor":
        """Attach to an :class:`~repro.core.hsgd.HSGD` plan (called by its
        constructor).  One executor serves one engine."""
        assert self.plan is None or self.plan is plan, \
            "executor is already bound to another engine"
        self.plan = plan
        self._validate()
        return self

    def _validate(self) -> None:
        """Check the bound plan is executable on this backend (fail fast)."""

    def twin(self) -> "Executor":
        """A fresh UNBOUND executor with this one's settings, for derived
        engines (the analysis layer's metrics-off twin, the population
        engine's inner engine) — one executor instance serves one engine."""
        return type(self)()

    def place(self, state: HSGDState) -> HSGDState:
        """Move a freshly initialized state onto this backend's layout."""
        return state

    # -- compiled-function caches -------------------------------------------
    def step_fn(self, event: Optional[SyncEvent], masked: bool = False):
        key = (event, masked)
        if key not in self._step_fns:
            self._step_fns[key] = self._build_step(event, masked)
        return self._step_fns[key]

    def round_fn(self, rnd: Round, masked: bool = False):
        key = (rnd, masked)
        if key not in self._round_fns:
            self._round_fns[key] = self._build_round(rnd, masked)
        return self._round_fns[key]

    @abc.abstractmethod
    def _build_step(self, event: Optional[SyncEvent], masked: bool = False):
        ...

    @abc.abstractmethod
    def _build_round(self, rnd: Round, masked: bool = False):
        ...

    # -- static-analysis surface (repro.analysis) ----------------------------
    @abc.abstractmethod
    def sync_fn(self, event: SyncEvent):
        """The UNcompiled aggregation subprogram one sync event embeds in
        every round body: ``(params, opt_state, cstate, mask=None) ->
        (params, opt_state, cstate)``.  This is the exact reduce path
        ``round_fn`` lowers (same closure, same collectives) exposed in
        isolation, so the analysis layer can trace WHAT an event ships
        without the local-update noise around it."""

    def sync_jaxpr(self, event: SyncEvent, state: HSGDState, mask=None):
        """ClosedJaxpr of :meth:`sync_fn` against ``state``'s shapes — the
        trace target of the ``repro.analysis`` walker (rules R1/R2/R5)."""
        fn = self.sync_fn(event)
        if mask is None:
            return jax.make_jaxpr(lambda p, o, c: fn(p, o, c))(
                state.params, state.opt_state, state.comms)
        return jax.make_jaxpr(lambda p, o, c, m: fn(p, o, c, mask=m))(
            state.params, state.opt_state, state.comms, jnp.asarray(mask))

    def round_jaxpr(self, rnd: Round, state: HSGDState, batches, mask=None):
        """ClosedJaxpr of the compiled round body for one ``Round``
        signature — the same cached function ``run_rounds`` dispatches
        (tracing it here warms nothing and compiles nothing), walked by
        ``repro.analysis`` for rules R3/R4 and the per-round collective
        budget."""
        fn = self.round_fn(rnd, masked=mask is not None)
        if mask is None:
            return jax.make_jaxpr(lambda s, b: fn(s, b))(state, batches)
        return jax.make_jaxpr(lambda s, b, m: fn(s, b, m))(
            state, batches, jnp.asarray(mask))


def _wire_eligible(plan, event: SyncEvent) -> bool:
    """Can this event's sync lower as a compressed collective
    (:meth:`Comms.sync` with a ``reduce_mode``)?  The wire path reproduces
    exactly the default lowering — bucketized payloads, uniform hierarchy,
    the aggregator's stock f32 encode/mean/decode, no static per-worker or
    per-event weights — so anything bespoke falls back to the legacy
    encode→reduce→decode roundtrip unchanged.  Runtime masks ARE supported
    (they thread into the WireOps)."""
    comms = plan.comms
    if comms is None or not (comms.wire_reduce and comms.codec.wire_reduce
                             and comms.bucket):
        return False
    topo = plan.topology
    if getattr(topo, "spec", None) is None:       # grouped: segment means
        return False
    if event.groups is not None or event.weights is not None:
        return False
    agg = topo.aggregator
    if type(agg).encode is not Aggregator.encode or \
            type(agg).decode is not Aggregator.decode:
        return False                              # custom wire hooks
    if agg.worker_weights(topo.n) is not None:
        return False                              # weighted means
    return jnp.dtype(agg.accum_dtype) == jnp.dtype(jnp.float32)


def _apply_sync(plan, reduce_fn, params, opt_state, cstate, wire=None):
    """Shared sync dispatch for both executors: apply ``reduce_fn`` (the
    backend's aggregation — topology segment-means under sim, named-axis
    collectives under mesh) either directly or through the comms wire
    (bucketize + codec roundtrip + reduce), optimizer moments riding the
    same path (stateless: no error feedback on moments).  ``wire`` is the
    backend's :class:`~repro.comms.reduce.WireOps` when the event lowers as
    a compressed collective (see :func:`_wire_eligible`), else None."""
    if plan.comms is None:
        params = reduce_fn(params)
        if plan.aggregate_opt_state:
            opt_state = _merge_moments(
                opt_state, reduce_fn(_moments_only(opt_state)))
        return params, opt_state, cstate
    params, cstate = plan.comms.sync(params, reduce_fn, residual=cstate,
                                     reduce_mode=wire)
    if plan.aggregate_opt_state:
        agg, _ = plan.comms.sync(_moments_only(opt_state), reduce_fn,
                                 reduce_mode=wire)
        opt_state = _merge_moments(opt_state, agg)
    return params, opt_state, cstate


def _keep_rows(mask, new, old):
    """Row-select on the leading worker axis: mask True -> ``new``, False ->
    ``old`` — the one definition of per-worker state selection (runtime
    participation masks, partial-group restores)."""
    def sel(a, b):
        m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(sel, new, old)


def _keep_shard(keep, new, old):
    """Per-shard counterpart of :func:`_keep_rows`: ``keep`` is this
    worker's scalar bool, selecting its whole shard (mesh backend, where
    each shard holds exactly one worker's row)."""
    return jax.tree.map(lambda a, b: jnp.where(keep, a, b), new, old)


def _stale_delta(live, agg, snap):
    """The stale-sync fold: the aggregate was computed on ``snap``, the live
    state has since moved on — apply the aggregation as a CORRECTION,
    ``live + (agg(snap) − snap)``, elementwise per worker row.  Elementwise
    arithmetic is bitwise-stable between sim's (n, ...) block and mesh's
    per-shard rows, which is what keeps ``exact=True`` bitwise through the
    stale path (DESIGN.md §9)."""
    return jax.tree.map(lambda a, b, c: a + (b - c), live, agg, snap)


def _stack_batches(n_local: int, batches):
    """length-``n_local`` tuple of per-step batches -> one (n_local, ...)
    stacked pytree, INSIDE the jitted graph so one round is exactly one
    dispatch (no host-side jnp.stack per round)."""
    if n_local == 1:
        return jax.tree.map(lambda x: x[None], batches[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


# ---------------------------------------------------------------------------
# sim: vmap over the worker axis on one device (the paper-experiment path)
# ---------------------------------------------------------------------------
class SimExecutor(Executor):
    """n = tens..hundreds of CPU "workers" on one device; aggregations are
    reshapes/means (uniform hierarchy) or membership segment-means (arbitrary
    fixed groupings, Theorem 1) through ``topology.aggregate``.

    With a comms plan bound, every sync routes through
    ``plan.comms.sync``: the tree is fused into flat per-dtype buckets,
    each worker's payload codec-roundtripped (error-feedback residuals
    threaded through ``HSGDState.comms``), and ``topology.aggregate`` runs
    on the O(dtypes) buffers — the aggregator rule is applied unchanged.

    **Masked-round contract** (shared with ``MeshExecutor``, which must
    replay it bitwise in exact mode): ``step_fn(event, masked=True)`` is
    Algorithm-1 partial participation — a masked-out worker's update is
    discarded and it still receives the aggregate; ``round_fn(rnd,
    masked=True)`` is the elastic-drop semantics — a dropped worker ran its
    local updates but neither contributes to nor receives the aggregate,
    keeping its exact post-update params, opt state and unconsumed comms
    residuals (see :meth:`_apply_event`)."""

    def _apply_event(self, params, opt_state, cstate, event: SyncEvent,
                     mask=None, drop: bool = False):
        """``mask`` weights the aggregation over participating workers only.
        ``drop=False`` is the classic runtime-mask semantics: masked-out
        workers still RECEIVE the aggregate (Algorithm 1 — they are present,
        they just contributed nothing).  ``drop=True`` is the elastic-
        deadline semantics: masked-out workers neither contribute nor
        receive — they were still computing when the barrier closed, so they
        keep their exact post-update params, opt state and unconsumed comms
        residuals (the elastic-participation contract; tested)."""
        with jax.named_scope(sync_scope(event.level)):
            plan = self.plan
            reduce_fn = lambda tree: plan.topology.aggregate(tree, event,
                                                             mask=mask)
            wire = SimWireOps(plan.topology.spec.group_sizes, event.level,
                              mask) if _wire_eligible(plan, event) else None
            new_p, new_o, new_c = _apply_sync(plan, reduce_fn, params,
                                              opt_state, cstate, wire=wire)
            if drop:
                keep = jnp.asarray(mask).astype(bool)
                new_p = _keep_rows(keep, new_p, params)
                new_o = _keep_rows(keep, new_o, opt_state)
                if cstate is not None:
                    new_c = _keep_rows(keep, new_c, cstate)
            if plan.comms is not None:
                # topology.aggregate keeps non-participants' rows untouched,
                # but the comms path hands it codec-roundtripped payloads —
                # restore the true state (and unconsumed residual) of
                # workers a partial-group event did not sync
                part = plan.topology.participants(event)
                if part is not None:
                    keep = jnp.asarray(part)
                    new_p = _keep_rows(keep, new_p, params)
                    new_o = _keep_rows(keep, new_o, opt_state)
                    if cstate is not None:
                        new_c = _keep_rows(keep, new_c, cstate)
                if mask is not None and cstate is not None:
                    # runtime-masked workers still RECEIVE the aggregate
                    # (Algorithm 1) but transmitted nothing: their
                    # error-feedback residual must not be consumed
                    new_c = _keep_rows(jnp.asarray(mask).astype(bool),
                                       new_c, cstate)
            return new_p, new_o, new_c

    def sync_fn(self, event: SyncEvent):
        def sync(params, opt_state, cstate, mask=None):
            return self._apply_event(params, opt_state, cstate, event,
                                     mask=mask)
        return sync

    def _stale_fold(self, params, opt_state, pending, ops, mask,
                    event: SyncEvent, want_staleness: bool):
        """Apply one boundary's static :class:`~repro.core.hsgd.StaleOp`s:
        each fold applies a stored snapshot's POSTED aggregate to the live
        state as a purely elementwise delta (:func:`_stale_delta`); a
        ``snapshot`` op then runs the level's aggregation on the post-fold
        payload — through the SAME machinery a fresh sync uses
        (:meth:`_apply_event` — codecs, wire eligibility, masked-residual
        preservation all included, against the SLOT's own residual chain) —
        and captures payload + aggregate into the newest slot.  Aggregating
        at posting time (not fold time) pins each outstanding sync's content
        to the participation mask of the boundary that posted it, so elastic
        drops at later boundaries cannot retroactively recombine
        mixed-vintage slots (DESIGN.md §9).

        Returns ``(params, opt_state, pending, staleness)`` where
        ``staleness`` (only when requested) is the probe scalar: worker-mean
        ‖applied − fresh‖² against the codec-free barrier aggregate of the
        pre-fold params — 0 at warm-up and flush boundaries."""
        with jax.named_scope(SYNC_FOLD):
            plan = self.plan
            agg_opt = plan.aggregate_opt_state
            pre_params = params
            pending = dict(pending)
            for op in ops:
                slot = pending[op.level]
                snaps, res = list(slot.snaps), slot.residual
                s = len(snaps)
                for j in range(op.n_fold):
                    snap = snaps[s - op.warm + j]
                    params = _stale_delta(params, snap.agg, snap.params)
                    if agg_opt:
                        opt_state = _merge_moments(opt_state, _stale_delta(
                            _moments_only(opt_state), snap.agg_opt, snap.opt))
                if op.snapshot:
                    base_o = _moments_only(opt_state) if agg_opt else {}
                    new_p, new_o, res = self._apply_event(
                        params, base_o, res, SyncEvent(level=op.level),
                        mask=mask)
                    snaps = snaps[1:] + [StaleSnap(
                        params, base_o, new_p,
                        _moments_only(new_o) if agg_opt else {})]
                pending[op.level] = StaleSlot(tuple(snaps), res)
            stale_val = None
            if want_staleness:
                own = ops[-1] if ops and ops[-1].snapshot else None
                if own is not None and own.n_fold:
                    fresh = plan.topology.aggregate(pre_params, event,
                                                    mask=mask)
                    d2 = sum(jnp.sum(jnp.square((a - f).astype(jnp.float32))
                                     .reshape(a.shape[0], -1), axis=1)
                             for a, f in zip(jax.tree.leaves(params),
                                             jax.tree.leaves(fresh)))
                    stale_val = jnp.mean(d2)
                else:
                    stale_val = jnp.zeros((), jnp.float32)
            return params, opt_state, pending, stale_val

    def _probe_row_fn(self, event: Optional[SyncEvent]):
        """The in-graph divergence probe for sync steps (None when metrics
        are off, divergences disabled, or there is no event).  Pushed BEFORE
        :meth:`_apply_event` so it measures the PRE-aggregation worker
        params — the live eq. (10) partition."""
        plan = self.plan
        if event is None or plan.metrics is None \
                or not plan.metrics.divergences:
            return None
        return plan.metrics.sim_row_fn(plan.topology)

    # -- one combined step per event ------------------------------------------
    def _build_step(self, event: Optional[SyncEvent], masked: bool = False):
        local_update = self.plan.local_update_fn()
        row_fn = self._probe_row_fn(event)
        # the staleness channel widens the probe row; the per-step path has
        # no stale boundaries (plan.step refuses async engines), so it pads 0
        stale_channel = row_fn is not None and self.plan.metrics.staleness

        def step(state: HSGDState, batch, mask=None):
            with jax.named_scope(LOCAL):
                params, opt_state, metrics = jax.vmap(local_update)(
                    state.params, state.opt_state, batch)
            cstate, mbuf = state.comms, state.metrics
            if masked:
                # non-participating workers keep their previous state
                params = _keep_rows(mask, params, state.params)
                opt_state = _keep_rows(mask, opt_state, state.opt_state)
            if event is not None:
                if row_fn is not None and mbuf is not None:
                    row = row_fn(params)
                    if stale_channel:
                        row = jnp.concatenate(
                            [row, jnp.zeros((1,), jnp.float32)])
                    mbuf = mbuf.push(row)
                amask = mask if masked else None
                params, opt_state, cstate = self._apply_event(
                    params, opt_state, cstate, event, mask=amask)
            metrics = jax.tree.map(lambda m: m.mean(), metrics)
            return HSGDState(params, opt_state, state.step + 1,
                             cstate, mbuf, state.pending), metrics

        if not self.plan._jit:
            return step
        return jax.jit(step, donate_argnums=0) if masked else \
            jax.jit(lambda s, b: step(s, b), donate_argnums=0)

    def _build_round(self, rnd: Round, masked: bool = False):
        """One jitted function for '``n_local`` local steps then sync': the
        local block is a single ``lax.scan`` over the stacked batches, so the
        whole round is ONE dispatch + ONE jit-cache hit instead of
        ``n_local`` of each.

        ``masked=True`` builds the elastic-drop variant ``(state, batches,
        mask) -> ...``: EVERY worker still runs the local block (a dropped
        worker was computing, not absent), but the round-ending sync runs
        with ``drop`` semantics — workers masked out neither contribute to
        nor receive the aggregate (see :meth:`_apply_event`).  One compiled
        function per Round serves every mask value (the mask is a traced
        argument)."""
        local_update = self.plan.local_update_fn()
        vupdate = jax.vmap(local_update)
        row_fn = self._probe_row_fn(rnd.event)
        stale_channel = row_fn is not None and self.plan.metrics.staleness
        if masked:
            assert rnd.event is not None, \
                "a masked round needs a sync event to drop workers from"

        def round_fn(state: HSGDState, batches, mask=None):
            """batches: a length-``n_local`` tuple of per-step batches."""
            stacked = _stack_batches(rnd.n_local, batches)

            def body(carry, batch):
                params, opt_state = carry
                params, opt_state, metrics = vupdate(params, opt_state, batch)
                return (params, opt_state), jax.tree.map(
                    lambda m: m.mean(), metrics)

            # what XLA's loop passes rebuild keeps the loop's name, so the
            # whole local block is named, not only the step inside it
            with jax.named_scope(LOCAL):
                (params, opt_state), metrics = jax.lax.scan(
                    body, (state.params, state.opt_state), stacked)
            cstate, mbuf, pending = state.comms, state.metrics, state.pending
            if rnd.event is not None:
                div_row = row_fn(params) \
                    if (row_fn is not None and mbuf is not None) else None
                stale_val = None
                if rnd.stale:
                    # stale boundary: fold what is due, then (own-level
                    # boundaries) snapshot; a flush boundary's fresh sync
                    # applies AFTER its folds.  Drop restores happen once,
                    # against the pre-boundary state, so a dropped worker
                    # misses the folds, the snapshot roll and the fresh
                    # aggregate alike.
                    p0, o0, c0, pend0 = params, opt_state, cstate, pending
                    params, opt_state, pending, stale_val = self._stale_fold(
                        params, opt_state, pending, rnd.stale, mask,
                        rnd.event, want_staleness=stale_channel)
                    if not rnd.stale[-1].snapshot:
                        params, opt_state, cstate = self._apply_event(
                            params, opt_state, cstate, rnd.event, mask=mask)
                    if masked:
                        keep = jnp.asarray(mask).astype(bool)
                        params = _keep_rows(keep, params, p0)
                        opt_state = _keep_rows(keep, opt_state, o0)
                        if cstate is not None:
                            cstate = _keep_rows(keep, cstate, c0)
                        pending = _keep_rows(keep, pending, pend0)
                else:
                    params, opt_state, cstate = self._apply_event(
                        params, opt_state, cstate, rnd.event,
                        mask=mask, drop=masked)
                if div_row is not None:
                    if stale_channel:
                        if stale_val is None:
                            stale_val = jnp.zeros((), jnp.float32)
                        div_row = jnp.concatenate([div_row, stale_val[None]])
                    mbuf = mbuf.push(div_row)
            state = HSGDState(params, opt_state, state.step + rnd.n_local,
                              cstate, mbuf, pending)
            return state, metrics  # metrics stacked (n_local,) per entry

        if not self.plan._jit:
            return round_fn
        if masked:
            return jax.jit(round_fn, donate_argnums=0)
        return jax.jit(lambda s, b: round_fn(s, b), donate_argnums=0)


# ---------------------------------------------------------------------------
# mesh: shard_map + named-axis collectives (the deployment path)
# ---------------------------------------------------------------------------
class MeshExecutor(Executor):
    """One worker per replica-mesh coordinate; sync events ARE named-axis
    all-reduces.

    mesh: for a uniform hierarchy, a mesh whose replica axes (everything but
    'model') mirror the hierarchy's ``group_sizes`` outermost-first — build
    one with ``launch.mesh.make_hsgd_mesh(spec.group_sizes)`` /
    ``make_host_mesh(group_sizes=...)``; a ``GroupedTopology`` has no
    per-level axis structure, so any replica layout with
    ``n_replicas(mesh) == topology.n`` works (events lower over the FLAT
    worker axis with one-hot membership weights — see
    ``GroupedTopology.shard_aggregate``).  None auto-builds the matching
    mesh from the bound topology (needs prod(group_sizes) / n devices).
    Params are placed ``P(('pod','data'), ...)`` so the level-ℓ mean is an
    all-reduce over exactly the mesh axes of levels >= ℓ.

    **Masked-round contract** (parity with ``SimExecutor``): runtime
    participation masks thread into the round core as a per-worker weight
    on the collective.  ``step_fn(event, masked=True)`` is the Algorithm-1
    semantics — a masked-out worker contributes nothing but still RECEIVES
    the aggregate (and keeps its unconsumed comms residual);
    ``round_fn(rnd, masked=True)`` is the elastic-deadline semantics — a
    dropped worker still runs its local updates but neither contributes to
    nor receives the aggregate, keeping its exact post-update params, opt
    state AND unconsumed comms residuals.  Elastic runtime policies
    therefore run on this backend too (``HSGD(..., executor='mesh',
    runtime=RuntimeModel(policy=...))``).

    exact: replay the ENTIRE sim reduce per shard — all_gather the full
    worker block and run ``topology.aggregate`` on it (identical input
    shape, identical reduce axes, identical weight combination), each shard
    then selecting its own row — instead of the production pmean/psum
    lowering.  Bit-identical to the SimExecutor trajectory for every
    topology (uniform AND grouped), every event (full, partial-group,
    masked, dropped) and every codec, at n_workers x the sync bytes.
    Verification mode; the default lowering matches sim to
    accumulation-dtype rounding (tested)."""

    def __init__(self, mesh=None, *, exact: bool = False):
        super().__init__()
        self.mesh = mesh
        self.exact = exact
        self.rep_axes = None

    def twin(self) -> "MeshExecutor":
        return MeshExecutor(mesh=self.mesh, exact=self.exact)

    def _validate(self) -> None:
        from repro.launch.mesh import (make_hsgd_mesh, n_replicas,
                                       replica_axes)
        topo = self.plan.topology
        spec = getattr(topo, "spec", None)
        if self.mesh is None:
            self.mesh = make_hsgd_mesh(
                spec.group_sizes if spec is not None else (topo.n,))
        self.rep_axes = replica_axes(self.mesh)
        sizes = tuple(self.mesh.shape[a] for a in self.rep_axes)
        if spec is not None:
            if sizes != tuple(spec.group_sizes):
                raise ValueError(
                    f"mesh replica axes {dict(zip(self.rep_axes, sizes))} "
                    f"do not mirror the hierarchy levels "
                    f"{spec.group_sizes}; build the mesh with "
                    f"make_hsgd_mesh(spec.group_sizes)")
        elif n_replicas(self.mesh) != topo.n:
            raise ValueError(
                f"{type(topo).__name__} lowers over the flat worker axis: "
                f"need n_replicas(mesh) == {topo.n} workers, got "
                f"{n_replicas(self.mesh)} "
                f"({dict(zip(self.rep_axes, sizes))})")
        if spec is None and self.plan.metrics is not None \
                and self.plan.metrics.divergences:
            raise NotImplementedError(
                f"{type(topo).__name__} has no named-axis level structure "
                "for the in-graph divergence probe; run it on the simulator "
                "(HSGD(..., executor='sim')) or disable divergence probing "
                "(metrics=Metrics(divergences=False))")

    def place(self, state: HSGDState) -> HSGDState:
        from repro.launch.partitioning import hsgd_state_shardings
        return jax.device_put(state, hsgd_state_shardings(self.mesh, state))

    # -- spec helpers -------------------------------------------------------
    def _lead_spec(self, ndim: int, lead_axis: int = 0) -> P:
        """Worker axis over all replica mesh axes, other dims replicated
        (shared definition with the device-placement shardings)."""
        from repro.launch.partitioning import worker_axis_spec
        return worker_axis_spec(self.rep_axes, ndim, lead_axis)

    # -- the per-shard sync body (shared: round core + analysis trace) ------
    def _event_applier(self, event: SyncEvent, drop: bool = False):
        """Per-shard sync body for one event: ``(params, opt_state, cstate,
        mask, widx) -> (params, opt_state, cstate)``.  Extracted from the
        round core so :meth:`sync_fn` can wrap the IDENTICAL closure in its
        own shard_map — the audited sync program and the round body can
        never drift apart."""
        plan, rep = self.plan, self.rep_axes
        topo = plan.topology
        acc = topo.aggregator.accum_dtype
        wvec = topo._event_weights(event, None)
        part = topo.participants(event)
        wire_ok = _wire_eligible(plan, event)
        if wire_ok:
            ev_axes = tuple(topo.level_axes(event, rep))
            members = math.prod(self.mesh.shape[a] for a in ev_axes)

        def apply_event(params, opt_state, cstate, mask, widx):
            with jax.named_scope(sync_scope(event.level)):
                wire = None
                if wire_ok:
                    # exact mode replays the SIM wire arithmetic on the
                    # gathered block (bitwise vs SimExecutor); production
                    # lowers the codec's collective over exactly the
                    # event's mesh axes
                    wire = ExactWireOps(rep, widx, topo.spec.group_sizes,
                                        event.level, mask) if self.exact else \
                        MeshWireOps(ev_axes, members, mask, widx)
                if self.exact:
                    # replay the ENTIRE sim reduce on the gathered worker block
                    # (same shapes, same weight combination -> bitwise), then
                    # select this shard's own row
                    def reduce_fn(tree):
                        with jax.named_scope(COLLECTIVE):
                            g = jax.tree.map(
                                lambda x: jax.lax.all_gather(
                                    x, rep, axis=0, tiled=True), tree)
                        out = topo.aggregate(g, event, mask=mask)
                        return jax.tree.map(
                            lambda x: jax.lax.dynamic_index_in_dim(
                                x, widx, axis=0, keepdims=True), out)
                else:
                    w = None if mask is None else mask.astype(acc)[widx]
                    if wvec is not None:
                        ws = jnp.asarray(wvec)[widx]
                        w = ws if w is None else w * ws
                    one = lambda x: topo.shard_aggregate(
                        x, rep, event, worker_index=widx, weight=w)

                    def reduce_fn(tree):
                        with jax.named_scope(COLLECTIVE):
                            return jax.tree.map(one, tree)
                new_p, new_o, new_c = _apply_sync(plan, reduce_fn, params,
                                                  opt_state, cstate, wire=wire)
                if plan.comms is not None:
                    # same restores as SimExecutor._apply_event, per shard: the
                    # comms path hands the reduce codec-roundtripped payloads,
                    # so workers a partial-group event did not sync get their
                    # true state back, and a masked-out worker's error-feedback
                    # residual is not consumed
                    if part is not None:
                        keep = jnp.asarray(part)[widx]
                        new_p = _keep_shard(keep, new_p, params)
                        new_o = _keep_shard(keep, new_o, opt_state)
                        if cstate is not None:
                            new_c = _keep_shard(keep, new_c, cstate)
                    if mask is not None and cstate is not None:
                        new_c = _keep_shard(mask.astype(bool)[widx], new_c,
                                            cstate)
                if drop:
                    keep = mask.astype(bool)[widx]
                    new_p = _keep_shard(keep, new_p, params)
                    new_o = _keep_shard(keep, new_o, opt_state)
                    if cstate is not None:
                        new_c = _keep_shard(keep, new_c, cstate)
                return new_p, new_o, new_c

        return apply_event

    def _stale_applier(self, event: SyncEvent, ops):
        """Per-shard counterpart of :meth:`SimExecutor._stale_fold`: folds
        apply each snapshot's posted aggregate as a per-shard elementwise
        delta; the ``snapshot`` op's posting-time aggregation reuses
        :meth:`_event_applier` (so exact mode replays it bitwise on the
        gathered payload block, and production mode lowers it over the
        event's mesh axes).  The staleness probe scalar is the pmean of this
        shard's ‖applied − fresh‖² against the production named-axis
        aggregate of the pre-fold params (rounding-level vs sim, like every
        mesh probe value)."""
        plan, rep = self.plan, self.rep_axes
        topo = plan.topology
        agg_opt = plan.aggregate_opt_state
        acc = topo.aggregator.accum_dtype
        wvec = topo._event_weights(event, None)
        appliers = {op.level: self._event_applier(SyncEvent(level=op.level))
                    for op in ops if op.snapshot}

        def fold(params, opt_state, pending, mask, widx, want_staleness):
            with jax.named_scope(SYNC_FOLD):
                pre_params = params
                pending = dict(pending)
                for op in ops:
                    slot = pending[op.level]
                    snaps, res = list(slot.snaps), slot.residual
                    s = len(snaps)
                    for j in range(op.n_fold):
                        snap = snaps[s - op.warm + j]
                        params = _stale_delta(params, snap.agg, snap.params)
                        if agg_opt:
                            opt_state = _merge_moments(
                                opt_state, _stale_delta(
                                    _moments_only(opt_state),
                                    snap.agg_opt, snap.opt))
                    if op.snapshot:
                        base_o = _moments_only(opt_state) if agg_opt else {}
                        new_p, new_o, res = appliers[op.level](
                            params, base_o, res, mask, widx)
                        snaps = snaps[1:] + [StaleSnap(
                            params, base_o, new_p,
                            _moments_only(new_o) if agg_opt else {})]
                    pending[op.level] = StaleSlot(tuple(snaps), res)
                stale_val = None
                if want_staleness:
                    own = ops[-1] if ops and ops[-1].snapshot else None
                    if own is not None and own.n_fold:
                        w = None if mask is None else mask.astype(acc)[widx]
                        if wvec is not None:
                            ws = jnp.asarray(wvec)[widx]
                            w = ws if w is None else w * ws
                        fresh = jax.tree.map(
                            lambda x: topo.shard_aggregate(
                                x, rep, event, worker_index=widx, weight=w),
                            pre_params)
                        d2 = sum(jnp.sum(jnp.square(
                                     (a - f).astype(jnp.float32)))
                                 for a, f in zip(jax.tree.leaves(params),
                                                 jax.tree.leaves(fresh)))
                        stale_val = jax.lax.pmean(d2, rep)
                    else:
                        stale_val = jnp.zeros((), jnp.float32)
                return params, opt_state, pending, stale_val

        return fold

    def sync_fn(self, event: SyncEvent):
        plan, mesh, rep = self.plan, self.mesh, self.rep_axes
        sizes = tuple(mesh.shape[a] for a in rep)
        applier = self._event_applier(event)

        def shard_body(params, opt_state, cstate, mask):
            widx = flat_worker_index(rep, sizes)
            return applier(params, opt_state, cstate, mask, widx)

        def sync(params, opt_state, cstate, mask=None):
            pspec = jax.tree.map(lambda x: self._lead_spec(x.ndim), params)
            ospec = jax.tree.map(lambda x: self._lead_spec(x.ndim), opt_state)
            cspec = jax.tree.map(lambda x: self._lead_spec(x.ndim), cstate)
            # same check_vma policy as the round core (see _round_core)
            kw = dict(check_vma=False) \
                if (plan.comms is not None or mask is not None) else {}
            if mask is None:
                fn = jax.shard_map(
                    lambda p, o, c: shard_body(p, o, c, None), mesh=mesh,
                    in_specs=(pspec, ospec, cspec),
                    out_specs=(pspec, ospec, cspec), **kw)
                return fn(params, opt_state, cstate)
            fn = jax.shard_map(lambda p, o, c, m: shard_body(p, o, c, m),
                               mesh=mesh,
                               in_specs=(pspec, ospec, cspec, P()),
                               out_specs=(pspec, ospec, cspec), **kw)
            return fn(params, opt_state, cstate, jnp.asarray(mask))

        return sync

    # -- the shard_mapped round body ----------------------------------------
    def _round_core(self, event: Optional[SyncEvent], masked: bool = False,
                    drop: bool = False, stale=()):
        """(params, opt_state, comms_state, stacked_batches[, mask]) ->
        (params, opt_state, comms_state, metrics) with the local scan and
        the event collective under one shard_map; each shard holds exactly
        one worker.  The round length is carried by the stacked batch's
        leading axis.  ``stale`` (the Round's static
        :class:`~repro.core.hsgd.StaleOp`s) routes the boundary through the
        stale-fold path (:meth:`_stale_applier`); the pending slots ride the
        shard_map sharded exactly like params (their leaves keep the leading
        worker axis).

        With a comms plan bound, each shard fuses its ``(1, ...)`` leaves
        into flat per-dtype buffers, codec-roundtrips them (error-feedback
        residuals are sharded like params), and the named-axis collective
        runs once per BUFFER — O(dtypes) pmeans per sync in the lowered
        program instead of O(leaves).

        ``masked=True`` threads a replicated (n,) runtime mask into the
        body; each shard folds its own mask entry into the collective's
        weight (mirroring ``Topology._event_weights``) and row-selects its
        state afterwards.  ``drop`` picks between the two mask semantics —
        see the class docstring.

        With metrics on, the probe buffer rides through the shard_map
        REPLICATED (``P()`` in and out): the divergence row is the
        named-axis probe (:meth:`~repro.obs.Metrics.mesh_row_fn` — per-level
        pmean group means, one final stacked pmean, so the pushed values are
        identical on every shard), measured on the pre-aggregation shard
        params right before the event collective."""
        plan, mesh, rep = self.plan, self.mesh, self.rep_axes
        vupdate = jax.vmap(plan.local_update_fn())
        sizes = tuple(mesh.shape[a] for a in rep)
        apply_event = self._event_applier(event, drop=drop) \
            if event is not None else None
        # the stale path never lets the applier restore drops itself: the
        # whole boundary (folds + snapshot roll + fresh apply) restores once
        # against the pre-boundary state below
        apply_fresh = self._event_applier(event) \
            if (event is not None and stale) else None
        stale_fold = self._stale_applier(event, stale) if stale else None
        row_fn = None
        if event is not None and plan.metrics is not None \
                and plan.metrics.divergences:
            row_fn = plan.metrics.mesh_row_fn(plan.topology, rep)
        stale_channel = row_fn is not None and plan.metrics.staleness

        def body(params, opt_state, cstate, mbuf, pending, stacked, mask):
            # per-shard shapes: leading worker axis == 1
            def local_block(carry, batch):
                p, o = carry
                p, o, metrics = vupdate(p, o, batch)
                return (p, o), jax.tree.map(lambda m: m.mean(), metrics)

            (p0, o0) = params, opt_state
            with jax.named_scope(LOCAL):
                (params, opt_state), metrics = jax.lax.scan(
                    local_block, (params, opt_state), stacked)
            widx = flat_worker_index(rep, sizes)
            if masked and not drop:
                # Algorithm-1 masked step: a non-participating worker never
                # ran its update (it still receives the aggregate below)
                keep = mask.astype(bool)[widx]
                params = _keep_shard(keep, params, p0)
                opt_state = _keep_shard(keep, opt_state, o0)
            if event is not None:
                div_row = row_fn(params) \
                    if (row_fn is not None and mbuf is not None) else None
                stale_val = None
                if stale:
                    amask = mask if masked else None
                    pp, po, pc, ppend = params, opt_state, cstate, pending
                    params, opt_state, pending, stale_val = stale_fold(
                        params, opt_state, pending, amask, widx,
                        stale_channel)
                    if not stale[-1].snapshot:
                        params, opt_state, cstate = apply_fresh(
                            params, opt_state, cstate, amask, widx)
                    if drop:
                        keep = mask.astype(bool)[widx]
                        params = _keep_shard(keep, params, pp)
                        opt_state = _keep_shard(keep, opt_state, po)
                        if cstate is not None:
                            cstate = _keep_shard(keep, cstate, pc)
                        pending = _keep_shard(keep, pending, ppend)
                else:
                    params, opt_state, cstate = apply_event(
                        params, opt_state, cstate,
                        mask if masked else None, widx)
                if div_row is not None:
                    if stale_channel:
                        if stale_val is None:
                            stale_val = jnp.zeros((), jnp.float32)
                        div_row = jnp.concatenate([div_row, stale_val[None]])
                    mbuf = mbuf.push(div_row)
            # worker-mean of the per-step metrics, replicated everywhere
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, rep), metrics)
            return params, opt_state, cstate, mbuf, pending, metrics

        def core(params, opt_state, cstate, mbuf, pending, stacked,
                 mask=None):
            pspec = jax.tree.map(lambda x: self._lead_spec(x.ndim), params)
            ospec = jax.tree.map(lambda x: self._lead_spec(x.ndim), opt_state)
            cspec = jax.tree.map(lambda x: self._lead_spec(x.ndim), cstate)
            mspec = jax.tree.map(lambda x: P(), mbuf)
            pendspec = jax.tree.map(lambda x: self._lead_spec(x.ndim),
                                    pending)
            bspec = jax.tree.map(lambda x: self._lead_spec(x.ndim, 1), stacked)
            # pallas_call (the comms codec kernels) has no shard_map
            # replication rule, masked rounds mix per-shard row-selects
            # into the collective outputs, and the probe pushes partially-
            # replicated pmeans into the replicated buffer; the aggregates
            # (and the probe row — its last op is a pmean over ALL replica
            # axes) are replicated by construction, so skipping the check
            # is safe.  The stale path mixes fold deltas the same way.
            kw = dict(check_vma=False) \
                if (plan.comms is not None or masked
                    or row_fn is not None or stale) else {}
            if not masked:
                fn = jax.shard_map(
                    lambda p, o, c, mb, pd, b: body(p, o, c, mb, pd, b,
                                                    None),
                    mesh=mesh,
                    in_specs=(pspec, ospec, cspec, mspec, pendspec, bspec),
                    out_specs=(pspec, ospec, cspec, mspec, pendspec, P()),
                    **kw)
                return fn(params, opt_state, cstate, mbuf, pending, stacked)
            # the mask rides in replicated: every shard reads its own entry
            fn = jax.shard_map(
                lambda p, o, c, mb, pd, b, m: body(p, o, c, mb, pd, b, m),
                mesh=mesh,
                in_specs=(pspec, ospec, cspec, mspec, pendspec, bspec, P()),
                out_specs=(pspec, ospec, cspec, mspec, pendspec, P()), **kw)
            return fn(params, opt_state, cstate, mbuf, pending, stacked,
                      mask)

        return core

    # -- compiled entry points ----------------------------------------------
    def _build_step(self, event: Optional[SyncEvent], masked: bool = False):
        # Algorithm-1 mask semantics when masked (drop=False): see class doc
        core = self._round_core(event, masked=masked)  # fails fast

        def step(state: HSGDState, batch, mask=None):
            args = () if not masked else (jnp.asarray(mask),)
            params, opt_state, cstate, mbuf, pending, metrics = core(
                state.params, state.opt_state, state.comms, state.metrics,
                state.pending, jax.tree.map(lambda x: x[None], batch), *args)
            metrics = jax.tree.map(lambda m: m[0], metrics)
            return HSGDState(params, opt_state, state.step + 1,
                             cstate, mbuf, pending), metrics

        if not self.plan._jit:
            return step
        return jax.jit(step, donate_argnums=0) if masked else \
            jax.jit(lambda s, b: step(s, b), donate_argnums=0)

    def _build_round(self, rnd: Round, masked: bool = False):
        # elastic-drop mask semantics when masked (drop=True): see class doc
        if masked:
            assert rnd.event is not None, \
                "a masked round needs a sync event to drop workers from"
        core = self._round_core(rnd.event, masked=masked, drop=masked,
                                stale=rnd.stale)

        def round_fn(state: HSGDState, batches, mask=None):
            stacked = _stack_batches(rnd.n_local, batches)
            args = () if not masked else (jnp.asarray(mask),)
            params, opt_state, cstate, mbuf, pending, metrics = core(
                state.params, state.opt_state, state.comms, state.metrics,
                state.pending, stacked, *args)
            state = HSGDState(params, opt_state, state.step + rnd.n_local,
                              cstate, mbuf, pending)
            return state, metrics  # metrics stacked (n_local,) per entry

        if not self.plan._jit:
            return round_fn
        return jax.jit(round_fn, donate_argnums=0) if masked else \
            jax.jit(lambda s, b: round_fn(s, b), donate_argnums=0)


# ---------------------------------------------------------------------------
# registry — the single construction path (mirrors make_topology/aggregator)
# ---------------------------------------------------------------------------
EXECUTORS = {
    "sim": SimExecutor,
    "mesh": MeshExecutor,
}

ExecutorLike = Union[str, Executor, None]


def make_executor(spec: ExecutorLike = None, **kwargs) -> Executor:
    """Resolve an executor from an instance, a registry name, or None
    (-> SimExecutor, the bitwise paper-experiment path)."""
    if isinstance(spec, Executor):
        assert not kwargs, "kwargs only apply when constructing by name"
        return spec
    if spec is None:
        return SimExecutor(**kwargs)
    name = spec.lower()
    if name not in EXECUTORS:
        raise KeyError(f"unknown executor {spec!r}; "
                       f"known: {sorted(EXECUTORS)}")
    return EXECUTORS[name](**kwargs)


def register_executor(name: str, cls) -> None:
    EXECUTORS[name.lower()] = cls
