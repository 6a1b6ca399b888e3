"""WireOps: the reduction surface a codec's compressed collective targets.

The legacy sync path round-trips every worker's payload through
encode→decode and hands the *decoded f32* tree to the executor's reduce —
so the declared compression never reaches the collective.  A ``WireOps``
instead exposes the executor's reduction vocabulary directly to the codec
(:meth:`~repro.comms.codecs.Compressor.reduce`), so the operand on the wire
is the ENCODED payload:

* :meth:`mean` — the aggregator's f32 group mean (the identity codec's
  whole lowering; bitwise-identical to ``UniformTopology.aggregate`` with
  the default :class:`~repro.core.aggregators.MeanAggregator`);
* :meth:`sum` — group sum in the payload's dtype, or in ``dtype`` when
  given: an int8 payload widened to int32 psums AS int32 (exact,
  order-independent — the int8 compressed allreduce);
* :meth:`max` — group max of non-negative block statistics (the shared
  quantization scale);
* :meth:`count` — participants per group (a static Python number when no
  runtime mask is threaded, so unmasked syncs fold it at trace time);
* :meth:`gathered` — ragged/packed forms that have no elementwise reduce
  (sign majority vote): hand ``fn`` the group-stacked wire arrays with the
  member axis at -2, plus the member participation mask (or None);
* :meth:`sparse_mean` — top-k (values, indices) payloads: a scatter-add
  decode-reduce (:func:`topk_decode_reduce`) into the dense mean.

Three implementations keep the exactness ladder intact: ``SimWireOps``
(in-array row-slice reduces over the worker axis — the reference arithmetic),
``MeshWireOps`` (named-axis collectives inside ``shard_map`` — psum/pmax on
the wire dtype, ``all_gather`` for ragged forms), and ``ExactWireOps``
(gather the full worker block, replay ``SimWireOps``, select this shard's
row — bitwise vs sim by construction).

Masks are 0/1 participation weights; a masked-out worker contributes
nothing to any reduction.  All group results come back broadcast over the
worker rows of the input (every member row holds its group's value), which
is the same contract ``Topology.aggregate`` keeps.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import COLLECTIVE, DECODE


def topk_decode_reduce(vals: jax.Array, idx: jax.Array, *,
                       size: int) -> jax.Array:
    """(vals (M, K), idx int32 (M, K)) -> dense sum f32 (size,): the top-k
    collective's receive side, M gathered sparse payloads scatter-added into
    one dense buffer without materializing M dense payloads.  A plain
    scatter-add on every backend (a VMEM-tiled kernel would have to sort the
    ~L/16 gathered entries per worker first)."""
    return jnp.zeros((size,), jnp.float32).at[idx.reshape(-1)].add(
        vals.reshape(-1).astype(jnp.float32))


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


class SimWireOps:
    """In-array reductions over the leading worker axis (the sim executor's
    form).  ``group_sizes`` + ``level`` define the member axis exactly as
    ``UniformTopology.aggregate`` does: a level-ℓ sync reduces over the
    trailing ``prod(group_sizes[ℓ-1:])`` workers of each outer group.

    Those members are a contiguous run of rows, so each group is reduced as
    its own row slice of the unreshaped ``(n, ...)`` input.  Splitting the
    worker axis of a flat ``(n, L)`` bucket into ``(outer, members, L)``
    instead is a relayout the TPU compiler takes minutes over at a full
    model's L; row slices compile in seconds at any L."""

    backend = "sim"

    def __init__(self, group_sizes: Sequence[int], level: int, mask=None):
        self.gs = tuple(int(g) for g in group_sizes)
        self.level = int(level)
        self.mask = mask
        self.members = _prod(self.gs[self.level - 1:])
        self.outer = _prod(self.gs) // self.members

    # -- shared shaping -----------------------------------------------------
    def _weights(self, dtype):
        """The (n,) participation mask in ``dtype``, or None."""
        if self.mask is None:
            return None
        return jnp.asarray(self.mask).astype(dtype).reshape(-1)

    def _per_group(self, fn, arrays, w):
        """``fn(*slices, w_slice)`` on each group's ``(members, ...)`` row
        slices (``w_slice`` the group's (members,) weights or None); each
        result keeps a leading axis of 1 and is broadcast back over its
        member rows."""
        m = self.members
        outs = []
        for o in range(self.outer):
            rows = slice(o * m, (o + 1) * m)
            out = fn(*(a[rows] for a in arrays),
                     None if w is None else w[rows])
            outs.append(jnp.broadcast_to(out, (m,) + out.shape[1:]))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    @staticmethod
    def _col(w, ndim):
        """(members,) weights -> (members, 1, ...) against an ndim array."""
        return None if w is None else w.reshape((-1,) + (1,) * (ndim - 1))

    # -- the reduction vocabulary -------------------------------------------
    def mean(self, x):
        """Bitwise replica of ``UniformTopology.aggregate`` for the default
        MeanAggregator(f32): encode=astype(f32), axis_weighted_mean,
        decode=astype back, broadcast over the group rows."""
        from repro.core.aggregators import axis_weighted_mean

        def one(g, w):
            out = axis_weighted_mean(g.astype(jnp.float32),
                                     self._col(w, g.ndim), (0,), jnp.float32)
            return out.astype(x.dtype)
        return self._per_group(one, (x,), self._weights(jnp.float32))

    def sum(self, x, dtype=None):
        """Masked group sum in ``dtype`` (default: the payload's own) — an
        int8 payload widened to int32 accumulates in int32 (exact,
        reassociation-free), which is the widened-accumulator rule of the
        int8 compressed allreduce."""
        dtype = x.dtype if dtype is None else jnp.dtype(dtype)

        def one(g, w):
            g = g.astype(dtype)
            if w is not None:
                g = g * self._col(w, g.ndim)
            return g.sum(axis=0, keepdims=True, dtype=dtype)
        return self._per_group(one, (x,), self._weights(dtype))

    def max(self, x):
        """Masked group max of NON-NEGATIVE statistics (block amax); masked
        rows are zeroed, never lowering a real max below 0."""
        def one(g, w):
            if w is not None:
                g = g * self._col(w, g.ndim)
            return g.max(axis=0, keepdims=True)
        return self._per_group(one, (x,), self._weights(x.dtype))

    def count(self):
        """Participants per group: a static Python float when unmasked (no
        device work), else a per-row (n, 1) f32 array floored away from 0."""
        if self.mask is None:
            return float(self.members)
        from repro.core.aggregators import denominator_floor
        w = self._weights(jnp.float32)[:, None]
        c = self._per_group(
            lambda g, _: g.sum(axis=0, keepdims=True, dtype=jnp.float32),
            (w,), None)
        return jnp.maximum(c, denominator_floor(jnp.float32))

    def gathered(self, fn: Callable, *arrays):
        """Call ``fn(*stacked, member_mask)`` once per group on its
        ``(1, members, ...)`` row slices of the (n, ...) wire arrays (member
        axis at -2; mask is (1, members) or None) and broadcast each
        ``(1, ...)`` result back over the group's member rows."""
        def one(*args):
            *g, w = args
            return fn(*(a[None] for a in g), None if w is None else w[None])
        return self._per_group(one, arrays, self._weights(jnp.float32))

    def sparse_mean(self, vals, idx, dense):
        """Sim reference for top-k: the decoded dense payload already exists
        locally, so a sparse decode-reduce is pointless — the group mean of
        the dense form IS the legacy arithmetic, bitwise."""
        del vals, idx
        return self.mean(dense)


class MeshWireOps:
    """Named-axis collectives inside ``shard_map`` (the production mesh
    lowering): psum/pmax carry the wire dtype, ragged forms all_gather the
    encoded arrays.  ``axis_names`` are the event's syncing mesh axes
    (``topology.level_axes``); ``members`` their static group size; ``mask``
    the replicated (n,) participation mask and ``widx`` this shard's flat
    worker index."""

    backend = "mesh"

    def __init__(self, axis_names: Sequence[str], members: int, mask=None,
                 widx=None):
        self.axes = tuple(axis_names)
        self.members = int(members)
        self.mask = mask
        self.widx = widx

    def _own_w(self, dtype):
        if self.mask is None:
            return None
        return jnp.asarray(self.mask).astype(dtype)[self.widx]

    def mean(self, x):
        """The aggregator's axis-collective mean (same arithmetic the
        legacy identity sync lowered to: one pmean per buffer)."""
        from repro.core.aggregators import named_axis_weighted_mean
        with jax.named_scope(COLLECTIVE):
            out = named_axis_weighted_mean(x.astype(jnp.float32),
                                           self._own_w(jnp.float32),
                                           self.axes, jnp.float32)
        return out.astype(x.dtype)

    def sum(self, x, dtype=None):
        from repro.core.aggregators import named_axis_sum
        if dtype is not None:
            x = x.astype(dtype)
        with jax.named_scope(COLLECTIVE):
            return named_axis_sum(x, self.axes, self._own_w(x.dtype))

    def max(self, x):
        from repro.core.aggregators import named_axis_max
        with jax.named_scope(COLLECTIVE):
            return named_axis_max(x, self.axes, self._own_w(x.dtype))

    def count(self):
        if self.mask is None:
            return float(self.members)
        from repro.core.aggregators import denominator_floor
        with jax.named_scope(COLLECTIVE):
            c = jax.lax.psum(self._own_w(jnp.float32), self.axes)
        return jnp.maximum(c, denominator_floor(jnp.float32))

    def _member_mask(self):
        if self.mask is None:
            return None
        with jax.named_scope(COLLECTIVE):
            return jax.lax.all_gather(self._own_w(jnp.float32), self.axes)

    def gathered(self, fn: Callable, *arrays):
        """all_gather each (1, ...) wire array over the syncing axes to
        (members, ...) — the member axis lands at -2 because the per-shard
        leading worker axis of size 1 is what gets tiled."""
        with jax.named_scope(COLLECTIVE):
            g = [jax.lax.all_gather(a, self.axes, axis=0, tiled=True)
                 for a in arrays]
        out = fn(*g, self._member_mask())
        return out[None]

    def sparse_mean(self, vals, idx, dense):
        """The top-k compressed collective: ragged all-gather of the
        (values, indices) payload + one scatter-add decode-reduce into the
        dense sum, then the participant mean."""
        with jax.named_scope(COLLECTIVE):
            vg = jax.lax.all_gather(vals, self.axes, axis=0, tiled=True)
            ig = jax.lax.all_gather(idx, self.axes, axis=0, tiled=True)
        wm = self._member_mask()
        count = self.count()
        with jax.named_scope(DECODE):
            if wm is not None:
                vg = vg * wm[:, None]
            size = int(np.prod(dense.shape[1:], dtype=np.int64))
            acc = topk_decode_reduce(vg, ig, size=size)
            out = (acc / count).reshape((1,) + dense.shape[1:])
            return out.astype(dense.dtype)


class ExactWireOps:
    """The mesh executor's ``exact=True`` form: all_gather the FULL worker
    block over every replica axis, replay :class:`SimWireOps` on it, and
    select this shard's own row — bitwise-identical to the sim trajectory
    for every codec, at n_workers x the sync bytes (verification mode)."""

    backend = "sim"  # replays the sim arithmetic

    def __init__(self, rep_axes: Sequence[str], widx,
                 group_sizes: Sequence[int], level: int, mask=None):
        self.rep = tuple(rep_axes)
        self.widx = widx
        self.sim = SimWireOps(group_sizes, level, mask)

    def _gather(self, x):
        with jax.named_scope(COLLECTIVE):
            return jax.lax.all_gather(x, self.rep, axis=0, tiled=True)

    def _pick(self, out):
        return jax.lax.dynamic_index_in_dim(out, self.widx, axis=0,
                                            keepdims=True)

    def mean(self, x):
        return self._pick(self.sim.mean(self._gather(x)))

    def sum(self, x, dtype=None):
        return self._pick(self.sim.sum(self._gather(x), dtype))

    def max(self, x):
        return self._pick(self.sim.max(self._gather(x)))

    def count(self):
        c = self.sim.count()
        return c if isinstance(c, float) else self._pick(c)

    def gathered(self, fn: Callable, *arrays):
        g = [self._gather(a) for a in arrays]
        return self._pick(self.sim.gathered(fn, *g))

    def sparse_mean(self, vals, idx, dense):
        return self._pick(self.sim.sparse_mean(
            self._gather(vals), self._gather(idx), self._gather(dense)))


WireOps = (SimWireOps, MeshWireOps, ExactWireOps)
