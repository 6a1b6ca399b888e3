"""Names of the work inside H-SGD on the profiler's clock.

The host side of ``HSGD.run_rounds`` is marked with
``jax.profiler.TraceAnnotation`` spans, one name per phase of a call:

* ``hsgd.run_rounds`` -- the whole call (argument ``update``: ``seeded``
  where the local step pulls the optimizer's scale back through the loss,
  ``grad`` where it forms the gradient; ``HSGD.update_path``);
* ``hsgd.schedule`` -- ``topology.schedule``, ``compile_schedule`` and the
  static wire accounting;
* ``hsgd.dispatch`` -- one per round: its batches from ``batch_fn`` and the
  call of the round program (arguments ``n_local`` and ``level``, 0 for a
  round that ends between syncs);
* ``hsgd.drain`` -- one device-to-host drain of the probe buffer;
* ``hsgd.history`` -- the per-step metrics brought to the host and the
  history records built from them.

Every span of one call carries ``call=<the call's first step>``.  The
device side is named with ``jax.named_scope`` where the work is traced,
under the names this module declares: ``local`` (the local block: a
round's scan of local steps, which keeps the name on what XLA's loop
passes rebuild), inside it ``local/fwd`` (the loss; its gradient carries
the scope under ``transpose(...)``) and ``local/update`` (the optimizer's
update and its add; on the seeded path the add alone, the step size
riding on the loss's cotangent under ``transpose(...)``);
``sync/L<level>`` (one level's aggregation, with ``encode``,
``collective`` and ``decode`` inside where the comms path has them),
``sync/fold`` (the stale-sync fold) and ``probe`` (the divergence row and
its push).  A scope costs nothing on the device (it is op metadata); a
span, with the profiler off, about a microsecond on the host.
"""
from __future__ import annotations

import jax

SPANS = ("hsgd.run_rounds", "hsgd.schedule", "hsgd.dispatch", "hsgd.drain",
         "hsgd.history")

# device scopes
LOCAL = "local"
LOCAL_FWD = "local/fwd"
LOCAL_UPDATE = "local/update"
SYNC_LEVEL = "sync/L{level}"
SYNC_FOLD = "sync/fold"
PROBE = "probe"
ENCODE, COLLECTIVE, DECODE = "encode", "collective", "decode"


def sync_scope(level: int) -> str:
    """The scope of one level's aggregation, ``sync/L<level>``."""
    return SYNC_LEVEL.format(level=level)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The host span ``name`` (one of :data:`SPANS`) with ``args`` as its
    arguments in the trace."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(name, **args)
