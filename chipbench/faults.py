"""Faults planted in the program, to show that ``correct`` catches them.

Each is a context manager that patches the program's classes; build the
engine inside it (``harness.prepare``), since the engine binds the loss
and compiles its rounds when first used.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def state_unchanged():
    """Every local step returns the worker's state as it came in."""
    from repro.core.hsgd import HSGD

    def local_update_fn(self):
        def local_update(params, opt_state, batch):
            _, metrics = self.loss_fn(params, batch)
            return params, opt_state, metrics
        return local_update
    return _patched(HSGD, "local_update_fn", local_update_fn)


def half_batch():
    """Each worker's loss leaves out the second half of its tokens and takes
    the mean over the rest."""
    from repro.models.transformer import DecoderLM
    loss = DecoderLM.loss

    def half(self, params, batch):
        s = batch["targets"].shape[-1]
        mask = jnp.broadcast_to((jnp.arange(s) < s // 2).astype(jnp.float32),
                                batch["targets"].shape)
        return loss(self, params, {**batch, "mask": mask})
    return _patched(DecoderLM, "loss", half)


@contextlib.contextmanager
def sync_left_out():
    """Every average (each level's exchange between workers: in the array
    on the sim executor, between chips on the mesh) is skipped."""
    from repro.core.executors import MeshExecutor, SimExecutor

    def apply_event(self, params, opt_state, cstate, event, mask=None,
                    drop=False):
        return params, opt_state, cstate

    def event_applier(self, event, drop=False):
        return lambda params, opt_state, cstate, mask, widx: (
            params, opt_state, cstate)

    with _patched(SimExecutor, "_apply_event", apply_event), \
            _patched(MeshExecutor, "_event_applier", event_applier):
        yield


def target_altered():
    """Every target token is replaced by the next id where the loss takes
    it."""
    from repro.models.transformer import DecoderLM
    loss = DecoderLM.loss

    def shifted(self, params, batch):
        tgt = (batch["targets"] + 1) % self.cfg.vocab_size
        return loss(self, params, {**batch, "targets": tgt})
    return _patched(DecoderLM, "loss", shifted)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "sync_left_out": sync_left_out, "target_altered": target_altered}
