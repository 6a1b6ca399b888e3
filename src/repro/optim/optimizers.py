"""Pure-JAX optimizers (optax-style, no dependency).

``update`` returns the delta to ADD to params. The LR may be a float or a
schedule ``step -> float``; ``step`` is threaded through opt_state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp

Schedule = Union[float, Callable[[jax.Array], jax.Array]]


def _lr_at(lr: Schedule, step: jax.Array) -> jax.Array:
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """``update(grads, state, params) -> (updates, state)``.

    ``seed(state) -> (scale, state)`` is set only where ``update`` is
    exactly ``scale * grads`` with the same new state (plain SGD,
    ``scale = -lr(step)``).  The engine then pulls ``scale`` back through
    the loss (``jax.vjp`` with cotangent ``scale`` where ``jax.grad`` seeds
    1), which yields the update itself: no gradient is formed and scaled in
    a pass of its own.  An optimizer whose update is not linear in the
    gradient (momentum, Adam) leaves it None and takes the gradient path.
    """
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Any]
    seed: Optional[Callable[[Any], Tuple[jax.Array, Any]]] = None


def sgd(lr: Schedule) -> Optimizer:
    """Plain SGD — the paper's optimizer (Algorithm 1 line 5)."""
    def init(params):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        del params
        g = _lr_at(lr, state["step"])
        upd = jax.tree.map(lambda x: (-g * x.astype(jnp.float32)).astype(x.dtype), grads)
        return upd, {"step": state["step"] + 1}

    def seed(state):
        return -_lr_at(lr, state["step"]), {"step": state["step"] + 1}

    return Optimizer(init, update, seed)


def momentum(lr: Schedule, beta: float = 0.9, nesterov: bool = False) -> Optimizer:
    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)}

    def update(grads, state, params):
        del params
        g = _lr_at(lr, state["step"])
        m = jax.tree.map(lambda mi, gi: beta * mi + gi.astype(jnp.float32),
                         state["m"], grads)
        if nesterov:
            upd = jax.tree.map(
                lambda mi, gi, xi: (-g * (beta * mi + gi.astype(jnp.float32))
                                    ).astype(xi.dtype), m, grads, grads)
        else:
            upd = jax.tree.map(lambda mi, gi: (-g * mi).astype(gi.dtype), m, grads)
        return upd, {"step": state["step"] + 1, "m": m}

    return Optimizer(init, update)


def adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        z = lambda x: jnp.zeros_like(x, jnp.float32)
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(z, params),
                "v": jax.tree.map(z, params)}

    def update(grads, state, params):
        step = state["step"] + 1
        g = _lr_at(lr, state["step"])
        m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * jnp.square(
            gi.astype(jnp.float32)), state["v"], grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(mi, vi, pi):
            u = (mi / c1) / (jnp.sqrt(vi / c2) + eps)
            if weight_decay:
                u = u + weight_decay * pi.astype(jnp.float32)
            return (-g * u).astype(pi.dtype)

        return (jax.tree.map(upd, m, v, params),
                {"step": step, "m": m, "v": v})

    return Optimizer(init, update)
