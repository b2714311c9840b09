"""Optimizers (pure pytree transforms; optimizer state shards like params).

SGD (the paper uses it for the vision models), Adam (the rest), AdamW for
the LM-family training runs.

Optimizer state is float32 whatever the parameter dtype: the update does
its math in float32, so bfloat16 moments would come back float32 and the
train state would change type (and the jitted step recompile) after the
first step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, jnp.ndarray], Tuple[Any, Any]]
    # update(grads, opt_state, params, step) -> (new_params, new_opt_state)


def _zeros_f32(params):
    return jax.tree.map(lambda p: jnp.zeros(jnp.shape(p), jnp.float32),
                        params)


def sgd(lr: float = 1e-2, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum:
            return _zeros_f32(params)
        return ()

    def update(grads, state, params, step):
        del step
        if momentum:
            state = jax.tree.map(lambda m, g: momentum * m + g, state, grads)
            upd = state
        else:
            upd = grads
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) - lr * u).astype(p.dtype),
            params, upd)
        return new_params, state

    return Optimizer(init, update)


def _adam_core(lr, b1, b2, eps, wd):
    def init(params):
        return {"m": _zeros_f32(params), "v": _zeros_f32(params)}

    def update(grads, state, params, step):
        t = step.astype(jnp.float32) + 1.0
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                         state["v"], grads)
        def upd(p, m_, v_):
            mh = m_ / (1 - b1 ** t)
            vh = v_ / (1 - b2 ** t)
            u = mh / (jnp.sqrt(vh) + eps)
            if wd:
                u = u + wd * p
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        new_params = jax.tree.map(upd, params, m, v)
        return new_params, {"m": m, "v": v}

    return Optimizer(init, update)


def adam(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Optimizer:
    return _adam_core(lr, b1, b2, eps, 0.0)


def adamw(lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1) -> Optimizer:
    return _adam_core(lr, b1, b2, eps, weight_decay)
