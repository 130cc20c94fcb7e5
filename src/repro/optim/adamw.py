"""AdamW in pure JAX (optax is not in the container).

State and updates are pytrees mirroring the params, so parameter shardings
propagate to optimizer state (ZeRO-style sharded moments fall out of FSDP
param shardings for free).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray
    m: Any
    v: Any


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params) -> (new_params, new_state)


def adamw(lr, *, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
          grad_clip=0.0, moment_dtype=jnp.float32) -> Optimizer:
    """lr: float or schedule fn(step)->float."""
    sched = lr if callable(lr) else (lambda _: lr)

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, moment_dtype)
        return AdamWState(step=jnp.zeros((), jnp.int32),
                          m=jax.tree_util.tree_map(zeros, params),
                          v=jax.tree_util.tree_map(zeros, params))

    def update(grads, state, params):
        with jax.named_scope("optimizer"):
            return _update(grads, state, params)

    def _update(grads, state, params):
        step = state.step + 1
        if grad_clip > 0:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        lr_t = sched(step)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v_new = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            mhat = m_new / bc1
            vhat = v_new / bc2
            delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
            p_new = p.astype(jnp.float32) - lr_t * delta
            return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                    v_new.astype(v.dtype))

        flat = jax.tree_util.tree_map(upd, params, grads, state.m, state.v)
        p_new = jax.tree_util.tree_map(lambda t: t[0], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
        m_new = jax.tree_util.tree_map(lambda t: t[1], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
        v_new = jax.tree_util.tree_map(lambda t: t[2], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
        return p_new, AdamWState(step=step, m=m_new, v=v_new)

    return Optimizer(init=init, update=update)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
