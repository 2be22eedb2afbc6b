"""Optimizers, implemented from scratch on pytrees (no external deps).

All optimizers follow the (init, update) pair convention:

    state = opt.init(params)
    updates, state = opt.update(grads, state, params)
    params = apply_updates(params, updates)

States are pytrees of arrays with the same tree structure as the params.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "Optimizer",
    "sgd",
    "adam",
    "adamw",
    "apply_updates",
    "clip_by_global_norm",
    "global_norm",
    "chain_clip",
]


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params) -> (updates, state)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    # under the limit the gradients pass through untouched: g * 1.0 would
    # flush subnormal entries to zero on backends that flush denormals
    return jax.tree.map(lambda g: jnp.where(norm > max_norm, g * scale, g),
                        grads), norm


# --------------------------------------------------------------------------
def sgd(lr: float | Callable, momentum: float = 0.0,
        nesterov: bool = False) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        mu = (jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
              if momentum else None)
        return {"step": jnp.zeros((), jnp.int32), "mu": mu}

    def update(grads, state, params=None):
        del params
        step = state["step"] + 1
        lr_t = lr_fn(step)
        if momentum:
            mu = jax.tree.map(
                lambda m, g: momentum * m + g.astype(jnp.float32),
                state["mu"], grads)
            if nesterov:
                upd = jax.tree.map(
                    lambda m, g: -lr_t * (momentum * m + g), mu, grads)
            else:
                upd = jax.tree.map(lambda m: -lr_t * m, mu)
            return upd, {"step": step, "mu": mu}
        upd = jax.tree.map(lambda g: -lr_t * g, grads)
        return upd, {"step": step, "mu": None}

    return Optimizer(init, update)


# --------------------------------------------------------------------------
def adam(lr: float | Callable, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Optimizer:
    return _adam_impl(lr, b1, b2, eps, weight_decay=0.0)


def adamw(lr: float | Callable, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.01) -> Optimizer:
    return _adam_impl(lr, b1, b2, eps, weight_decay=weight_decay)


def _adam_impl(lr, b1, b2, eps, weight_decay) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
        }

    def update(grads, state, params=None):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        stepf = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** stepf
        bc2 = 1.0 - b2 ** stepf
        m = jax.tree.map(
            lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads)
        v = jax.tree.map(
            lambda v_, g: b2 * v_
            + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state["v"], grads)

        def upd(m_, v_, p=None):
            u = -(lr_t) * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if weight_decay and p is not None:
                u = u - lr_t * weight_decay * p.astype(jnp.float32)
            return u

        if weight_decay and params is not None:
            updates = jax.tree.map(upd, m, v, params)
        else:
            updates = jax.tree.map(lambda m_, v_: upd(m_, v_), m, v)
        return updates, {"step": step, "m": m, "v": v}

    return Optimizer(init, update)


# --------------------------------------------------------------------------
def chain_clip(opt: Optimizer, max_norm: float) -> Optimizer:
    """Global-norm clipping composed in front of any optimizer."""

    def update(grads, state, params=None):
        grads, _ = clip_by_global_norm(grads, max_norm)
        return opt.update(grads, state, params)

    return Optimizer(opt.init, update)


# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Schedules:
    """LR schedules as step -> lr callables."""

    @staticmethod
    def constant(lr: float):
        return lambda step: jnp.float32(lr)

    @staticmethod
    def warmup_cosine(peak_lr: float, warmup_steps: int, total_steps: int,
                      floor: float = 0.0):
        def fn(step):
            step = step.astype(jnp.float32) if hasattr(step, "astype") else (
                jnp.float32(step))
            warm = peak_lr * step / max(warmup_steps, 1)
            prog = jnp.clip((step - warmup_steps)
                            / max(total_steps - warmup_steps, 1), 0.0, 1.0)
            cos = floor + (peak_lr - floor) * 0.5 * (
                1.0 + jnp.cos(jnp.pi * prog))
            return jnp.where(step < warmup_steps, warm, cos)
        return fn

    @staticmethod
    def linear_decay(peak_lr: float, total_steps: int):
        def fn(step):
            s = step.astype(jnp.float32) if hasattr(step, "astype") else (
                jnp.float32(step))
            return peak_lr * jnp.clip(1.0 - s / total_steps, 0.0, 1.0)
        return fn
