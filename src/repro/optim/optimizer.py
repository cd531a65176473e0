"""Optimizers from scratch (no optax in this environment).

AdamW with decoupled weight decay, global-norm clipping, bf16-safe (moments
fp32), plus Lion as a lighter-state alternative. State and update are pure
pytree functions; the m/v moments shard like their parameters, and under the
ZeRO-1 flag the trainer shards them over the data axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scopes


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    schedule: str = "cosine"        # cosine | linear | constant


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


def schedule_lr(cfg: OptimizerConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * \
            (1 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1 - cfg.min_lr_ratio) * frac
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


@jax.named_scope(scopes.OPTIM)
def clip_by_global_norm(tree, max_norm):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, tree), norm


def init_opt_state(params) -> OptState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(zeros, params),
                    v=jax.tree.map(zeros, params))


def _is_matrix(p):
    return p.ndim >= 2


@jax.named_scope(scopes.OPTIM)
def adamw_update(cfg: OptimizerConfig, grads, state: OptState, params):
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                     state.m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) *
                     jnp.square(g.astype(jnp.float32)), state.v, grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + cfg.eps)
        if _is_matrix(p):            # decoupled decay on matrices only
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    new_params = jax.tree.map(upd, params, m, v)
    return new_params, OptState(step, m, v), {"lr": lr, "grad_norm": gnorm}


@jax.named_scope(scopes.OPTIM)
def lion_update(cfg: OptimizerConfig, grads, state: OptState, params):
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.b1, cfg.b2

    def upd(p, m_, g):
        g = g.astype(jnp.float32)
        u = jnp.sign(b1 * m_ + (1 - b1) * g)
        if _is_matrix(p):
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    new_params = jax.tree.map(upd, params, state.m, grads)
    m = jax.tree.map(lambda m_, g: b2 * m_ + (1 - b2) * g.astype(jnp.float32),
                     state.m, grads)
    return new_params, OptState(step, m, state.v), {"lr": lr, "grad_norm": gnorm}


def make_optimizer(cfg: OptimizerConfig) -> Callable:
    return {"adamw": adamw_update, "lion": lion_update}[cfg.name]
