"""Optimizers implemented from scratch (no optax dependency).

Each optimizer is an (init, update) pair over arbitrary pytrees:
    state = init(params)
    new_params, new_state = update(params, grads, state, step)

The paper's experiments use Adam(lr=1e-3); large-arch training defaults to
AdamW with cosine schedule; SGD/momentum kept for FedSGD semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (params, grads, state, step) -> (params, state)
    name: str


def sgd(lr: float = 0.01, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(jnp.zeros_like, params)

    def update(params, grads, state, step):
        del step
        if momentum == 0.0:
            new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
            return new, state
        vel = jax.tree.map(lambda v, g: momentum * v + g, state, grads)
        new = jax.tree.map(lambda p, v: p - lr * v.astype(p.dtype), params, vel)
        return new, vel

    return Optimizer(init, update, f"sgd(lr={lr},m={momentum})")


def adam(
    lr: float = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    schedule: Optional[Callable] = None,
    moment_dtype=None,
) -> Optimizer:
    """moment_dtype: store m/v in a reduced dtype (e.g. jnp.bfloat16) —
    halves optimizer-state HBM (the difference between jamba-398b fitting a
    512-chip mesh or not, see EXPERIMENTS.md); update math stays fp32."""
    mdt = moment_dtype or jnp.float32

    def init(params):
        # zeros_like keeps each leaf's varying-axis type, so the moments
        # match the updated carry inside a shard_map'd scan
        m = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mdt), params)
        v = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mdt), params)
        return (m, v)

    def update(params, grads, state, step):
        m, v = state
        t = step.astype(jnp.float32) + 1.0
        lr_t = lr if schedule is None else lr * schedule(step)
        m = jax.tree.map(
            lambda mm, g: (b1 * mm.astype(jnp.float32) + (1 - b1) * g.astype(jnp.float32)).astype(mdt),
            m, grads,
        )
        v = jax.tree.map(
            lambda vv, g: (b2 * vv.astype(jnp.float32) + (1 - b2) * jnp.square(g.astype(jnp.float32))).astype(mdt),
            v, grads,
        )
        mh_scale = 1.0 / (1.0 - b1**t)
        vh_scale = 1.0 / (1.0 - b2**t)

        def step_fn(p, mm, vv):
            mm = mm.astype(jnp.float32)
            vv = vv.astype(jnp.float32)
            upd = (mm * mh_scale) / (jnp.sqrt(vv * vh_scale) + eps)
            if weight_decay:
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * upd).astype(p.dtype)

        new = jax.tree.map(step_fn, params, m, v)
        return new, (m, v)

    wd = f",wd={weight_decay}" if weight_decay else ""
    return Optimizer(init, update, f"adam(lr={lr}{wd})")


def adamw(lr: float = 3e-4, weight_decay: float = 0.1, **kw) -> Optimizer:
    return adam(lr=lr, weight_decay=weight_decay, **kw)


def cosine_schedule(total_steps: int, warmup: int = 0, floor: float = 0.1):
    def fn(step):
        s = step.astype(jnp.float32)
        warm = jnp.minimum(s / jnp.maximum(warmup, 1), 1.0)
        prog = jnp.clip((s - warmup) / jnp.maximum(total_steps - warmup, 1), 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return warm * cos

    return fn


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm
