"""Optimizers as pure functions over param pytrees.

Kept deliberately dependency-free (no optax): ``init_opt_state`` builds the
state pytree, ``apply_updates`` maps ``(grads, state, params, lr) → (new_params,
new_state)``. State leaves mirror param leaves, so the *same logical sharding
axes* apply (``opt_state_axes``) — this is what lets ZeRO-style sharding of
optimizer state fall out of the param sharding rules for free.

Schedules include the paper's ``α = 1/(1+t)`` epoch-decaying rate
(``paper_inverse``), used by the SVM reproduction.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config.base import OptimizerConfig
from repro.core.telemetry import scope

OptState = Dict[str, Any]


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def make_schedule(cfg: OptimizerConfig) -> Callable[[jax.Array], jax.Array]:
    """step (int32 array) → learning rate (float32 array)."""
    base = cfg.learning_rate

    if cfg.schedule == "constant":
        return lambda step: jnp.float32(base)

    if cfg.schedule == "paper_inverse":
        # the paper's α = 1/(1+t); `t` is the epoch/step counter. `base`
        # rescales (paper uses base=1).
        return lambda step: jnp.float32(base) / (1.0 + step.astype(jnp.float32))

    if cfg.schedule == "cosine":
        warm = max(1, cfg.warmup_steps)
        total = max(cfg.total_steps, warm + 1)

        def sched(step):
            step = step.astype(jnp.float32)
            warm_lr = base * step / warm
            prog = jnp.clip((step - warm) / (total - warm), 0.0, 1.0)
            cos_lr = 0.5 * base * (1.0 + jnp.cos(jnp.pi * prog))
            return jnp.where(step < warm, warm_lr, cos_lr).astype(jnp.float32)

        return sched

    raise ValueError(f"unknown schedule {cfg.schedule!r}")


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------

def init_opt_state(cfg: OptimizerConfig, params) -> OptState:
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros_like = lambda: jax.tree.map(
        lambda p: jnp.zeros(p.shape, mdt), params)
    if cfg.name == "sgd":
        return {}
    if cfg.name == "momentum":
        return {"mu": zeros_like()}
    if cfg.name == "adamw":
        return {"mu": zeros_like(), "nu": zeros_like()}
    raise ValueError(f"unknown optimizer {cfg.name!r}")


def opt_state_axes(cfg: OptimizerConfig, param_axes) -> OptState:
    """Logical-axes pytree matching ``init_opt_state`` (mirrors params)."""
    if cfg.name == "sgd":
        return {}
    if cfg.name == "momentum":
        return {"mu": param_axes}
    if cfg.name == "adamw":
        return {"mu": param_axes, "nu": param_axes}
    raise ValueError(cfg.name)


# ---------------------------------------------------------------------------
# update rules
# ---------------------------------------------------------------------------

def _global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def _maybe_clip(grads, clip: float):
    if not clip:
        return grads
    norm = _global_norm(grads)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def apply_updates(cfg: OptimizerConfig, grads, state: OptState, params,
                  step: jax.Array, lr: Optional[jax.Array] = None):
    """Returns (new_params, new_state). ``step`` is the global step counter.
    The whole update, clipping and schedule included, is traced under the
    ``lm.optimizer`` scope."""
    with scope("lm.optimizer"):
        return _apply_updates(cfg, grads, state, params, step, lr)


def _apply_updates(cfg: OptimizerConfig, grads, state: OptState, params,
                   step: jax.Array, lr: Optional[jax.Array]):
    if lr is None:
        lr = make_schedule(cfg)(step)
    grads = _maybe_clip(grads, cfg.grad_clip)

    if cfg.name == "sgd":
        def upd(p, g):
            p32 = p.astype(jnp.float32)
            if cfg.weight_decay:
                p32 = p32 * (1.0 - lr * cfg.weight_decay)
            return (p32 - lr * g.astype(jnp.float32)).astype(p.dtype)
        return jax.tree.map(upd, params, grads), state

    if cfg.name == "momentum":
        def upd(p, g, m):
            m32 = cfg.momentum * m.astype(jnp.float32) + g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if cfg.weight_decay:
                p32 = p32 * (1.0 - lr * cfg.weight_decay)
            return (p32 - lr * m32).astype(p.dtype), m32.astype(m.dtype)
        out = jax.tree.map(upd, params, grads, state["mu"])
        new_params = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": new_mu}

    if cfg.name == "adamw":
        t = step.astype(jnp.float32) + 1.0
        bc1 = 1.0 - cfg.beta1 ** t
        bc2 = 1.0 - cfg.beta2 ** t

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = cfg.beta1 * m.astype(jnp.float32) + (1 - cfg.beta1) * g32
            v32 = cfg.beta2 * v.astype(jnp.float32) + (1 - cfg.beta2) * g32 * g32
            mhat = m32 / bc1
            vhat = v32 / bc2
            p32 = p.astype(jnp.float32)
            if cfg.weight_decay:
                p32 = p32 * (1.0 - lr * cfg.weight_decay)
            p32 = p32 - lr * mhat / (jnp.sqrt(vhat) + cfg.eps)
            return p32.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)

        out = jax.tree.map(upd, params, grads, state["mu"], state["nu"])
        is_tup = lambda x: isinstance(x, tuple)
        new_params = jax.tree.map(lambda o: o[0], out, is_leaf=is_tup)
        new_mu = jax.tree.map(lambda o: o[1], out, is_leaf=is_tup)
        new_nu = jax.tree.map(lambda o: o[2], out, is_leaf=is_tup)
        return new_params, {"mu": new_mu, "nu": new_nu}

    raise ValueError(f"unknown optimizer {cfg.name!r}")
