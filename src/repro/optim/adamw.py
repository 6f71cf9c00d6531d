"""AdamW with global-norm clipping and warmup-cosine schedule (no optax in
this environment).  Optimizer moments are f32 regardless of param dtype;
under the production mesh the moment tree additionally gets ZeRO-1
sharding (see repro.parallel.sharding.zero1_specs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(1.0, cfg.warmup_steps)
    t = (step - cfg.warmup_steps) / jnp.maximum(
        1.0, cfg.total_steps - cfg.warmup_steps)
    t = jnp.clip(t, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init(params) -> Dict[str, Any]:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def update(cfg: AdamWConfig, grads, opt_state, params
           ) -> Tuple[Any, Dict[str, Any], Dict[str, jnp.ndarray]]:
    with jax.named_scope("adamw_update"):
        step = opt_state["step"] + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
        lr = schedule(cfg, step)
        b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m2 = cfg.b1 * m + (1 - cfg.b1) * g
            v2 = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mhat, vhat = m2 / b1c, v2 / b2c
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if p.ndim >= 2:  # decay matrices only
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m2, v2

        out = jax.tree.map(upd, grads, opt_state["m"], opt_state["v"], params)
        new_params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda o: o[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"m": new_m, "v": new_v, "step": step}, \
            {"grad_norm": gnorm, "lr": lr}
