"""Plain float32 reference of ``resnet101_t3``: each stage a chain of
``lax.conv_general_dilated`` at HIGHEST precision, each conv followed by
ReLU; the training loss, its gradient, and AdamW (decoupled weight decay,
bias correction, warmup then cosine schedule, global-norm clipping).

It imports nothing of the program under test.  ``rounding="fp8"`` is the
precision control: every conv operand, activation and cotangent rounded
to float8 e4m3 with a per-tensor scale, accumulation in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


@jax.custom_vjp
def round_fp8(x):
    return _fp8(x)


def _round_fwd(x):
    return _fp8(x), None


def _round_bwd(_, g):
    return (_fp8(g),)


round_fp8.defvjp(_round_fwd, _round_bwd)

ROUNDINGS = {None: lambda x: x, "fp8": round_fp8}


def conv(x, w, stage: Dict):
    p, s = stage["pad"], stage["stride"]
    return lax.conv_general_dilated(
        x, w, (s, s), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST, preferred_element_type=F32)


def forward(params: Sequence, xs: Sequence, stages: Sequence[Dict],
            rounding: Optional[str] = None) -> List:
    """Each stage's output from its own input, in float32."""
    r = ROUNDINGS[rounding]
    outs, i = [], 0
    for st, x in zip(stages, xs):
        x = r(x.astype(F32))
        for _ in range(st["count"]):
            x = r(jax.nn.relu(conv(x, r(params[i].astype(F32)), st)))
            i += 1
        outs.append(x)
    return outs


def loss(params, xs, targets, stages, rounding=None):
    outs = forward(params, xs, stages, rounding)
    return sum(jnp.mean(jnp.square(o - t.astype(F32)))
               for o, t in zip(outs, targets))


def adamw(opt: Dict, grads, m, v, params, step: int):
    """One AdamW step on float32 leaves; ``step`` counts from 1."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    if step < opt["warmup_steps"]:
        lr = opt["lr"] * step / max(1, opt["warmup_steps"])
    else:
        t = (step - opt["warmup_steps"]) / max(
            1, opt["total_steps"] - opt["warmup_steps"])
        t = min(max(t, 0.0), 1.0)
        lr = opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) *
                          0.5 * (1 + jnp.cos(jnp.pi * t)))
    b1c, b2c = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step
    out_p, out_m, out_v, clipped = [], [], [], []
    for g, mi, vi, p in zip(grads, m, v, params):
        g = g * scale
        mi = opt["b1"] * mi + (1 - opt["b1"]) * g
        vi = opt["b2"] * vi + (1 - opt["b2"]) * jnp.square(g)
        delta = (mi / b1c) / (jnp.sqrt(vi / b2c) + opt["eps"])
        delta = delta + opt["weight_decay"] * p
        out_p.append(p - lr * delta)
        out_m.append(mi)
        out_v.append(vi)
        clipped.append(g)
    return out_p, out_m, out_v, clipped


@functools.partial(jax.jit, static_argnames=("stages", "rounding"))
def _grad(params, xs, targets, stages, rounding):
    return jax.value_and_grad(loss)(params, xs, targets,
                                    [dict(s) for s in stages], rounding)


def train(params0: Sequence, batches: Sequence, opt: Dict,
          stages: Sequence[Dict], rounding: Optional[str] = None) -> Dict:
    """AdamW steps from ``params0``, one per ``(xs, targets)`` batch: each
    step's loss, the per-leaf norms of the first gradient as the
    optimizer takes it (clipped), and of the parameters' change."""
    frozen = tuple(tuple(sorted(s.items())) for s in stages)
    params = [p.astype(F32) for p in params0]
    m = [jnp.zeros_like(p) for p in params]
    v = [jnp.zeros_like(p) for p in params]
    losses, grad_norms = [], None
    for step, (xs, targets) in enumerate(batches, start=1):
        value, grads = _grad(params, xs, targets, frozen, rounding)
        params, m, v, clipped = adamw(opt, grads, m, v, params, step)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = [float(jnp.linalg.norm(g)) for g in clipped]
    update_norms = [float(jnp.linalg.norm(p - p0.astype(F32)))
                    for p, p0 in zip(params, params0)]
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
