"""Plain float32 reference of ``resnet101``: ResNet-101 in torchvision's
v1.5 form (stride on each block's 3x3 conv), written out from the
configuration with ``lax.conv_general_dilated`` at HIGHEST precision;
training-mode batch norm (batch mean and biased variance, eps 1e-5;
running mean and unbiased variance at momentum 0.1), the stem's max-pool,
global average pool, the linear head and mean softmax cross-entropy; and
AdamW (decoupled weight decay on matrices and kernels only, bias
correction, warmup then cosine schedule, global-norm clipping).

It imports nothing of the program under test.  Parameters and running
statistics come in as nested dicts: ``stem``/``stem_bn``,
``stages[i][j]`` with ``conv1``-``conv3``, ``bn1``-``bn3`` and, in a
stage's first block, ``proj``/``proj_bn``, then ``fc``; :func:`train`
refuses them unless every leaf has the shape that :func:`shapes` derives
from the configuration.  At batch 32 a
training step holds 3.8 GB of float32 temporaries (v5e compile), so the
whole batch is checked at once, with no recomputation.

``rounding="fp8"`` is the precision control: every conv operand, conv
output and their cotangents rounded to float8 e4m3 with a per-tensor
scale, accumulation in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
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


def conv(x, w, stride: int, pad: int, r):
    y = lax.conv_general_dilated(
        r(x), r(w), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    return r(y)


def batch_norm(x, p, s, eps: float = 1e-5, momentum: float = 0.1):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    n = x.shape[0] * x.shape[1] * x.shape[2]
    y = (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    new = {"mean": (1 - momentum) * s["mean"] + momentum * mean,
           "var": (1 - momentum) * s["var"] + momentum * var * n / (n - 1)}
    return y, jax.tree.map(lax.stop_gradient, new)


def block(p, s, x, stride: int, r):
    y, s1 = batch_norm(conv(x, p["conv1"]["w"], 1, 0, r), p["bn1"], s["bn1"])
    y, s2 = batch_norm(conv(jax.nn.relu(y), p["conv2"]["w"], stride, 1, r),
                       p["bn2"], s["bn2"])
    y, s3 = batch_norm(conv(jax.nn.relu(y), p["conv3"]["w"], 1, 0, r),
                       p["bn3"], s["bn3"])
    new = {"bn1": s1, "bn2": s2, "bn3": s3}
    if "proj" in p:
        x, new["proj_bn"] = batch_norm(
            conv(x, p["proj"]["w"], stride, 0, r), p["proj_bn"],
            s["proj_bn"])
    return jax.nn.relu(y + x), new


def forward(params, stats, images, cfg: Dict, rounding: Optional[str] = None):
    """Training-mode logits and the new running statistics, in float32."""
    r = ROUNDINGS[rounding]
    st = cfg["stem"]
    x, stem_bn = batch_norm(
        conv(images.astype(F32), params["stem"]["w"], st["stride"],
             st["pad"], r), params["stem_bn"], stats["stem_bn"])
    pool = st["pool"]
    x = lax.reduce_window(
        jax.nn.relu(x), -jnp.inf, lax.max, (1, pool["kernel"],
                                            pool["kernel"], 1),
        (1, pool["stride"], pool["stride"], 1),
        ((0, 0), (pool["pad"],) * 2, (pool["pad"],) * 2, (0, 0)))
    new = {"stem_bn": stem_bn, "stages": []}
    for i, (ps, ss) in enumerate(zip(params["stages"], stats["stages"])):
        new["stages"].append([])
        for j, (p, s) in enumerate(zip(ps, ss)):
            stride = 2 if i > 0 and j == 0 else 1
            x, s = block(p, s, x, stride, r)
            new["stages"][-1].append(s)
    feats = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(feats, params["fc"]["w"],
                     precision=lax.Precision.HIGHEST) + params["fc"]["b"]
    return logits, new


def loss(params, stats, images, labels, cfg, rounding=None):
    logits, stats = forward(params, stats, images, cfg, rounding)
    logp = jax.nn.log_softmax(logits, axis=1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -jnp.mean(picked), stats


def shapes(cfg: Dict):
    """The shape of every parameter and running statistic, from the
    configuration alone: ``(params, stats)`` as nested dicts of tuples."""
    st, exp = cfg["stem"], cfg["expansion"]

    def bn(c):
        return ({"scale": (c,), "bias": (c,)}, {"mean": (c,), "var": (c,)})

    def conv_bn(k, c_in, c_out):
        return {"w": (k, k, c_in, c_out)}, bn(c_out)

    params, stats = {"stages": []}, {"stages": []}
    params["stem"], (params["stem_bn"], stats["stem_bn"]) = conv_bn(
        st["kernel"], cfg["in_channels"], st["width"])
    c = st["width"]
    for i, (depth, width) in enumerate(zip(cfg["depths"], cfg["widths"])):
        params["stages"].append([])
        stats["stages"].append([])
        for j in range(depth):
            p, s = {}, {}
            for n, (k, c_in, c_out) in enumerate(
                    ((1, c, width), (3, width, width), (1, width, width * exp)),
                    start=1):
                p[f"conv{n}"], (p[f"bn{n}"], s[f"bn{n}"]) = conv_bn(
                    k, c_in, c_out)
            if j == 0:
                p["proj"], (p["proj_bn"], s["proj_bn"]) = conv_bn(
                    1, c, width * exp)
            params["stages"][-1].append(p)
            stats["stages"][-1].append(s)
            c = width * exp
    params["fc"] = {"w": (c, cfg["num_classes"]), "b": (cfg["num_classes"],)}
    return params, stats


def check_shapes(params, stats, cfg: Dict) -> None:
    """Raise ``ValueError`` unless ``params`` and ``stats`` have exactly
    the leaves and shapes of :func:`shapes`."""
    def as_tuple(x):
        return isinstance(x, tuple)

    for name, got, want in zip(("parameters", "running statistics"),
                               (params, stats), shapes(cfg)):
        got_shapes = jax.tree.map(lambda a: tuple(a.shape), got)
        if jax.tree.structure(got_shapes, is_leaf=as_tuple) != \
                jax.tree.structure(want, is_leaf=as_tuple) or \
                jax.tree.leaves(got_shapes, is_leaf=as_tuple) != \
                jax.tree.leaves(want, is_leaf=as_tuple):
            bad = [(jax.tree_util.keystr(k), g, w) for (k, g), w in zip(
                jax.tree_util.tree_leaves_with_path(got_shapes,
                                                    is_leaf=as_tuple),
                jax.tree.leaves(want, is_leaf=as_tuple)) if g != w]
            raise ValueError(f"{name} do not have the configuration's "
                             f"shapes: {bad[:5] or 'the trees differ'}")


def adamw(opt: Dict, grads, m, v, params, step):
    """One AdamW step on float32 leaves; ``step`` counts from 1.  Weight
    decay applies to leaves of two or more axes (kernels, the linear
    weight), not to batch-norm scales and shifts or the bias."""
    gs = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in gs))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    t = jnp.clip((step - opt["warmup_steps"]) / max(
        1, opt["total_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    lr = jnp.where(step < opt["warmup_steps"],
                   opt["lr"] * step / max(1, opt["warmup_steps"]),
                   opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                                * 0.5 * (1 + jnp.cos(jnp.pi * t))))
    b1c, b2c = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step

    def one(g, mi, vi, p):
        g = g * scale
        mi = opt["b1"] * mi + (1 - opt["b1"]) * g
        vi = opt["b2"] * vi + (1 - opt["b2"]) * jnp.square(g)
        delta = (mi / b1c) / (jnp.sqrt(vi / b2c) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, mi, vi, g

    out = jax.tree.map(one, grads, m, v, params)
    parts = [jax.tree.map(lambda o, k=k: o[k], out,
                          is_leaf=lambda o: isinstance(o, tuple))
             for k in range(4)]
    return tuple(parts)


def train(params0, stats0, batches: Sequence, opt: Dict, cfg: Dict,
          rounding: Optional[str] = None) -> Dict:
    """AdamW steps from ``params0`` and ``stats0``, one per ``(images,
    labels)`` batch: each step's loss, the per-leaf norms (leaves in
    ``jax.tree.leaves`` order) of the first gradient as the optimizer
    takes it (clipped), of the parameters' change, and the running
    statistics after the first step and after the last."""
    check_shapes(params0, stats0, cfg)
    grad = jax.value_and_grad(functools.partial(loss, cfg=cfg,
                                                rounding=rounding),
                              has_aux=True)

    @jax.jit
    def step(params, stats, m, v, images, labels, t):
        (value, stats), grads = grad(params, stats, images, labels)
        params, m, v, clipped = adamw(opt, grads, m, v, params, t)
        norms = jnp.stack([jnp.linalg.norm(g)
                           for g in jax.tree.leaves(clipped)])
        return params, stats, m, v, value, norms

    @jax.jit
    def start(params0):
        params = jax.tree.map(lambda p: p.astype(F32), params0)
        return params, jax.tree.map(jnp.zeros_like, params), \
            jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def change(params, params0):
        return jnp.stack([jnp.linalg.norm(p - p0.astype(F32)) for p, p0 in
                          zip(jax.tree.leaves(params),
                              jax.tree.leaves(params0))])

    (params, m, v), stats = start(params0), stats0
    losses, grad_norms, first_stats = [], None, None
    for t, (images, labels) in enumerate(batches, start=1):
        params, stats, m, v, value, norms = step(
            params, stats, m, v, images, labels, jnp.asarray(t, F32))
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = [float(g) for g in np.asarray(norms)]
            first_stats = jax.device_get(stats)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": [float(u) for u in
                             np.asarray(change(params, params0))],
            "first_stats": first_stats, "stats": jax.device_get(stats)}
