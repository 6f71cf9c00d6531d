"""Plain float32 reference of ``convnext_b``: ConvNeXt as
``facebookresearch/ConvNeXt``'s ``models/convnext.py`` builds it, written
out from the configuration with ``lax.conv_general_dilated`` at HIGHEST
precision: the 4x4/4 stem and its layer norm, a layer norm and a 2x2/2
conv before stages 2-4, blocks of a depthwise 7x7 conv (pad 3,
``feature_group_count`` = C, bias), layer norm (eps 1e-6), linear C -> 4C,
exact GELU, linear 4C -> C, layer scale and per-sample stochastic depth
(timm's ``DropPath``: a kept sample's branch over the keep probability)
around a residual add; global mean pool, layer norm, the linear head and
mean softmax cross-entropy; and AdamW (decoupled weight decay on matrices
and kernels only, bias correction, warmup then cosine schedule,
global-norm clipping).

It imports nothing of the program under test.  Block ``b`` of a step
with key ``k`` keeps sample ``n`` where ``jax.random.bernoulli(
fold_in(k, b), 1 - rate_b, (batch,))[n]``, at the rates
``linspace(0, drop_path, blocks)``; step ``t`` (from 1) of
:func:`train` draws with ``fold_in(key, t - 1)``.  Parameters come in as
a nested dict: ``stem``/``stem_norm``, ``downs[i]`` (``norm``, ``conv``),
``stages[i][j]`` (``dw``, ``norm``, ``pw1``, ``pw2`` as HWIO 1x1 kernels,
``gamma``), ``head_norm``, ``fc``; :func:`train` refuses them unless every
leaf has the shape that :func:`shapes` derives from the configuration.

A training step's gradient is the mean of the gradients of ``BLOCK``-image
blocks of the batch, one block at a time: at batch 32 the whole batch at
once would hold about 7.4 GB of float32 activations for the backward.

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

# Images a block of the batch holds in the backward.
BLOCK = 8


# float8 e4m3's largest finite value.
E4M3_MAX = 448.0


def round_e4m3(q):
    """Float32 ``q``, within +-448, rounded to the nearest float8 e4m3
    value (3 mantissa bits, spacing 2**-9 below the smallest normal 2**-6,
    ties to even), as a cast to ``float8_e4m3fn`` and back rounds it.  It
    is written out in float32 arithmetic, the spacing built from ``q``'s
    exponent bits, so it is exact and the same on every backend: it needs
    no float8 support of the device and no compiler pass can fold it
    away."""
    biased = lax.shift_right_logical(
        lax.bitcast_convert_type(jnp.abs(q), jnp.int32), 23)
    step = lax.bitcast_convert_type(
        lax.shift_left(jnp.maximum(biased, 121) - 3, 23), F32)
    return jnp.round(q / step) * step


def _fp8(x):
    """``x`` in float8 e4m3 under a per-tensor scale that maps its largest
    magnitude to 448; a tensor whose largest magnitude is not a normal
    float32 keeps scale 1."""
    amax = jnp.max(jnp.abs(x))
    amax = jnp.where(amax > jnp.finfo(F32).tiny, amax, 1.0)
    q = jnp.clip(x / amax * E4M3_MAX, -E4M3_MAX, E4M3_MAX)
    return round_e4m3(q) * (amax / E4M3_MAX)


@jax.custom_vjp
def round_fp8(x):
    return _fp8(x)


def _round_fwd(x):
    return _fp8(x), None


def _round_bwd(_, g):
    return (_fp8(g),)


round_fp8.defvjp(_round_fwd, _round_bwd)

ROUNDINGS = {None: lambda x: x, "fp8": round_fp8}


def conv(x, p, stride: int, pad: int, r):
    """NHWC x HWIO plus bias; grouped where the kernel's ``I`` is less
    than the input's channels."""
    w = p["w"]
    y = lax.conv_general_dilated(
        r(x), r(w), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1] // w.shape[2],
        precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    return r(y) + p["b"]


def layer_norm(x, p, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rates(cfg: Dict):
    """``linspace(0, drop_path, blocks)``, each rate as ``drop_path * b /
    (blocks - 1)``."""
    n = sum(cfg["depths"])
    return [cfg["drop_path"] * b / max(n - 1, 1) for b in range(n)]


def keep_factors(key, cfg: Dict, batch: int):
    """Each block's per-sample stochastic-depth factor, ``(blocks,
    batch)``: 0 for a dropped sample, 1 / keep for a kept one."""
    out = []
    for b, rate in enumerate(rates(cfg)):
        keep = 1.0 - rate
        mask = jax.random.bernoulli(jax.random.fold_in(key, b), keep,
                                    (batch,))
        out.append(mask.astype(F32) / keep)
    return jnp.stack(out)


def forward(params, images, factors, cfg: Dict,
            rounding: Optional[str] = None):
    """Logits in float32; ``factors`` as :func:`keep_factors` gives them
    for these images."""
    r = ROUNDINGS[rounding]
    eps, k = cfg["ln_eps"], cfg["kernel"]
    st, down = cfg["stem"], cfg["downsample"]
    x = layer_norm(conv(images.astype(F32), params["stem"], st["stride"], 0,
                        r), params["stem_norm"], eps)
    b = 0
    for i, stage in enumerate(params["stages"]):
        if i > 0:
            d = params["downs"][i - 1]
            x = conv(layer_norm(x, d["norm"], eps), d["conv"],
                     down["stride"], 0, r)
        for p in stage:
            y = layer_norm(conv(x, p["dw"], 1, k // 2, r), p["norm"], eps)
            y = jax.nn.gelu(conv(y, p["pw1"], 1, 0, r), approximate=False)
            y = conv(y, p["pw2"], 1, 0, r)
            x = x + y * p["gamma"] * factors[b][:, None, None, None]
            b += 1
    feats = layer_norm(jnp.mean(x, axis=(1, 2)), params["head_norm"], eps)
    return jnp.dot(feats, params["fc"]["w"],
                   precision=lax.Precision.HIGHEST) + params["fc"]["b"]


def loss(params, images, labels, factors, cfg, rounding=None):
    logp = jax.nn.log_softmax(forward(params, images, factors, cfg,
                                      rounding), axis=1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def shapes(cfg: Dict):
    """The shape of every parameter, from the configuration alone, as a
    nested dict of tuples."""
    dims, k, exp = cfg["dims"], cfg["kernel"], cfg["expansion"]
    st, down = cfg["stem"]["kernel"], cfg["downsample"]["kernel"]

    def norm(c):
        return {"scale": (c,), "bias": (c,)}

    def conv_p(kh, c_in, c_out):
        return {"w": (kh, kh, c_in, c_out), "b": (c_out,)}

    params = {"stem": conv_p(st, cfg["in_channels"], dims[0]),
              "stem_norm": norm(dims[0]),
              "downs": [{"norm": norm(c), "conv": conv_p(down, c, c2)}
                        for c, c2 in zip(dims[:-1], dims[1:])],
              "stages": [[{"dw": conv_p(k, 1, c), "norm": norm(c),
                           "pw1": conv_p(1, c, c * exp),
                           "pw2": conv_p(1, c * exp, c), "gamma": (c,)}
                          for _ in range(depth)]
                         for depth, c in zip(cfg["depths"], dims)],
              "head_norm": norm(dims[-1]),
              "fc": {"w": (dims[-1], cfg["num_classes"]),
                     "b": (cfg["num_classes"],)}}
    return params


def check_shapes(params, cfg: Dict) -> None:
    """Raise ``ValueError`` unless ``params`` has exactly the leaves and
    shapes of :func:`shapes`."""
    def as_tuple(x):
        return isinstance(x, tuple)

    want = shapes(cfg)
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if jax.tree.structure(got, is_leaf=as_tuple) != \
            jax.tree.structure(want, is_leaf=as_tuple) or \
            jax.tree.leaves(got, is_leaf=as_tuple) != \
            jax.tree.leaves(want, is_leaf=as_tuple):
        bad = [(jax.tree_util.keystr(k), g, w) for (k, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got, is_leaf=as_tuple),
            jax.tree.leaves(want, is_leaf=as_tuple)) if g != w]
        raise ValueError("parameters do not have the configuration's "
                         f"shapes: {bad[:5] or 'the trees differ'}")


def adamw(opt: Dict, grads, m, v, params, step):
    """One AdamW step on float32 leaves; ``step`` counts from 1.  Weight
    decay applies to leaves of two or more axes (kernels, the linear
    weight), not to norms, layer scales or biases."""
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


def value_and_grad(params, images, labels, factors, cfg, rounding=None):
    """The batch's mean loss and its gradient, as the mean over blocks of
    ``BLOCK`` images (the whole batch where it is no larger), one block
    at a time."""
    n = images.shape[0]
    block = BLOCK if n % BLOCK == 0 else n
    grad = jax.value_and_grad(functools.partial(loss, cfg=cfg,
                                                rounding=rounding))

    def one(carry, xs):
        x, y, f = xs
        value, g = grad(params, x, y, f)
        return jax.tree.map(jnp.add, carry, (value, g)), None

    split = lambda a, axis=0: jnp.moveaxis(  # noqa: E731
        a.reshape(a.shape[:axis] + (n // block, block) + a.shape[axis + 1:]),
        axis, 0)
    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
    (value, grads), _ = lax.scan(
        one, zero, (split(images), split(labels), split(factors, 1)))
    k = block / n
    return value * k, jax.tree.map(lambda g: g * k, grads)


def train(params0, batches: Sequence, opt: Dict, cfg: Dict, key,
          rounding: Optional[str] = None) -> Dict:
    """AdamW steps from ``params0``, one per ``(images, labels)`` batch,
    step ``t`` (from 1) with stochastic depth from ``fold_in(key, t -
    1)``: each step's loss, and the per-leaf norms (leaves in
    ``jax.tree.leaves`` order) of the first gradient as the optimizer
    takes it (clipped) and of the parameters' change."""
    check_shapes(params0, cfg)

    # The key is an argument, not a constant of the program, so that one
    # compiled step serves every seed.
    @jax.jit
    def step(params, m, v, images, labels, t, key):
        factors = keep_factors(jax.random.fold_in(key, t - 1), cfg,
                               images.shape[0])
        value, grads = value_and_grad(params, images, labels, factors, cfg,
                                      rounding)
        params, m, v, clipped = adamw(opt, grads, m, v, params,
                                      t.astype(F32))
        norms = jnp.stack([jnp.linalg.norm(g)
                           for g in jax.tree.leaves(clipped)])
        return params, m, v, value, norms

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

    params, m, v = start(params0)
    losses, grad_norms = [], None
    for t, (images, labels) in enumerate(batches, start=1):
        params, m, v, value, norms = step(params, m, v, images, labels,
                                          jnp.asarray(t, jnp.int32), key)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = [float(g) for g in np.asarray(norms)]
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": [float(u) for u in
                             np.asarray(change(params, params0))]}
