"""ResNet with bottleneck blocks (He et al. 2016, arXiv:1512.03385), in the
torchvision "v1.5" form: the stride of a block sits on its 3x3 conv.

    stem    7x7 conv, stride 2, pad 3 -> BN -> ReLU -> 3x3 max-pool, stride 2
    stages  blocks of 1x1 -> BN -> ReLU -> 3x3 (stride s) -> BN -> ReLU
            -> 1x1 -> BN, plus the shortcut, then ReLU; the first block of a
            stage projects its shortcut (1x1 conv at stride s, then BN)
    head    global average pool -> linear -> softmax cross-entropy

Every conv runs through ``conv2d_layer(plan=)`` with no bias, its plan
resolved once per distinct geometry by ``plan_conv2d_layer`` (:func:`plan`).
Parameters are float32 master weights; activations take the images'
dtype; batch norm computes in float32 and keeps torchvision's running
statistics.

A configuration is a dict: ``image_size``, ``in_channels``,
``num_classes``, ``stem`` (``width``, ``kernel``, ``stride``, ``pad`` and
``pool`` with ``kernel``, ``stride``, ``pad``), ``depths`` and ``widths``
per stage, ``expansion``.  Parameters, running statistics and plans are
nested dicts of the same shape: ``stem``/``stem_bn``, ``stages[i][j]``
with ``conv1``-``conv3``, ``bn1``-``bn3`` and, in a stage's first block,
``proj``/``proj_bn``, then ``fc``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import batch_norm, conv2d_layer, plan_conv2d_layer

F32 = jnp.float32


# ---------------------------------------------------------------- geometry

def blocks(cfg: Dict) -> Iterator[Tuple[int, int, int, int, int]]:
    """``(stage, block, in_channels, width, stride)`` of every bottleneck
    block, in order."""
    c = cfg["stem"]["width"]
    for i, (depth, width) in enumerate(zip(cfg["depths"], cfg["widths"])):
        for j in range(depth):
            yield i, j, c, width, (1 if i == 0 or j > 0 else 2)
            c = width * cfg["expansion"]


def convs(cfg: Dict, batch: int) -> List[Tuple]:
    """``(path, input shape, kernel shape, stride, pad)`` of every conv of
    the network on ``batch`` images, in order; ``path`` is its key path
    in the parameters."""
    st, size = cfg["stem"], cfg["image_size"]
    out = [(("stem",), (batch, size, size, cfg["in_channels"]),
            (st["kernel"], st["kernel"], cfg["in_channels"], st["width"]),
            st["stride"], st["pad"])]
    h = _out(_out(size, st["kernel"], st["stride"], st["pad"]),
             st["pool"]["kernel"], st["pool"]["stride"], st["pool"]["pad"])
    for i, j, c, width, s in blocks(cfg):
        path, wide = ("stages", i, j), width * cfg["expansion"]
        h2 = _out(h, 3, s, 1)
        out += [(path + ("conv1",), (batch, h, h, c), (1, 1, c, width), 1, 0),
                (path + ("conv2",), (batch, h, h, width),
                 (3, 3, width, width), s, 1),
                (path + ("conv3",), (batch, h2, h2, width),
                 (1, 1, width, wide), 1, 0)]
        if j == 0:
            out.append((path + ("proj",), (batch, h, h, c), (1, 1, c, wide),
                        s, 0))
        h = h2
    return out


def _out(size: int, k: int, s: int, p: int) -> int:
    return (size + 2 * p - k) // s + 1


def _padding(pad: int):
    return "VALID" if pad == 0 else pad


def _put(tree: Dict, path: Tuple, value) -> None:
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = value


def _skeleton(cfg: Dict) -> Dict:
    return {"stages": [[{} for _ in range(d)] for d in cfg["depths"]]}


def _bn_path(path: Tuple) -> Tuple:
    """The batch norm after the conv at ``path``: ``stem_bn``, ``bn1``-
    ``bn3`` or ``proj_bn``."""
    last = path[-1]
    return path[:-1] + ((last + "_bn",) if last in ("stem", "proj")
                        else ("bn" + last[-1],))


# ------------------------------------------------------------ construction

def init(key, cfg: Dict) -> Tuple[Dict, Dict]:
    """Parameters and running statistics, as torchvision initialises them:
    conv kernels Kaiming-normal (fan out, ReLU gain), batch-norm scale 1
    and shift 0, running mean 0 and variance 1, the linear layer's weight
    and bias uniform in +-1/sqrt(fan in)."""
    params, stats = _skeleton(cfg), _skeleton(cfg)
    layers = convs(cfg, 1)
    sizes = [math.prod(shape) for _, _, shape, _, _ in layers]
    # One draw for every kernel: a single generator call to compile.
    flat = jax.random.normal(jax.random.fold_in(key, 0), (sum(sizes),), F32)
    at = 0
    for (path, _, shape, _, _), size in zip(layers, sizes):
        std = (2.0 / (shape[0] * shape[1] * shape[3])) ** 0.5
        _put(params, path, {"w": flat[at:at + size].reshape(shape) * std})
        at += size
        bn = _bn_path(path)
        _put(params, bn, {"scale": jnp.ones(shape[3], F32),
                          "bias": jnp.zeros(shape[3], F32)})
        _put(stats, bn, {"mean": jnp.zeros(shape[3], F32),
                         "var": jnp.ones(shape[3], F32)})
    d_in = cfg["widths"][-1] * cfg["expansion"]
    bound = d_in ** -0.5
    kw, kb = jax.random.split(jax.random.fold_in(key, 1))
    params["fc"] = {
        "w": jax.random.uniform(kw, (d_in, cfg["num_classes"]), F32,
                                -bound, bound),
        "b": jax.random.uniform(kb, (cfg["num_classes"],), F32, -bound,
                                bound)}
    return params, stats


def plan(cfg: Dict, batch: int, dtype) -> Dict:
    """Every conv's ``ConvPlan`` on ``batch`` images, from one
    ``plan_conv2d_layer`` call per distinct geometry, in the parameters'
    shape; and the stem's max-pool window, ``(kernel, stride, pad)``,
    under ``pool``."""
    pool = cfg["stem"]["pool"]
    plans, resolved = _skeleton(cfg), {}
    plans["pool"] = (pool["kernel"], pool["stride"], pool["pad"])
    for path, x_shape, k_shape, stride, pad in convs(cfg, batch):
        geometry = (x_shape, k_shape, stride, pad)
        if geometry not in resolved:
            w = jax.ShapeDtypeStruct(k_shape, dtype)
            resolved[geometry] = plan_conv2d_layer(
                {"w": w}, x_shape, stride=stride, padding=_padding(pad),
                dtype=dtype)
        _put(plans, path, resolved[geometry])
    return plans


# ----------------------------------------------------------------- forward

def conv(p: Dict, x: jax.Array, plan) -> jax.Array:
    """A conv executing its plan; a 1x1 conv inside scope ``pointwise``."""
    spec = plan.spec
    if spec.k_h == 1 and spec.k_w == 1:
        with jax.named_scope("pointwise"):
            return conv2d_layer(p, x, stride=spec.s_h, padding="VALID",
                                plan=plan)
    # The plan's geometry is after padding, which is symmetric.
    pad = (spec.i_h - x.shape[1]) // 2
    return conv2d_layer(p, x, stride=spec.s_h, padding=_padding(pad),
                        plan=plan)


def shortcut(p: Dict, s: Dict, x: jax.Array, plans: Dict):
    """The block's input, or its projection where the block has one, and
    the projection's new running statistics."""
    if "proj" not in p:
        return x, s
    y, proj_bn = batch_norm(conv(p["proj"], x, plans["proj"]), p["proj_bn"],
                            s["proj_bn"])
    return y, dict(s, proj_bn=proj_bn)


def bottleneck(p: Dict, s: Dict, x: jax.Array, plans: Dict):
    """One block's output and its new running statistics."""
    new, y = {}, x
    for n in ("1", "2", "3"):
        y = conv(p["conv" + n], y, plans["conv" + n])
        y, new["bn" + n] = batch_norm(y, p["bn" + n], s["bn" + n])
        if n != "3":
            y = jax.nn.relu(y)
    short, s = shortcut(p, s, x, plans)
    return jax.nn.relu(y + short), dict(s, **new)


def max_pool(x: jax.Array, k: int, stride: int, pad: int) -> jax.Array:
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1),
        (1, stride, stride, 1), ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def forward(params: Dict, stats: Dict, images: jax.Array, plans: Dict):
    """Training-mode logits (float32) and the new running statistics."""
    x, stem_bn = batch_norm(conv(params["stem"], images, plans["stem"]),
                            params["stem_bn"], stats["stem_bn"])
    x = max_pool(jax.nn.relu(x), *plans["pool"])
    new = {"stem_bn": stem_bn, "stages": []}
    for ps, ss, pl in zip(params["stages"], stats["stages"],
                          plans["stages"]):
        new["stages"].append([])
        for p, s, bp in zip(ps, ss, pl):
            x, s = bottleneck(p, s, x, bp)
            new["stages"][-1].append(s)
    with jax.named_scope("head"):
        feats = jnp.mean(x.astype(F32), axis=(1, 2))
        logits = jnp.dot(feats, params["fc"]["w"],
                         precision=lax.Precision.HIGHEST) + params["fc"]["b"]
    return logits, new


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy over integer labels."""
    with jax.named_scope("head"):
        picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)


def loss(params: Dict, stats: Dict, images: jax.Array, labels: jax.Array,
         plans: Dict):
    """The mean cross-entropy of a training-mode forward, and the new
    running statistics."""
    logits, stats = forward(params, stats, images, plans)
    return cross_entropy(logits, labels), stats


def train_step(plans: Dict, opt_cfg):
    """``step(params, stats, opt_state, images, labels) -> (params, stats,
    opt_state, loss)``: the loss's value and gradient through the
    network's convs, then ``repro.optim.adamw.update``."""
    from repro.optim import adamw
    grad = jax.value_and_grad(functools.partial(loss, plans=plans),
                              has_aux=True)

    def step(params, stats, opt_state, images, labels):
        (value, stats), grads = grad(params, stats, images, labels)
        params, opt_state, _ = adamw.update(opt_cfg, grads, opt_state, params)
        return params, stats, opt_state, value
    return step
