"""ConvNeXt (Liu et al. 2022, "A ConvNet for the 2020s", arXiv:2201.03545),
as ``facebookresearch/ConvNeXt``'s ``models/convnext.py`` builds it.

    stem        4x4 conv, stride 4 -> layer norm
    downsample  layer norm -> 2x2 conv, stride 2 (before stages 2-4)
    blocks      depthwise 7x7 conv (pad 3, groups = C, bias) -> layer norm
                -> 1x1 C -> 4C -> exact GELU -> 1x1 4C -> C -> layer scale
                gamma -> stochastic depth -> plus the block's input
    head        global mean pool -> layer norm -> linear -> softmax
                cross-entropy

Every conv runs through ``conv2d_layer(plan=)`` with its bias, its plan
resolved once per distinct geometry by ``plan_conv2d_layer``
(:func:`plan`); a depthwise conv is a grouped conv with ``groups = C``
(its HWIO kernel ``(k, k, 1, C)``), which the planner puts on ``direct``.
The reference's ``Linear`` layers of a block are the 1x1 convs here.
Parameters are float32 master weights; activations take the images'
dtype; layer norms compute in float32.

Stochastic depth is per sample, as timm's ``DropPath`` (a kept sample's
branch divided by the keep probability); the rate of block ``b`` of all
``n`` is ``drop_path * b / (n - 1)``.  A training step draws block
``b``'s mask with ``jax.random.bernoulli(fold_in(key, b), keep,
(batch,))`` from the key it is given; without a key no branch is
dropped.

A configuration is a dict: ``image_size``, ``in_channels``,
``num_classes``, ``depths`` and ``dims`` per stage, ``stem`` and
``downsample`` (``kernel``, ``stride``), ``kernel`` (the depthwise conv's),
``expansion``, ``ln_eps``, ``drop_path`` and ``layer_scale_init``.
Parameters are a nested dict: ``stem``/``stem_norm``, ``downs[i]`` with
``norm`` and ``conv`` (before stage ``i + 1``), ``stages[i][j]`` with
``dw``, ``norm``, ``pw1``, ``pw2`` and ``gamma``, then ``head_norm`` and
``fc``; plans have the parameters' shape for the convs.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import (conv2d_layer, gelu, layer_norm,
                                 plan_conv2d_layer)
from repro.models.resnet import cross_entropy

F32 = jnp.float32

# timm's trunc_normal_(std=.02), as the reference code initialises every
# conv and linear weight: a normal of std 0.02 cut at +-2 (absolute, so at
# 100 standard deviations).
INIT_STD = 0.02
INIT_CUT = 2.0


# ---------------------------------------------------------------- geometry

def blocks(cfg: Dict) -> Iterator[Tuple[int, int, int, int]]:
    """``(stage, block, channels, index)`` of every block, in order;
    ``index`` counts the blocks of all stages from 0."""
    index = 0
    for i, (depth, dim) in enumerate(zip(cfg["depths"], cfg["dims"])):
        for j in range(depth):
            yield i, j, dim, index
            index += 1


def drop_rates(cfg: Dict) -> List[float]:
    """Each block's stochastic-depth rate: ``linspace(0, drop_path, n)``."""
    n = sum(cfg["depths"])
    return [cfg["drop_path"] * b / max(n - 1, 1) for b in range(n)]


def convs(cfg: Dict, batch: int) -> List[Tuple]:
    """``(path, input shape, kernel shape, stride, pad)`` of every conv of
    the network on ``batch`` images, in order; ``path`` is its key path
    in the parameters.  A depthwise kernel is ``(k, k, 1, C)``."""
    st, down, k = cfg["stem"], cfg["downsample"], cfg["kernel"]
    size, dims = cfg["image_size"], cfg["dims"]
    out = [(("stem",), (batch, size, size, cfg["in_channels"]),
            (st["kernel"], st["kernel"], cfg["in_channels"], dims[0]),
            st["stride"], 0)]
    h = _out(size, st["kernel"], st["stride"], 0)
    for i, j, c, _ in blocks(cfg):
        if i > 0 and j == 0:
            out.append((("downs", i - 1, "conv"), (batch, h, h, dims[i - 1]),
                        (down["kernel"], down["kernel"], dims[i - 1], c),
                        down["stride"], 0))
            h = _out(h, down["kernel"], down["stride"], 0)
        path, wide = ("stages", i, j), c * cfg["expansion"]
        out += [(path + ("dw",), (batch, h, h, c), (k, k, 1, c), 1, k // 2),
                (path + ("pw1",), (batch, h, h, c), (1, 1, c, wide), 1, 0),
                (path + ("pw2",), (batch, h, h, wide), (1, 1, wide, c), 1, 0)]
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
    return {"stages": [[{} for _ in range(d)] for d in cfg["depths"]],
            "downs": [{} for _ in cfg["depths"][1:]]}


def _norm(c: int) -> Dict:
    return {"scale": jnp.ones(c, F32), "bias": jnp.zeros(c, F32)}


# ------------------------------------------------------------ construction

def init(key, cfg: Dict) -> Dict:
    """Parameters as the reference code initialises them: conv and linear
    weights normal with std 0.02 cut at +-2 (timm's ``trunc_normal_``),
    biases 0, layer norms scale 1 and shift 0, every layer scale
    ``layer_scale_init``."""
    params = _skeleton(cfg)
    layers = convs(cfg, 1)
    fc_shape = (cfg["dims"][-1], cfg["num_classes"])
    sizes = [math.prod(shape) for _, _, shape, _, _ in layers] + \
        [math.prod(fc_shape)]
    # One draw for every weight: a single generator call to compile.
    flat = jnp.clip(jax.random.normal(key, (sum(sizes),), F32) * INIT_STD,
                    -INIT_CUT, INIT_CUT)
    at = 0
    for (path, _, shape, _, _), size in zip(layers, sizes):
        _put(params, path, {"w": flat[at:at + size].reshape(shape),
                            "b": jnp.zeros(shape[3], F32)})
        at += size
    params["stem_norm"] = _norm(cfg["dims"][0])
    for i, c in enumerate(cfg["dims"][:-1]):
        params["downs"][i]["norm"] = _norm(c)
    for i, j, c, _ in blocks(cfg):
        params["stages"][i][j].update(
            norm=_norm(c), gamma=jnp.full(c, cfg["layer_scale_init"], F32))
    params["head_norm"] = _norm(cfg["dims"][-1])
    params["fc"] = {"w": flat[at:].reshape(fc_shape),
                    "b": jnp.zeros(cfg["num_classes"], F32)}
    return params


def plan(cfg: Dict, batch: int, dtype) -> Dict:
    """Every conv's ``ConvPlan`` on ``batch`` images, from one
    ``plan_conv2d_layer`` call per distinct geometry, in the parameters'
    shape; each block's stochastic-depth rate under ``drop``, and the
    layer norms' epsilon under ``ln_eps``."""
    plans, resolved = _skeleton(cfg), {}
    for path, x_shape, k_shape, stride, pad in convs(cfg, batch):
        geometry = (x_shape, k_shape, stride, pad)
        if geometry not in resolved:
            w = jax.ShapeDtypeStruct(k_shape, dtype)
            resolved[geometry] = plan_conv2d_layer(
                {"w": w}, x_shape, stride=stride, padding=_padding(pad),
                dtype=dtype)
        _put(plans, path, resolved[geometry])
    rates = drop_rates(cfg)
    for i, j, _, b in blocks(cfg):
        plans["stages"][i][j]["drop"] = rates[b]
    plans["ln_eps"] = cfg["ln_eps"]
    return plans


# ----------------------------------------------------------------- forward

def conv(p: Dict, x: jax.Array, plan) -> jax.Array:
    """A conv executing its plan, with its bias (padding symmetric)."""
    spec = plan.spec
    pad = (spec.i_h - x.shape[1]) // 2
    return conv2d_layer(p, x, stride=spec.s_h, padding=_padding(pad),
                        plan=plan)


def depthwise(p: Dict, x: jax.Array, plan) -> jax.Array:
    """A block's depthwise conv, inside scope ``depthwise``."""
    with jax.named_scope("depthwise"):
        return conv(p, x, plan)


def pointwise(p: Dict, x: jax.Array, plan) -> jax.Array:
    """A 1x1 conv, inside scope ``pointwise``."""
    with jax.named_scope("pointwise"):
        return conv(p, x, plan)


def branch_scale(gamma: jax.Array, rate: float, key, batch: int):
    """Layer scale times each sample's stochastic-depth factor (0, or 1
    over the keep probability), ``(batch, C)`` in float32; ``gamma``
    alone without a key."""
    if key is None:
        return gamma[None, :]
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, (batch,))
    return gamma[None, :] * (mask.astype(F32) / keep)[:, None]


def block(p: Dict, x: jax.Array, plans: Dict, eps: float, key):
    """One block's output."""
    y = depthwise(p["dw"], x, plans["dw"])
    y = layer_norm(y, p["norm"], eps)
    y = gelu(pointwise(p["pw1"], y, plans["pw1"]))
    y = pointwise(p["pw2"], y, plans["pw2"])
    scale = branch_scale(p["gamma"], plans["drop"], key, x.shape[0])
    return x + (y.astype(F32) * scale[:, None, None, :]).astype(x.dtype)


def forward(params: Dict, images: jax.Array, plans: Dict, key=None):
    """Logits (float32); with ``key``, stochastic depth as in training."""
    eps = plans["ln_eps"]
    x = layer_norm(conv(params["stem"], images, plans["stem"]),
                   params["stem_norm"], eps)
    b = 0
    for i, (ps, pl) in enumerate(zip(params["stages"], plans["stages"])):
        if i > 0:
            down = params["downs"][i - 1]
            x = conv(down["conv"], layer_norm(x, down["norm"], eps),
                     plans["downs"][i - 1]["conv"])
        for p, bp in zip(ps, pl):
            x = block(p, x, bp, eps,
                      None if key is None else jax.random.fold_in(key, b))
            b += 1
    with jax.named_scope("head"):
        feats = layer_norm(jnp.mean(x.astype(F32), axis=(1, 2)),
                           params["head_norm"], eps)
        return jnp.dot(feats, params["fc"]["w"],
                       precision=lax.Precision.HIGHEST) + params["fc"]["b"]


def loss(params: Dict, images: jax.Array, labels: jax.Array, plans: Dict,
         key=None) -> jax.Array:
    """The mean cross-entropy of a forward (in training with ``key``)."""
    return cross_entropy(forward(params, images, plans, key), labels)


def train_step(plans: Dict, opt_cfg):
    """``step(params, opt_state, images, labels, key) -> (params,
    opt_state, loss)``: the loss's value and gradient through the
    network's convs, stochastic depth drawn from ``key`` folded with the
    optimizer's step count (so each step draws anew), then
    ``repro.optim.adamw.update``."""
    from repro.optim import adamw
    grad = jax.value_and_grad(functools.partial(loss, plans=plans))

    def step(params, opt_state, images, labels, key):
        value, grads = grad(params, images, labels,
                            key=jax.random.fold_in(key, opt_state["step"]))
        params, opt_state, _ = adamw.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, value
    return step
