"""The ``convnext`` system: a configuration's whole ConvNeXt built by the
program's ``repro.models.convnext`` (every conv, the depthwise ones
included, through ``conv2d_layer(plan=)``, its plan resolved once per
geometry by the program's default policy; layer norms, GELU, layer scale,
stochastic depth, the head), driven as a closed loop of AdamW training
steps (traffic ``work`` ``train``).

Weights, images and labels are made on the device from the seed, in one
jitted call each; every layer scale is drawn uniform in [0.1, 1) in
place of the published 1e-6, so that each block reaches the logits (the
configuration's ``assumed``).  Stochastic depth draws from one key of the
seed, folded with the step count inside the step.  A run compares, with
the reference given the same key, the first gradient's leaf norms
(``train.grad``), the parameters' change over the checked steps
(``train.update``) and the loss of each checked step (``train.loss``),
as every training cell does (``compare.train_checks``).
"""
from __future__ import annotations

import copy
import functools
import json
import math
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp

from chipbench import bench, compare, faults, yardstick
from chipbench.control import CONTROL
from chipbench.systems.conv_chain import _closed_loop, leaf_norms
from chipbench.systems.resnet import change_norms, make_batches
from repro.models import convnext as model

F32 = jnp.float32

# The layer scales a run draws, uniform in [low, high).
GAMMA_RANGE = (0.1, 1.0)


def _flops(x, k, stride: int, pad: int) -> int:
    """Multiply-adds of one conv's forward, as two operations; a grouped
    kernel's ``I`` is the channels each output reads."""
    o_h = (x[1] + 2 * pad - k[0]) // stride + 1
    o_w = (x[2] + 2 * pad - k[1]) // stride + 1
    return 2 * x[0] * o_h * o_w * k[0] * k[1] * k[2] * k[3]


def forward_flops(cfg: Dict, batch: int) -> int:
    """Every conv's forward operations and the linear head's."""
    return sum(_flops(x, k, s, p) for _, x, k, s, p in
               model.convs(cfg, batch)) + \
        2 * batch * cfg["dims"][-1] * cfg["num_classes"]


def train_flops(cfg: Dict, batch: int) -> int:
    """The yardstick's training operations: forward, kernel gradients and
    input gradients of every conv (groups counted) and of the linear head,
    but no input gradient for the stem, whose input is the images.
    Norms, GELU, layer scale and adds are not counted."""
    _, x, k, s, p = model.convs(cfg, batch)[0]
    return 3 * forward_flops(cfg, batch) - _flops(x, k, s, p)


def pointwise_convs(cfg: Dict, batch: int) -> List[yardstick.Conv]:
    """The 1x1 convs (each block's two)."""
    return [yardstick.Conv(batch, x[1], x[2], x[3], 1, 1, k[3])
            for _, x, k, _, _ in model.convs(cfg, batch)
            if k[0] == k[1] == 1]


def depthwise_work(cfg: Dict, batch: int, itemsize: int) -> Dict[str, int]:
    """The step's depthwise convs: ``bytes``, the input, kernel and output
    of the forward, the input gradient and the kernel gradient, each
    moved once in ``itemsize``; ``flops``, three times the forward's."""
    moved = flops = 0
    for path, x, k, s, p in model.convs(cfg, batch):
        if path[-1] == "dw":
            o = (x[0], (x[1] + 2 * p - k[0]) // s + 1,
                 (x[2] + 2 * p - k[1]) // s + 1, k[3])
            # forward x, w -> y; input gradient dy, w -> dx; kernel
            # gradient x, dy -> dw.
            moved += 3 * (math.prod(x) + math.prod(o) + math.prod(k))
            flops += 3 * _flops(x, k, s, p)
    return {"bytes": moved * itemsize, "flops": flops}


@functools.partial(jax.jit, static_argnums=1)
def _init(key, cfg_json: str):
    cfg = json.loads(cfg_json)
    params = model.init(jax.random.fold_in(key, 0), cfg)
    for b, (i, j, c, _) in enumerate(model.blocks(cfg)):
        params["stages"][i][j]["gamma"] = jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, 1), b), (c,), F32,
            *GAMMA_RANGE)
    return params


def init_state(key, cfg: Dict):
    """Parameters, made on the device by one program per configuration,
    with the layer scales drawn in ``GAMMA_RANGE``."""
    return _init(key, json.dumps(cfg, sort_keys=True))


def build_train_step(cfg, plans, opt_cfg):
    return jax.jit(model.train_step(plans, opt_cfg), donate_argnums=(0, 1))


def checked(prog: Dict, want: Dict, limits: Dict) -> Dict[str, float]:
    """The numbers of ``compare.train_checks`` that the configuration
    limits."""
    values = compare.train_checks(prog, want)
    return {k: v for k, v in values.items() if k in limits}


def _plan_notes(cfg, plans) -> List[str]:
    seen, notes = set(), []
    for path, x, k, s, _ in model.convs(cfg, 1):
        key = (x[1:], k, s)
        if key not in seen:
            seen.add(key)
            plan = plans
            for part in path:
                plan = plan[part]
            notes.append(f"[plan] {k[0]}x{k[1]}/{s} {x[1]}px {x[3]}->{k[3]}"
                         f" g{plan.spec.groups}: {plan.algorithm}")
    return notes


def run(cell: bench.Cell, seed: int, seconds: float,
        window: bench.Window) -> bench.Run:
    from repro.optim import adamw
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "train":
        raise ValueError(f"convnext has no work {tr['work']!r}")
    dtype, batch = cfg["dtype"], tr["batch"]
    held, n_checked, opt = tr["batches_held"], tr["checked_steps"], \
        tr["optimizer"]
    with bench.span("bench.plan"):
        plans = model.plan(cfg, batch, dtype)
    window.mark("plan")
    params = init_state(bench.key(seed, 0), cfg)
    batches = make_batches(bench.key(seed, 1), cfg, batch, held, dtype)
    drop_key = bench.key(seed, 2)
    jax.block_until_ready(batches)
    window.mark("data")
    step = build_train_step(cfg, plans, adamw.AdamWConfig(**opt))
    opt_state = adamw.init(params)

    # The first steps go through the window's own call, each on another
    # batch; the reference follows them.
    start = jax.tree.map(jnp.copy, params)
    losses, grad_norms = [], None
    for i in range(n_checked):
        params, opt_state, value = step(params, opt_state,
                                        *batches[i % held], drop_key)
        losses.append(value)
        if grad_norms is None:
            grad_norms = leaf_norms(opt_state["m"]) / (1 - opt["b1"])
    prog = {"losses": [float(v) for v in losses],
            "grad_norms": [float(v) for v in grad_norms],
            "update_norms": [float(v) for v in change_norms(params, start)]}
    del start
    window.mark(f"warm and {n_checked} checked steps")

    state = {"params": params, "opt": opt_state}

    def call(i):
        state["params"], state["opt"], value = step(
            state["params"], state["opt"], *batches[(n_checked + i) % held],
            drop_key)
        return value
    n = _closed_loop(window, seconds, tr["in_flight"], call)
    mem = bench.memory_peak_bytes(cell.chips)

    del state, params, opt_state
    want = bench.reference_of(cfg).train(
        init_state(bench.key(seed, 0), cfg),
        [batches[i % held] for i in range(n_checked)], opt, cfg, drop_key)
    itemsize = jnp.dtype(dtype).itemsize
    return bench.Run(
        attempted=n, failed=0, window_s=window.seconds,
        e2e={"train_img_per_s": n * batch * cell.chips / window.seconds},
        work={"steps": n, "images": n * batch * cell.chips, "batch": batch,
              "flops_per_image": train_flops(cfg, batch) / batch,
              "pointwise": pointwise_convs(cfg, batch),
              "depthwise": depthwise_work(cfg, batch, itemsize),
              "dtype": dtype},
        checks=bench.checks_against(checked(prog, want,
                                            cfg["limits"]["train"]),
                                    cfg["limits"]["train"]),
        memory_peak_bytes=mem,
        notes=_plan_notes(cfg, plans) + [
            f"[window] {window.seconds:.3f} s, {n} steps, "
            f"{window.compiles.count} compiles inside",
            f"[train] program losses {prog['losses']}, reference "
            f"{want['losses']}"],
        window=window)


# ---------------------------------------------------------------- control

def control(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The reference in float8 against the reference in float32, on the
    weights, batches and stochastic-depth key a run with this seed
    checks."""
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "train":
        raise ValueError(f"convnext has no work {tr['work']!r}")
    ref = bench.reference_of(cfg)
    params = init_state(bench.key(seed, 0), cfg)
    batches = make_batches(bench.key(seed, 1), cfg, tr["batch"],
                           tr["batches_held"],
                           cfg["dtype"])[:tr["checked_steps"]]
    drop_key = bench.key(seed, 2)
    want = ref.train(params, batches, tr["optimizer"], cfg, drop_key)
    got = ref.train(params, batches, tr["optimizer"], cfg, drop_key, CONTROL)
    return checked(got, want, cfg["limits"]["train"])


# ----------------------------------------------------------------- faults

def _unchanged(_build):
    def build_unchanged(cfg, plans, opt_cfg):
        loss = functools.partial(model.loss, plans=plans)
        return jax.jit(lambda p, o, x, y, k: (
            p, o, loss(p, x, y, key=jax.random.fold_in(k, o["step"]))))
    return build_unchanged


def _half(_build):
    def build_half(cfg, plans, opt_cfg):
        h = plans["stem"].spec.i_n // 2
        step = model.train_step(model.plan(cfg, h, plans["stem"].dtype),
                                opt_cfg)
        return jax.jit(lambda p, o, x, y, k: step(p, o, x[:h], y[:h], k),
                       donate_argnums=(0, 1))
    return build_half


def _dropped(_depthwise):
    """Every block's depthwise conv replaced by its input."""
    return lambda p, x, plan: x


_THIS = sys.modules[__name__]
FAULTS = {
    "state_unchanged": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _unchanged)),
    "half_batch": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _half)),
    "depthwise_dropped": ("train", functools.partial(
        faults.swap, model, "depthwise", _dropped)),
}


# -------------------------------------------------------------- test size

def tiny(config: Dict, traffic: Dict):
    """One block per stage at a sixteenth of the widths, 32 x 32 images, 10
    classes and batch 4, with the configuration's limits, in float32."""
    cfg, tr = copy.deepcopy(config), dict(traffic)
    cfg.update(depths=[1] * len(cfg["depths"]),
               dims=[d // 16 for d in cfg["dims"]], image_size=32,
               num_classes=10, dtype="float32")
    tr.update(batch=4, batches_held=4)
    return cfg, tr
