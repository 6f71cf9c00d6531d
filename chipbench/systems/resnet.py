"""The ``resnet`` system: a configuration's whole ResNet built by the
program's ``repro.models.resnet`` (every conv through
``conv2d_layer(plan=)``, its plan resolved once per geometry by the
program's default policy, batch norm, the residual adds, the head), driven
as a closed loop of AdamW training steps (traffic ``work`` ``train``).

Weights, running statistics, images and labels are made on the device from
the seed, in one jitted call each.  A run compares, with the reference, the
first gradient's leaf norms (``train.grad``) and the parameters' change
over the checked steps (``train.update``) as every training cell does
(``compare.train_checks``), and the stem's running statistics after the
first step (``train.stem_stats``): the one batch-norm reading that every
image of the batch reaches before bfloat16 rounding, amplified by the
batch norms after it, decorrelates the network's deeper activations from
the float32 reference.  The loss and the running statistics of every
batch norm after the checked steps are printed, not compared.
"""
from __future__ import annotations

import copy
import functools
import json
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, compare, faults, yardstick
from chipbench.control import CONTROL
from chipbench.systems.conv_chain import _closed_loop, leaf_norms
from repro.models import resnet as model

F32 = jnp.float32


def layer_convs(cfg: Dict, batch: int) -> List[yardstick.Conv]:
    """Every conv of the network on ``batch`` images, in order."""
    return [yardstick.Conv(batch, x[1], x[2], x[3], k[0], k[1], k[3], s, s,
                           p, p)
            for _, x, k, s, p in model.convs(cfg, batch)]


def train_flops(cfg: Dict, batch: int) -> int:
    """The yardstick's training operations: forward, kernel gradients and
    input gradients of every conv and of the linear head (a 1x1 conv on a
    1x1 image), but no input gradient for the stem, whose input is the
    images.  Norms, pools and adds are not counted."""
    fc = yardstick.Conv(batch, 1, 1, cfg["widths"][-1] * cfg["expansion"],
                        1, 1, cfg["num_classes"])
    convs = layer_convs(cfg, batch) + [fc]
    return 3 * yardstick.forward_flops(convs) - convs[0].flops


def pointwise_convs(cfg: Dict, batch: int) -> List[yardstick.Conv]:
    """The 1x1 convs, the projections included."""
    return [c for c in layer_convs(cfg, batch) if c.k_h == c.k_w == 1]


@functools.partial(jax.jit, static_argnums=1)
def _init(key, cfg_json: str):
    return model.init(key, json.loads(cfg_json))


def init_state(key, cfg: Dict):
    """Parameters and running statistics, made on the device by one
    program per configuration."""
    return _init(key, json.dumps(cfg, sort_keys=True))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _batches(key, shape, classes: int, held: int, dtype: str):
    images = jax.random.normal(jax.random.fold_in(key, 0), (held,) + shape,
                               F32)
    labels = jax.random.randint(jax.random.fold_in(key, 1),
                                (held, shape[0]), 0, classes, jnp.int32)
    return [(images[b].astype(dtype), labels[b]) for b in range(held)]


def make_batches(key, cfg: Dict, batch: int, held: int, dtype) -> List:
    """``held`` batches of standard-normal images and uniform labels."""
    size = cfg["image_size"]
    return _batches(key, (batch, size, size, cfg["in_channels"]),
                    cfg["num_classes"], held, str(dtype))


def build_train_step(cfg, plans, opt_cfg):
    return jax.jit(model.train_step(plans, opt_cfg), donate_argnums=(0, 1, 2))


@jax.jit
def change_norms(new, old):
    return jnp.stack([jnp.linalg.norm(a.astype(F32) - b.astype(F32))
                      for a, b in zip(jax.tree.leaves(new),
                                      jax.tree.leaves(old))])


def stats_gap(got, ref, start) -> float:
    """The worst running-statistics leaf: the gap between the program's
    and the reference's, over the larger of the reference's change of
    that leaf over the checked steps and the median leaf's change."""
    got, ref, start = ([np.asarray(x, np.float64) for x in jax.tree.leaves(t)]
                       for t in (got, ref, start))
    gaps = np.array([np.linalg.norm(g - r) for g, r in zip(got, ref)])
    moves = np.array([np.linalg.norm(r - s) for r, s in zip(ref, start)])
    return float(np.max(gaps / np.maximum(np.maximum(moves, np.median(moves)),
                                          1e-30)))


def stem_stats_gap(got, ref, start) -> float:
    """The stem batch norm's running statistics after the first step: the
    worse of mean and variance, each the gap between the program's and the
    reference's over the reference's move from ``start``."""
    return max(float(np.linalg.norm(np.float64(got[k]) - np.float64(ref[k])) /
                     max(np.linalg.norm(np.float64(ref[k]) -
                                        np.float64(start[k])), 1e-30))
               for k in ("mean", "var"))


def checked(prog: Dict, want: Dict, stats0) -> Dict[str, float]:
    """The numbers the limits decide: ``train.grad`` and ``train.update``
    of ``compare.train_checks`` and ``train.stem_stats``."""
    values = compare.train_checks(prog, want)
    del values["train.loss"]
    values["train.stem_stats"] = stem_stats_gap(
        prog["first_stats"]["stem_bn"], want["first_stats"]["stem_bn"],
        stats0["stem_bn"])
    return values


def unchecked(prog: Dict, want: Dict, stats0) -> str:
    """The loss and every batch norm's running statistics after the
    checked steps, against the reference: printed, not compared."""
    loss = compare.train_checks(prog, want)["train.loss"]
    stats = stats_gap(prog["stats"], want["stats"], stats0)
    return f"[train] not compared: loss gap {loss:.6g}, running " \
        f"statistics gap {stats:.6g}"


def _plan_notes(cfg, plans) -> List[str]:
    seen, notes = set(), []
    for path, x, k, s, _ in model.convs(cfg, 1):
        key = (x[1:], k, s)
        if key not in seen:
            seen.add(key)
            plan = plans
            for part in path:
                plan = plan[part]
            notes.append(f"[plan] {k[0]}x{k[1]}/{s} {x[1]}px {k[2]}->{k[3]}: "
                         f"{plan.algorithm}")
    return notes


def run(cell: bench.Cell, seed: int, seconds: float,
        window: bench.Window) -> bench.Run:
    from repro.optim import adamw
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "train":
        raise ValueError(f"resnet has no work {tr['work']!r}")
    dtype, batch = cfg["dtype"], tr["batch"]
    held, n_checked, opt = tr["batches_held"], tr["checked_steps"], \
        tr["optimizer"]
    with bench.span("bench.plan"):
        plans = model.plan(cfg, batch, dtype)
    window.mark("plan")
    params, stats = init_state(bench.key(seed, 0), cfg)
    batches = make_batches(bench.key(seed, 1), cfg, batch, held, dtype)
    jax.block_until_ready(batches)
    window.mark("data")
    step = build_train_step(cfg, plans, adamw.AdamWConfig(**opt))
    opt_state = adamw.init(params)

    # The first steps go through the window's own call, each on another
    # batch; the reference follows them.
    start = jax.tree.map(jnp.copy, params)
    losses, grad_norms, first_stats = [], None, None
    for i in range(n_checked):
        params, stats, opt_state, value = step(params, stats, opt_state,
                                               *batches[i % held])
        losses.append(value)
        if grad_norms is None:
            grad_norms = leaf_norms(opt_state["m"]) / (1 - opt["b1"])
            first_stats = jax.device_get(stats)
    prog = {"losses": [float(v) for v in losses],
            "grad_norms": [float(v) for v in grad_norms],
            "update_norms": [float(v) for v in change_norms(params, start)],
            "first_stats": first_stats, "stats": jax.device_get(stats)}
    del start
    window.mark(f"warm and {n_checked} checked steps")

    state = {"params": params, "stats": stats, "opt": opt_state}

    def call(i):
        state["params"], state["stats"], state["opt"], value = step(
            state["params"], state["stats"], state["opt"],
            *batches[(n_checked + i) % held])
        return value
    n = _closed_loop(window, seconds, tr["in_flight"], call)
    mem = bench.memory_peak_bytes(cell.chips)

    del state, params, stats, opt_state
    params0, stats0 = init_state(bench.key(seed, 0), cfg)
    want = bench.reference_of(cfg).train(
        params0, stats0, [batches[i % held] for i in range(n_checked)], opt,
        cfg)
    return bench.Run(
        attempted=n, failed=0, window_s=window.seconds,
        e2e={"train_img_per_s": n * batch * cell.chips / window.seconds},
        work={"steps": n, "images": n * batch * cell.chips, "batch": batch,
              "flops_per_image": train_flops(cfg, batch) / batch,
              "pointwise": pointwise_convs(cfg, batch), "dtype": dtype},
        checks=bench.checks_against(checked(prog, want, stats0),
                                    cfg["limits"]["train"]),
        memory_peak_bytes=mem,
        notes=_plan_notes(cfg, plans) + [
            f"[window] {window.seconds:.3f} s, {n} steps, "
            f"{window.compiles.count} compiles inside",
            f"[train] program losses {prog['losses']}, reference "
            f"{want['losses']}", unchecked(prog, want, stats0)],
        window=window)


# ---------------------------------------------------------------- control

def control(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The reference in float8 against the reference in float32, on the
    weights and batches a run with this seed checks."""
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "train":
        raise ValueError(f"resnet has no work {tr['work']!r}")
    ref = bench.reference_of(cfg)
    params, stats = init_state(bench.key(seed, 0), cfg)
    batches = make_batches(bench.key(seed, 1), cfg, tr["batch"],
                           tr["batches_held"],
                           cfg["dtype"])[:tr["checked_steps"]]
    want = ref.train(params, stats, batches, tr["optimizer"], cfg)
    got = ref.train(params, stats, batches, tr["optimizer"], cfg, CONTROL)
    print(unchecked(got, want, stats), file=sys.stderr, flush=True)
    return checked(got, want, stats)


# ----------------------------------------------------------------- faults

def _unchanged(_build):
    def build_unchanged(cfg, plans, opt_cfg):
        loss = functools.partial(model.loss, plans=plans)
        return jax.jit(lambda p, s, o, x, y: (p, s, o, loss(p, s, x, y)[0]))
    return build_unchanged


def _half(_build):
    def build_half(cfg, plans, opt_cfg):
        h = plans["stem"].spec.i_n // 2
        step = model.train_step(model.plan(cfg, h, plans["stem"].dtype),
                                opt_cfg)
        return jax.jit(lambda p, s, o, x, y: step(p, s, o, x[:h], y[:h]),
                       donate_argnums=(0, 1, 2))
    return build_half


def _dropped(shortcut):
    """Every block's shortcut left out of its sum (the projections' running
    statistics still move)."""
    return lambda p, s, x, plans: (0.0, shortcut(p, s, x, plans)[1])


_THIS = sys.modules[__name__]
FAULTS = {
    "state_unchanged": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _unchanged)),
    "half_batch": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _half)),
    "shortcut_dropped": ("train", functools.partial(
        faults.swap, model, "shortcut", _dropped)),
}


# -------------------------------------------------------------- test size

def tiny(config: Dict, traffic: Dict):
    """One block per stage at a sixteenth of the widths, 32 x 32 images, 10
    classes and batch 4, with the configuration's limits, in float32: at
    this size a batch norm sees as few as 4 values a channel, which
    magnifies bfloat16's rounding past the limits set at full size (the
    float32 reference with its conv operands and outputs rounded to
    bfloat16 reads a gradient-norm gap of 0.77 here)."""
    cfg, tr = copy.deepcopy(config), dict(traffic)
    cfg["stem"]["width"] //= 16
    cfg.update(depths=[1] * len(cfg["depths"]),
               widths=[w // 16 for w in cfg["widths"]], image_size=32,
               num_classes=10, dtype="float32")
    tr.update(batch=4, batches_held=4)
    return cfg, tr
