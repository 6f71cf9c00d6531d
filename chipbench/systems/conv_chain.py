"""The ``conv_chain`` system: a configuration's stages as chains of convs
through the repo's layer entry points (``plan_conv2d_layer`` once per
layer, then ``conv2d_layer(plan=)``, ReLU after each), driven as a closed
loop of forward passes (traffic ``work`` ``forward``) or of AdamW
training steps (``train``).

Weights and inputs are made on the device from the seed, in one jitted
call each.  Plans come from the program's default policy, never pinned to
an algorithm.
"""
from __future__ import annotations

import collections
import copy
import functools
import sys
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from chipbench import bench, compare, faults, yardstick
from chipbench.control import CONTROL

F32 = jnp.float32


def _padding(stage: Dict):
    return "VALID" if stage["pad"] == 0 else stage["pad"]


def kernel_shapes(stages: Sequence[Dict]) -> List[tuple]:
    return [(st["k_h"], st["k_w"], st["i_c"], st["o_c"])
            for st in stages for _ in range(st["count"])]


def input_shapes(stages: Sequence[Dict], batch: int) -> List[tuple]:
    return [(batch, st["i_h"], st["i_w"], st["i_c"]) for st in stages]


def output_shapes(stages: Sequence[Dict], batch: int) -> List[tuple]:
    return [(batch, c.o_h, c.o_w, c.o_c) for c in
            (yardstick.chain_convs([st], batch)[0] for st in stages)]


def make_params(key, stages: Sequence[Dict], dtype) -> List[jax.Array]:
    """He-normal kernels, one per conv, made on the device in ``dtype``."""
    shapes = kernel_shapes(stages)

    @jax.jit
    def init(k):
        return [(jax.random.normal(jax.random.fold_in(k, i), s, F32) *
                 (2.0 / (s[0] * s[1] * s[2])) ** 0.5).astype(dtype)
                for i, s in enumerate(shapes)]
    return init(key)


def row_scales(batch: int) -> jax.Array:
    """Each row's contrast, 0.5 to 1.5 across the batch: rows differ in
    scale, as images do, so that a step that leaves rows out changes the
    loss and the gradient."""
    return jnp.linspace(0.5, 1.5, batch, dtype=F32)


def make_batches(key, stages: Sequence[Dict], batch: int, held: int, dtype,
                 targets: bool = False) -> List:
    """``held`` batches, each one normal input per stage, row ``i``
    scaled by ``row_scales(batch)[i]`` (and, with ``targets``, one
    standard-normal target per stage output)."""
    ins, outs = input_shapes(stages, batch), output_shapes(stages, batch)
    scale = row_scales(batch)[:, None, None, None]

    @jax.jit
    def init(k):
        out = []
        for b in range(held):
            kb = jax.random.fold_in(k, b)
            xs = [(jax.random.normal(jax.random.fold_in(kb, s), shape, F32)
                   * scale).astype(dtype)
                  for s, shape in enumerate(ins)]
            if targets:
                ts = [jax.random.normal(jax.random.fold_in(kb, 1000 + s),
                                        shape, F32).astype(dtype)
                      for s, shape in enumerate(outs)]
                out.append((xs, ts))
            else:
                out.append(xs)
        return out
    return init(key)


def plan_layers(stages: Sequence[Dict], batch: int, dtype) -> List:
    """One plan per conv, resolved once by the program's default policy."""
    from repro.models.layers import plan_conv2d_layer
    plans = []
    for st in stages:
        w = jax.ShapeDtypeStruct((st["k_h"], st["k_w"], st["i_c"], st["o_c"]),
                                 dtype)
        plan = plan_conv2d_layer({"w": w},
                                 (batch, st["i_h"], st["i_w"], st["i_c"]),
                                 stride=st["stride"], padding=_padding(st),
                                 dtype=dtype)
        plans.extend([plan] * st["count"])
    return plans


def chain(params, xs, *, stages, plans):
    """Each stage's output: its input through its convs, ReLU after each."""
    from repro.models.layers import conv2d_layer
    outs, i = [], 0
    for st, x in zip(stages, xs):
        for _ in range(st["count"]):
            x = jax.nn.relu(conv2d_layer(
                {"w": params[i]}, x, stride=st["stride"],
                padding=_padding(st), plan=plans[i]))
            i += 1
        outs.append(x)
    return outs


def loss_fn(params, xs, targets, *, stages, plans):
    outs = chain(params, xs, stages=stages, plans=plans)
    return sum(jnp.mean(jnp.square(o.astype(F32) - t.astype(F32)))
               for o, t in zip(outs, targets))


def train_step_fn(stages, plans, opt_cfg):
    """One training step: the loss's value and gradient through the
    program's convs, then ``repro.optim.adamw.update``."""
    from repro.optim import adamw
    loss = functools.partial(loss_fn, stages=stages, plans=plans)

    def step(params, opt_state, xs, targets):
        value, grads = jax.value_and_grad(loss)(params, xs, targets)
        params, opt_state, _ = adamw.update(opt_cfg, grads, opt_state,
                                            params)
        return params, opt_state, value
    return step


def build_forward(stages, plans):
    return jax.jit(functools.partial(chain, stages=stages, plans=plans))


def build_train_step(stages, plans, opt_cfg):
    return jax.jit(train_step_fn(stages, plans, opt_cfg),
                   donate_argnums=(0, 1))


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(leaf.astype(F32))
                      for leaf in jax.tree.leaves(tree)])


@jax.jit
def change_norms(new, old):
    return jnp.stack([jnp.linalg.norm(a.astype(F32) - b.astype(F32))
                      for a, b in zip(new, old)])


def _closed_loop(window, seconds: float, in_flight: int, call) -> int:
    """Call ``call(i)`` back to back for ``seconds``, at most ``in_flight``
    results ahead of the host, and drain the device inside the window.
    Returns the number of calls."""
    n, pending = 0, collections.deque()
    with window() as w:
        end = w.t0 + seconds
        while time.perf_counter() < end:
            w.tick(n)
            with bench.span("bench.step"):
                pending.append(call(n))
            n += 1
            if len(pending) > in_flight:
                with bench.span("bench.wait"):
                    jax.block_until_ready(pending.popleft())
        with bench.span("bench.wait"):
            jax.block_until_ready(list(pending))
    return n


def _plan_notes(stages, plans) -> List[str]:
    notes, i = [], 0
    for st in stages:
        notes.append(f"[plan] {st['layer']} x{st['count']}: "
                     f"{plans[i].algorithm}")
        i += st["count"]
    return notes


def run(cell: bench.Cell, seed: int, seconds: float,
        window: bench.Window) -> bench.Run:
    work = cell.traffic["work"]
    if work == "forward":
        return run_forward(cell, seed, seconds, window)
    if work == "train":
        return run_train(cell, seed, seconds, window)
    raise ValueError(f"conv_chain has no work {work!r}")


def run_forward(cell, seed, seconds, window) -> bench.Run:
    cfg, tr = cell.config, cell.traffic
    stages, dtype, batch = cfg["stages"], cfg["dtype"], tr["batch"]
    held = tr["batches_held"]
    with bench.span("bench.plan"):
        plans = plan_layers(stages, batch, dtype)
    window.mark("plan")
    params = make_params(bench.key(seed, 0), stages, dtype)
    batches = make_batches(bench.key(seed, 1), stages, batch, held, dtype)
    jax.block_until_ready(batches)
    window.mark("data")
    fwd = build_forward(stages, plans)
    jax.block_until_ready(fwd(params, batches[0]))
    window.mark("warm")

    outs = {}

    def call(i):
        outs["last"] = (i % held, fwd(params, batches[i % held]))
        return outs["last"][1]
    n = _closed_loop(window, seconds, tr["in_flight"], call)
    mem = bench.memory_peak_bytes(cell.chips)

    # The window's last forward against the reference on its own batch.
    which, got = outs.pop("last")
    x = batches[which]
    del batches
    ref = bench.reference_of(cfg)
    want = jax.jit(lambda p, xs: ref.forward(p, xs, stages))(params, x)
    values = {f"fwd.{st['layer']}": compare.rel_l2(g, r)
              for st, g, r in zip(stages, got, want)}
    convs = yardstick.chain_convs(stages, batch)
    return bench.Run(
        attempted=n, failed=0, window_s=window.seconds,
        e2e={"fwd_img_per_s": n * batch / window.seconds},
        work={"forwards": n, "images": n * batch, "batch": batch,
              "traced_forwards": n - (window.traced_from or 0),
              "flops_per_image": yardstick.forward_flops(convs) / batch,
              "convs": convs, "dtype": dtype},
        checks=bench.checks_against(values, cfg["limits"]["forward"]),
        memory_peak_bytes=mem,
        notes=_plan_notes(stages, plans) + [
            f"[window] {window.seconds:.3f} s, {n} forwards, "
            f"{window.compiles.count} compiles inside"],
        window=window)


def run_train(cell, seed, seconds, window) -> bench.Run:
    from repro.optim import adamw
    cfg, tr = cell.config, cell.traffic
    stages, dtype, batch = cfg["stages"], cfg["dtype"], tr["batch"]
    held, checked, opt = tr["batches_held"], tr["checked_steps"], \
        tr["optimizer"]
    with bench.span("bench.plan"):
        plans = plan_layers(stages, batch, dtype)
    window.mark("plan")
    params = make_params(bench.key(seed, 0), stages, F32)
    batches = make_batches(bench.key(seed, 1), stages, batch, held, dtype,
                           targets=True)
    jax.block_until_ready(batches)
    window.mark("data")
    step = build_train_step(stages, plans, adamw.AdamWConfig(**opt))
    opt_state = adamw.init(params)

    # The first steps go through the window's own call, each on another
    # batch; the reference follows them.
    start = jax.tree.map(jnp.copy, params)
    losses, grad_norms = [], None
    for i in range(checked):
        params, opt_state, value = step(params, opt_state, *batches[i % held])
        losses.append(value)
        if grad_norms is None:
            grad_norms = leaf_norms(opt_state["m"]) / (1 - opt["b1"])
    prog = {"losses": [float(v) for v in losses],
            "grad_norms": [float(v) for v in grad_norms],
            "update_norms": [float(v) for v in change_norms(params, start)]}
    del start
    window.mark(f"warm and {checked} checked steps")

    state = {"params": params, "opt": opt_state}

    def call(i):
        state["params"], state["opt"], value = step(
            state["params"], state["opt"], *batches[(checked + i) % held])
        return value
    n = _closed_loop(window, seconds, tr["in_flight"], call)
    mem = bench.memory_peak_bytes(cell.chips)

    del state, params, opt_state
    batches = [batches[i % held] for i in range(checked)]
    ref = bench.reference_of(cfg)
    want = ref.train(make_params(bench.key(seed, 0), stages, F32), batches,
                     opt, stages)
    return bench.Run(
        attempted=n, failed=0, window_s=window.seconds,
        e2e={"train_img_per_s": n * batch * cell.chips / window.seconds},
        work={"steps": n, "images": n * batch * cell.chips, "batch": batch,
              "flops_per_image": yardstick.train_flops(stages, batch) / batch,
              "dtype": dtype},
        checks=bench.checks_against(compare.train_checks(prog, want),
                                    cfg["limits"]["train"]),
        memory_peak_bytes=mem,
        notes=_plan_notes(stages, plans) + [
            f"[window] {window.seconds:.3f} s, {n} steps, "
            f"{window.compiles.count} compiles inside",
            f"[train] program losses {prog['losses']}, reference "
            f"{want['losses']}"],
        window=window)


# ---------------------------------------------------------------- control

def control(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The reference in float8 against the reference in float32, on the
    weights and batches a run with this seed checks."""
    cfg, tr = cell.config, cell.traffic
    stages, ref = cfg["stages"], bench.reference_of(cfg)
    if tr["work"] == "forward":
        params = make_params(bench.key(seed, 0), stages, cfg["dtype"])
        xs = make_batches(bench.key(seed, 1), stages, tr["batch"],
                          tr["batches_held"], cfg["dtype"])[0]
        f = jax.jit(lambda p, x, r: ref.forward(p, x, stages, r),
                    static_argnums=2)
        want, got = f(params, xs, None), f(params, xs, CONTROL)
        return {f"fwd.{st['layer']}": compare.rel_l2(g, w)
                for st, g, w in zip(stages, got, want)}
    if tr["work"] == "train":
        params = make_params(bench.key(seed, 0), stages, F32)
        batches = make_batches(
            bench.key(seed, 1), stages, tr["batch"], tr["batches_held"],
            cfg["dtype"], targets=True)[:tr["checked_steps"]]
        want = ref.train(params, batches, tr["optimizer"], stages)
        got = ref.train(params, batches, tr["optimizer"], stages, CONTROL)
        return compare.train_checks(got, want)
    raise ValueError(f"conv_chain has no work {tr['work']!r}")


# ----------------------------------------------------------------- faults

def _altered_forward(build):
    """The first stage's output for the first image zeroed."""
    def build_altered(stages, plans):
        fwd = build(stages, plans)

        @jax.jit
        def altered(params, xs):
            outs = fwd(params, xs)
            return [outs[0].at[0].set(0)] + list(outs[1:])
        return altered
    return build_altered


def _unchanged(_build):
    def build_unchanged(stages, plans, opt_cfg):
        loss = functools.partial(loss_fn, stages=stages, plans=plans)
        return jax.jit(lambda p, o, xs, ts: (p, o, loss(p, xs, ts)))
    return build_unchanged


def _half(_build):
    def build_half(stages, plans, opt_cfg):
        h = plans[0].spec.i_n // 2
        step = train_step_fn(
            stages, plan_layers(stages, h, plans[0].dtype), opt_cfg)

        def half(p, o, xs, ts):
            return step(p, o, [x[:h] for x in xs], [t[:h] for t in ts])
        return jax.jit(half, donate_argnums=(0, 1))
    return build_half


_THIS = sys.modules[__name__]
FAULTS = {
    "answer_altered": ("forward", functools.partial(
        faults.swap, _THIS, "build_forward", _altered_forward)),
    "state_unchanged": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _unchanged)),
    "half_batch": ("train", functools.partial(
        faults.swap, _THIS, "build_train_step", _half)),
}


# -------------------------------------------------------------- test size

# Two stages at tiny widths: a strided 7x7 VALID conv and a chain of three
# padded 3x3 convs.
TINY_STAGES = [
    {"layer": "cv4", "count": 1, "i_h": 20, "i_w": 20, "i_c": 8, "k_h": 7,
     "k_w": 7, "o_c": 8, "stride": 2, "pad": 0},
    {"layer": "cv9", "count": 3, "i_h": 8, "i_w": 8, "i_c": 8, "k_h": 3,
     "k_w": 3, "o_c": 8, "stride": 1, "pad": 1},
]


def tiny(config: Dict, traffic: Dict):
    """``TINY_STAGES`` at batch 4, with the configuration's limits for
    those stages."""
    cfg, tr = copy.deepcopy(config), dict(traffic)
    cfg["stages"] = TINY_STAGES
    keep = {f"fwd.{st['layer']}" for st in TINY_STAGES}
    cfg["limits"]["forward"] = {k: v for k, v in
                                cfg["limits"]["forward"].items() if k in keep}
    tr.update(batch=4, batches_held=4)
    return cfg, tr
