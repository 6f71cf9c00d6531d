"""Chip smoke: drive the main path once on a TPU and check what comes out.

    python3 chip_smoke.py               # one chip: device, layers, train, serve
    python3 chip_smoke.py --four-chips  # four chips: the sharded conv only

Phases, in order, each failing the run on its first error:

device  the platform must be ``tpu`` (no CPU fallback); prints the device
        kind and count, the jax version and the compile-cache directory.
layers  paper Table 2 (cv1-cv12) at full width, batch 32, in bf16 and in
        f32 at HIGHEST precision: plan each conv with ``plan_conv2d``,
        run ``conv2d(plan=...)``, and compare with an f32
        ``lax.conv_general_dilated`` at HIGHEST precision within the plan
        algorithm's contract budget (``repro.core.numerics``).  A Pallas
        plan must compile to a ``tpu_custom_call``.
train   5 AdamW steps of ``value_and_grad`` through ``conv2d(plan=...)``
        (the MEC custom VJP) at ResNet-101's Table-3 layers in bf16.
        Step 1's input and kernel grads must match the reference conv's
        within budget, and the loss must stay finite and fall.
serve   the whisper-tiny conv frontend (80 mels -> d_model 384) served by
        two warmed ``ConvService``s over classes up to 30 s of audio at
        batch 1 and 8; 8 requests, each checked against the reference.

``--four-chips`` runs cv4 at batch 32 in bf16 through ``conv2d`` with a
spatial and a batch x spatial partition over all four chips (the plan
asserts the shardcheck collective contract), forward and
``value_and_grad``, against the same conv on one device.

Times printed here are smoke timings of one call, not benchmarks.  The
last line of stdout is one JSON object, ``{"ok": true, "device": ...}``,
printed only when every phase passed.  Everything runs in this one
process: the chip belongs to it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from repro.bench.scenarios import (CV_LAYERS, RESNET101_WEIGHTS,  # noqa: E402
                                   layer_spec)
from repro.core.conv_api import conv2d  # noqa: E402
from repro.core.numerics import contract_for  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.plan import plan_conv2d  # noqa: E402

# (dtype, GEMM precision) of the layers phase.  On TPU an f32 GEMM at the
# default precision is one bf16 pass; the f32 budgets assume f32 products,
# so the f32 plans ask for HIGHEST.
LAYER_DTYPES = (("bfloat16", None), ("float32", "HIGHEST"))
TRAIN_LAYERS = tuple(RESNET101_WEIGHTS)          # cv4, cv9-cv12
WHISPER_TINY = {"n_mels": 80, "d_model": 384}    # configs/archs.py
SERVE_CLASSES = ((1, 1000, 1), (1, 3000, 1), (8, 1000, 1), (8, 3000, 1))
SERVE_REQUESTS = ((1, 3000), (1, 2210), (1, 700), (8, 3000), (3, 1500),
                  (8, 950), (2, 2999), (1, 1))   # (batch, frames)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or unchecked result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- helpers

def reference_conv(x, k, stride):
    """Plain f32 conv at HIGHEST precision on the operands' own values."""
    return lax.conv_general_dilated(
        x.astype(jnp.float32), k.astype(jnp.float32), stride, "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def rel_err(got, ref) -> float:
    """Scale-normalized max error, the contract's metric."""
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) /
                 jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def budget(algorithm: str, dtype: str, direction: str) -> float:
    tol = contract_for(algorithm).tolerance(dtype, direction)
    check(tol is not None, f"{algorithm} declares no {dtype} {direction} "
          "budget")
    return tol


def operands(spec, dtype, key):
    """Random NHWC input and HWIO kernel (fan-in scaled), made on device."""
    kx, kk = jax.random.split(key)
    x = jax.random.normal(kx, (spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                          jnp.float32)
    k = jax.random.normal(kk, (spec.k_h, spec.k_w, spec.i_c, spec.k_c),
                          jnp.float32) * (spec.k_h * spec.k_w * spec.i_c) ** -.5
    return x.astype(dtype), k.astype(dtype)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def assert_kernel_compiled(plan, compiled, what: str) -> None:
    """On the chip a Pallas plan must lower to a Mosaic custom call, never
    to the interpreter's XLA loop."""
    if plan.algorithm == "mec_fused" and on_tpu():
        check("tpu_custom_call" in compiled.as_text(),
              f"{what}: {plan.algorithm} compiled without a "
              "tpu_custom_call (the kernel was interpreted)")


def device_bytes_limit():
    """Bytes the first device may hold, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def compiled_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.temp_size_in_bytes + m.argument_size_in_bytes +
               m.output_size_in_bytes - m.alias_size_in_bytes)


# ----------------------------------------------------------------- phases

class CacheCounter:
    """Persistent-compile-cache hits and misses, from JAX's own events."""
    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        self.counts = {"hits": 0, "misses": 0}

    def __call__(self, event: str, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def phase_device(want_count: int = 1) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"JAX found no TPU (platform {dev.platform!r}); this smoke runs "
            "on the chip only")
    check(len(devices) >= want_count,
          f"need {want_count} TPU device(s), found {len(devices)}")
    from repro.core.compat import enable_compile_cache
    cache = enable_compile_cache()
    log(f"[device] kind={dev.device_kind!r} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_layers(names=tuple(CV_LAYERS), batch: int = 32,
                 dtypes=LAYER_DTYPES, channel_cap=None,
                 seed: int = 0) -> list:
    """Each Table-2 layer through plan_conv2d -> conv2d(plan=...), once
    per (dtype, precision) of ``dtypes``."""
    rows = []
    key = jax.random.key(seed)
    for (dtype, precision), (i, name) in itertools.product(
            dtypes, enumerate(names)):
        spec = layer_spec(name, batch=batch, channel_cap=channel_cap)
        plan = plan_conv2d(spec, dtype=dtype, mode="analytic",
                           precision=precision)
        x, k = operands(spec, dtype, jax.random.fold_in(key, i))
        stride = (spec.s_h, spec.s_w)
        fn = jax.jit(lambda a, b, _p=plan, _s=stride: conv2d(
            a, b, stride=_s, plan=_p))
        compiled = fn.lower(x, k).compile()
        assert_kernel_compiled(plan, compiled, name)
        jax.block_until_ready(compiled(x, k))
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(x, k))
        dt = time.perf_counter() - t0
        err = rel_err(out, reference_conv(x, k, stride))
        tol = budget(plan.algorithm, dtype, "fwd")
        log(f"[layers] {name} n={batch} {dtype} precision={precision} "
            f"algorithm={plan.algorithm} err={err:.3e} budget={tol:.1e} "
            f"smoke_time={dt * 1e3:.3f} ms")
        check(math.isfinite(err) and err <= tol,
              f"{name} {dtype}: {plan.algorithm} error {err:.3e} > budget "
              f"{tol:.1e}")
        rows.append({"layer": name, "dtype": dtype,
                     "algorithm": plan.algorithm, "err": err, "budget": tol,
                     "smoke_seconds": dt})
    return rows


def _train_fns(spec, plan, dtype):
    stride = (spec.s_h, spec.s_w)

    def loss_of(conv):
        def loss(x, k, target):
            y = conv(x, k).astype(jnp.float32)
            return jnp.mean(jnp.square(y - target))
        return loss

    plan_loss = loss_of(lambda x, k: conv2d(x, k, stride=stride, plan=plan))
    ref_loss = loss_of(lambda x, k: reference_conv(x, k, stride))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, weight_decay=0.0,
                                total_steps=5)

    def step(params, opt_state, x, target):
        loss, g = jax.value_and_grad(
            lambda p: plan_loss(x, p.astype(dtype), target))(params)
        params, opt_state, _ = adamw.update(opt_cfg, g, opt_state, params)
        return params, opt_state, loss

    grads = jax.grad(plan_loss, argnums=(0, 1))
    ref_grads = jax.grad(ref_loss, argnums=(0, 1))
    return step, grads, ref_grads


def fit_batch(spec_at, dtype, want: int) -> int:
    """Largest power of two <= want whose compiled training programs fit
    the device memory (every batch fits where no limit is reported)."""
    limit = device_bytes_limit()
    batch = want
    while True:
        spec = spec_at(batch)
        if limit is None or batch == 1:
            return batch
        plan = plan_conv2d(spec, dtype=dtype, mode="analytic")
        step, grads, ref_grads = _train_fns(spec, plan, dtype)
        x = jax.ShapeDtypeStruct((spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                                 dtype)
        kf = jax.ShapeDtypeStruct((spec.k_h, spec.k_w, spec.i_c, spec.k_c),
                                  jnp.float32)
        kd = jax.ShapeDtypeStruct(kf.shape, dtype)
        t = jax.ShapeDtypeStruct(spec.out_shape, jnp.float32)
        opt = jax.eval_shape(adamw.init, kf)
        need = max(
            compiled_bytes(jax.jit(step).lower(kf, opt, x, t).compile()),
            compiled_bytes(jax.jit(grads).lower(x, kd, t).compile()),
            compiled_bytes(jax.jit(ref_grads).lower(x, kd, t).compile()))
        if need <= limit:
            return batch
        log(f"[train] batch {batch} needs {need} B > {limit} B; halving")
        batch //= 2


def phase_train(names=TRAIN_LAYERS, batch: int = 32,
                dtype: str = "bfloat16", steps: int = 5, channel_cap=None,
                seed: int = 1) -> list:
    """AdamW on one conv kernel per layer, fitting a teacher conv."""
    rows = []
    key = jax.random.key(seed)
    for i, name in enumerate(names):
        n = fit_batch(lambda b: layer_spec(name, batch=b,
                                           channel_cap=channel_cap),
                      dtype, batch)
        spec = layer_spec(name, batch=n, channel_cap=channel_cap)
        plan = plan_conv2d(spec, dtype=dtype, mode="analytic")
        step, grads, ref_grads = _train_fns(spec, plan, dtype)
        kx, kt = jax.random.split(jax.random.fold_in(key, i))
        x, k = operands(spec, dtype, kx)
        _, teacher = operands(spec, dtype, kt)
        target = reference_conv(x, teacher, (spec.s_h, spec.s_w))

        g_x, g_k = jax.jit(grads)(x, k, target)
        r_x, r_k = jax.jit(ref_grads)(x, k, target)
        errs = {"d_input": rel_err(g_x, r_x), "d_kernel": rel_err(g_k, r_k)}
        tol = budget(plan.algorithm, dtype, "grad")

        params = k.astype(jnp.float32)
        opt_state = adamw.init(params)
        jstep = jax.jit(step)
        compiled = jstep.lower(params, opt_state, x, target).compile()
        assert_kernel_compiled(plan, compiled, f"{name} train step")
        losses, t0 = [], time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = compiled(params, opt_state, x, target)
            losses.append(float(loss))
        dt = (time.perf_counter() - t0) / steps
        log(f"[train] {name} n={n} {dtype} algorithm={plan.algorithm} "
            f"grad_err d_input={errs['d_input']:.3e} "
            f"d_kernel={errs['d_kernel']:.3e} budget={tol:.1e} "
            f"losses={['%.6g' % v for v in losses]} "
            f"smoke_step_time={dt * 1e3:.3f} ms")
        for which, err in errs.items():
            check(math.isfinite(err) and err <= tol,
                  f"{name}: step-1 {which} error {err:.3e} > {tol:.1e}")
        check(all(math.isfinite(v) for v in losses),
              f"{name}: non-finite loss {losses}")
        check(losses[-1] < losses[0], f"{name}: loss did not fall {losses}")
        rows.append({"layer": name, "batch": n, "algorithm": plan.algorithm,
                     "grad_err": errs, "budget": tol, "losses": losses})
    return rows


def phase_serve(n_mels: int = WHISPER_TINY["n_mels"],
                d_model: int = WHISPER_TINY["d_model"],
                classes=SERVE_CLASSES, requests=SERVE_REQUESTS,
                dtype: str = "bfloat16", seed: int = 2) -> list:
    """The whisper conv frontend behind two warmed ConvServices."""
    from repro.serving.conv_service import whisper_frontend_service
    frontend, (svc1, svc2) = whisper_frontend_service(
        jax.random.key(seed), n_mels, d_model, classes, dtype=dtype)
    for svc in (svc1, svc2):
        check(not svc.warmup.warnings,
              f"warmup warnings: {svc.warmup.warnings}")
    log(f"[serve] warmed {len(classes)} classes x 2 convs: "
        f"{svc1.warmup.summary()} | {svc2.warmup.summary()}")
    rows = []
    key = jax.random.key(seed + 1)
    for i, (b, t) in enumerate(requests):
        mel = jax.random.normal(jax.random.fold_in(key, i), (b, t, n_mels),
                                jnp.float32).astype(dtype)
        t0 = time.perf_counter()
        out = jax.block_until_ready(frontend(mel))
        dt = time.perf_counter() - t0
        # The reference frontend, one conv at a time on the service's own
        # stage input, so each conv is held to its own budget.
        x = mel[:, :, None, :]
        h1 = svc1(x)
        e1 = rel_err(h1, reference_conv(
            jnp.pad(x, ((0, 0), (1, 1), (0, 0), (0, 0))), svc1.kernel,
            (1, 1)))
        g1 = jax.nn.gelu(h1)
        h2 = svc2(g1)
        e2 = rel_err(h2, reference_conv(
            jnp.pad(g1, ((0, 0), (1, 1), (0, 0), (0, 0))), svc2.kernel,
            (2, 1)))
        same = bool(jnp.all(out == jax.nn.gelu(h2)[:, :, 0, :]))
        want = (b, (t + 1) // 2, d_model)
        algs = (svc1.plans[svc1.bucket(x.shape)].algorithm,
                svc2.plans[svc2.bucket(g1.shape)].algorithm)
        tols = (budget(algs[0], dtype, "fwd"), budget(algs[1], dtype, "fwd"))
        log(f"[serve] request {i} mel={b}x{t} out={tuple(out.shape)} "
            f"algorithms={algs} err=({e1:.3e}, {e2:.3e}) "
            f"budget=({tols[0]:.1e}, {tols[1]:.1e}) "
            f"smoke_latency={dt * 1e3:.3f} ms")
        check(tuple(out.shape) == want, f"request {i}: shape {out.shape} "
              f"!= {want}")
        check(same, f"request {i}: frontend output differs from its stages")
        check(e1 <= tols[0] and e2 <= tols[1],
              f"request {i}: error ({e1:.3e}, {e2:.3e}) over budget {tols}")
        rows.append({"request": i, "mel": [b, t], "err": [e1, e2]})
    return rows


def phase_four_chips(name: str = "cv4", batch: int = 32,
                     dtype: str = "bfloat16", channel_cap=None,
                     seed: int = 3) -> list:
    """cv4 split over 4 devices (spatial, batch x spatial) vs one device."""
    from jax.sharding import Mesh, NamedSharding
    from repro.parallel.axes import ShardingRules, use_rules
    from repro.parallel.conv import conv_partition_specs
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"need 4 devices, found {len(jax.devices())}")
    spec = layer_spec(name, batch=batch, channel_cap=channel_cap)
    stride = (spec.s_h, spec.s_w)
    x, k = operands(spec, dtype, jax.random.key(seed))

    def loss(out):
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    def run(plan, xa, ka):
        fwd = jax.jit(lambda a, b: conv2d(a, b, stride=stride, plan=plan))
        vg = jax.jit(jax.value_and_grad(
            lambda a, b: loss(conv2d(a, b, stride=stride, plan=plan)),
            argnums=(0, 1)))
        return fwd(xa, ka), vg(xa, ka)

    one = jax.sharding.SingleDeviceSharding(devices[0])
    base_plan = plan_conv2d(spec, dtype=dtype, mode="analytic",
                            partition="none")
    base_out, (base_loss, base_g) = run(
        base_plan, jax.device_put(x, one), jax.device_put(k, one))

    meshes = {
        "spatial": (Mesh(np.asarray(devices), ("model",)), "model"),
        ("batch", "spatial"): (Mesh(np.asarray(devices).reshape(2, 2),
                                    ("data", "model")), ("data", "model")),
    }
    rows = []
    for partition, (mesh, axes) in meshes.items():
        with use_rules(ShardingRules(mesh=mesh, rules={})):
            # plan_conv2d asserts the shardcheck collective contract on
            # this mesh before it returns a partitioned plan.
            plan = plan_conv2d(spec, dtype=dtype, mode="analytic",
                               partition=partition, partition_axis=axes)
            parts = plan.partition if len(plan.partition) > 1 \
                else plan.partition[0]
            p_axes = plan.partition_axes if len(plan.partition_axes) > 1 \
                else plan.partition_axes[0]
            x_spec, k_spec, _ = conv_partition_specs(parts, p_axes)
            xs = jax.device_put(x, NamedSharding(mesh, x_spec))
            ks = jax.device_put(k, NamedSharding(mesh, k_spec))
            t0 = time.perf_counter()
            out, (val, g) = run(plan, xs, ks)
            jax.block_until_ready((out, val, g))
            dt = time.perf_counter() - t0
        # A jit output must split evenly, so the trimmed output of an
        # uneven spatial split (cv4: o_h 109 over 4 rows) leaves the
        # program gathered.  The input gradient comes out of the shard_map
        # transpose in the input's own layout: it shows the work was
        # spread, not done on one device.
        placed = {s.device for s in out.addressable_shards}
        shard = out.addressable_shards[0].data.shape
        g_placed = {s.device for s in g[0].addressable_shards}
        g_shard = g[0].addressable_shards[0].data.shape
        check(len(placed) == 4 and len(g_placed) == 4 and
              math.prod(g_shard) < math.prod(g[0].shape),
              f"{partition}: not spread over 4 devices (output on "
              f"{len(placed)}, input gradient on {len(g_placed)} in shards "
              f"{g_shard} of {g[0].shape})")
        # Compared on the baseline's device, where both results gather.
        out, g = jax.device_put((out, g), one)
        errs = {"fwd": rel_err(out, base_out),
                "loss": abs(float(val) - float(base_loss)) /
                max(abs(float(base_loss)), 1e-30),
                "d_input": rel_err(g[0], base_g[0]),
                "d_kernel": rel_err(g[1], base_g[1])}
        tols = {"fwd": budget(plan.algorithm, dtype, "fwd"),
                "loss": budget(plan.algorithm, dtype, "fwd"),
                "d_input": budget(plan.algorithm, dtype, "grad"),
                "d_kernel": budget(plan.algorithm, dtype, "grad")}
        log(f"[four_chips] {name} n={batch} {dtype} partition={partition} "
            f"axes={plan.partition_axes} algorithm={plan.algorithm} "
            f"devices={len(placed)} out_shard={shard} "
            f"d_input_shard={g_shard} "
            + " ".join(f"{w}_err={e:.3e}" for w, e in errs.items())
            + f" smoke_time_first_call={dt:.3f} s (incl. compile)")
        for w, e in errs.items():
            check(math.isfinite(e) and e <= tols[w],
                  f"{partition}: {w} differs from one device by {e:.3e} "
                  f"> {tols[w]:.1e}")
        rows.append({"partition": str(partition), "errs": errs})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-conv phase")
    args = ap.parse_args(argv)
    # Plans resolved by the serving path persist inside the checkout.
    os.environ.setdefault("REPRO_PLAN_CACHE_DIR", str(ROOT / ".plan_cache"))
    t0 = time.perf_counter()
    cache_counter = CacheCounter()
    jax.monitoring.register_event_listener(cache_counter)
    try:
        device = phase_device(want_count=4 if args.four_chips else 1)
        if args.four_chips:
            phase_four_chips()
        else:
            phase_layers()
            phase_train()
            phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"persistent compile cache {cache_counter.counts}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
