"""The ``whisper_frontend`` system: the whisper conv frontend served by
``repro.serving.conv_service.whisper_frontend_service``, each request
through its two ``ConvService.execute`` calls, under an open loop of
arrivals from the traffic generator (traffic ``work`` ``serve``).

Besides the contract of ``chipbench/systems``, ``build``, ``make_inputs``
and ``serve`` drive an open loop at any rate (``chipbench/sweep.py``).
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, compare, faults, traffic
from chipbench.control import CONTROL

SPIN_S = 0.0005     # the last stretch before a request is due is spun


@dataclasses.dataclass
class Served:
    latency_s: np.ndarray         # due -> output ready, per request
    late_s: np.ndarray            # generator lateness, per request
    kept: Dict[int, np.ndarray]   # outputs of the checked requests
    seconds: float


def make_inputs(cell: bench.Cell, seed: int) -> Dict[int, List[jax.Array]]:
    """The held inputs, made on the device: for each request size
    (1 to ``max_windows`` windows), ``inputs_held`` seeded standard-normal
    (windows, frames, mels) arrays."""
    cfg, tr = cell.config, cell.traffic
    sizes = range(1, tr["max_windows"] + 1)
    shape = (tr["frames_per_window"], cfg["num_mel_bins"])

    @jax.jit
    def init(k):
        return {b: [jax.random.normal(jax.random.fold_in(k, 100 * b + j),
                                      (b,) + shape).astype(cfg["dtype"])
                    for j in range(tr["inputs_held"])] for b in sizes}
    return init(bench.key(seed, 1))


def build(cell: bench.Cell, seed: int):
    """The warmed service, with every request shape run once so that
    nothing compiles in the window."""
    from repro.serving.conv_service import whisper_frontend_service
    cfg, tr = cell.config, cell.traffic
    with bench.span("bench.plan"):
        frontend, _ = whisper_frontend_service(
            bench.key(seed, 0), cfg["num_mel_bins"], cfg["d_model"],
            [tuple(c) for c in tr["classes"]], dtype=cfg["dtype"])
    for b in range(1, tr["max_windows"] + 1):
        jax.block_until_ready(frontend(jnp.zeros(
            (b, tr["frames_per_window"], cfg["num_mel_bins"]), cfg["dtype"])))
    return frontend


def serve(frontend, inputs, schedule: List[traffic.Request], keep,
          window: bench.Window) -> Served:
    """Send each request when due, or as soon as the one before it is
    done; time it from when it was due to when its output is ready."""
    n = len(schedule)
    latency, late = np.zeros(n), np.zeros(n)
    kept, keep = {}, set(keep)
    with window() as w:
        prev_done = w.t0
        for r in schedule:
            w.tick(r.index)
            due = w.t0 + r.due_s
            if time.perf_counter() < due:
                with bench.span("bench.wait"):
                    time.sleep(max(0.0, due - time.perf_counter() - SPIN_S))
                    while time.perf_counter() < due:
                        pass
            start = time.perf_counter()
            with bench.span("bench.request"):
                out = frontend(inputs[r.windows][r.slot])
                jax.block_until_ready(out)
            done = time.perf_counter()
            latency[r.index] = done - due
            late[r.index] = start - max(due, prev_done)
            prev_done = done
            if r.index in keep:
                # On the host, so that which requests the seed picks for
                # the check leaves the device's peak memory alone.
                kept[r.index] = np.asarray(out)
    return Served(latency, late, kept, w.seconds)


def run(cell: bench.Cell, seed: int, seconds: float,
        window: bench.Window) -> bench.Run:
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "serve":
        raise ValueError(f"whisper_frontend has no work {tr['work']!r}")
    frontend = build(cell, seed)
    window.mark("plan and warm")
    inputs = make_inputs(cell, seed)
    jax.block_until_ready(inputs)
    window.mark("data")
    schedule = traffic.open_loop_schedule(tr, seed, seconds)
    checked = traffic.checked_sample(schedule, tr["checked_requests"], seed)
    served = serve(frontend, inputs, schedule, checked, window)
    mem = bench.memory_peak_bytes(cell.chips)

    ref = bench.reference_of(cfg)
    w1, w2 = ref.weights(bench.key(seed, 0), cfg)
    ref_fn = jax.jit(ref.frontend)
    values = []
    for i in checked:
        r = schedule[i]
        want = ref_fn(inputs[r.windows][r.slot], w1, w2)
        values.append(compare.rel_l2(served.kept[i], want))
    lat_ms = served.latency_s * 1e3
    windows = sum(r.windows for r in schedule)
    late_ms = served.late_s * 1e3
    return bench.Run(
        attempted=len(schedule), failed=0, window_s=served.seconds,
        e2e={"serve_p95_ms": float(np.percentile(lat_ms, 95))},
        work={"requests": len(schedule),
              "traced_requests": len(schedule) - (window.traced_from or 0)},
        checks=bench.checks_against({"serve.rel_l2": max(values)},
                                    cfg["limits"]["serve"]),
        memory_peak_bytes=mem,
        notes=[f"[window] {served.seconds:.3f} s, {len(schedule)} requests "
               f"({windows} windows) offered at {tr['rate_per_s']}/s, "
               f"{window.compiles.count} compiles inside",
               f"[serve] latency ms p50 {np.percentile(lat_ms, 50):.3f} "
               f"p95 {np.percentile(lat_ms, 95):.3f} "
               f"p99 {np.percentile(lat_ms, 99):.3f} max {lat_ms.max():.3f}",
               f"[serve] generator late ms p50 "
               f"{np.percentile(late_ms, 50):.3f} p99 "
               f"{np.percentile(late_ms, 99):.3f} max {late_ms.max():.3f}",
               f"[serve] checked {len(values)} requests, widest rel_l2 "
               f"{max(values):.4e}"],
        window=window)


def control(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The reference in float8 against the reference in float32, on the
    requests a run with this seed checks."""
    cfg, tr = cell.config, cell.traffic
    if tr["work"] != "serve":
        raise ValueError(f"whisper_frontend has no work {tr['work']!r}")
    ref = bench.reference_of(cfg)
    schedule = traffic.open_loop_schedule(tr, seed, seconds)
    checked = traffic.checked_sample(schedule, tr["checked_requests"], seed)
    inputs = make_inputs(cell, seed)
    w1, w2 = ref.weights(bench.key(seed, 0), cfg)
    f = jax.jit(ref.frontend, static_argnums=3)
    worst = 0.0
    for i in checked:
        r = schedule[i]
        mel = inputs[r.windows][r.slot]
        worst = max(worst, compare.rel_l2(f(mel, w1, w2, CONTROL),
                                          f(mel, w1, w2, None)))
    return {"serve.rel_l2": worst}


def _altered_service(build):
    """Each request's last window zeroed."""
    def build_altered(cell, seed):
        serve = build(cell, seed)
        return lambda mel: serve(mel).at[-1].set(0)
    return build_altered


FAULTS = {"answer_altered": ("serve", functools.partial(
    faults.swap, sys.modules[__name__], "build", _altered_service))}

TINY_FRAMES = 40


def tiny(config: Dict, traffic: Dict):
    """8 mel bins into 16 channels, 40-frame windows, at 20 requests a
    second."""
    cfg, tr = copy.deepcopy(config), dict(traffic)
    cfg.update(num_mel_bins=8, d_model=16)
    tr.update(frames_per_window=TINY_FRAMES, rate_per_s=20.0,
              checked_requests=4, inputs_held=2,
              classes=[[n, TINY_FRAMES, 1] for n in (1, 2, 4, 8)])
    return cfg, tr
