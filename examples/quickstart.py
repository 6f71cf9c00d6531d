"""Quickstart: the MEC convolution engine (Cho & Brand, ICML 2017),
every algorithm through the one ``conv2d`` front-end.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax.numpy as jnp

from repro.core import conv2d, conv2d_spec
from repro.core.memory import ALL_OVERHEADS
from repro.launch.costmodel import pick_conv2d_algorithm

# --- a cv7-like layer: 3x3 kernel, stride 1, SAME padding -----------------
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(1, 56, 56, 8).astype(np.float32))
k = jnp.asarray(rng.randn(3, 3, 8, 16).astype(np.float32))

ref = conv2d(x, k, padding="SAME", algorithm="direct")
print("output:", ref.shape)
for name, kwargs in [
    ("mec (Solution A)", dict(algorithm="mec", solution="A")),
    ("mec (Solution B)", dict(algorithm="mec", solution="B")),
    ("im2col", dict(algorithm="im2col")),
    ("fft", dict(algorithm="fft")),
    ("winograd F(2x2,3x3)", dict(algorithm="winograd")),
    ("Pallas MEC kernel (fused)", dict(algorithm="mec_fused")),
]:
    err = float(jnp.max(jnp.abs(conv2d(x, k, padding="SAME", **kwargs) - ref)))
    print(f"  {name:28s} max|err| vs direct = {err:.2e}")

# --- the paper's memory story (Eqs. 2-4) ----------------------------------
spec = conv2d_spec(x, k, padding="SAME")
print(f"\nauto dispatch on this geometry -> {pick_conv2d_algorithm(spec)!r}")
print("lowered-matrix overhead (f32 MB):")
for alg, f in ALL_OVERHEADS.items():
    print(f"  {alg:10s} {f(spec) * 4 / 2**20:8.2f} MB")

# --- the planner (DESIGN.md §7): inspect, serialize, replay ---------------
from repro.plan import ConvPlan, plan_conv2d  # noqa: E402

plan = plan_conv2d(spec)                      # analytic policy (default)
print("\n" + plan.explain())
replayed = ConvPlan.from_json(plan.to_json())  # plans are values
out = conv2d(x, k, padding="SAME", plan=replayed)
print("replayed-plan output matches auto kwargs:",
      bool(jnp.all(out == conv2d(x, k, padding='SAME', algorithm='auto'))))
