"""The readers of the ConvNeXt cell's per-layer metrics on synthetic
trace summaries: device time per step inside ``depthwise`` and
``layer_norm``, and the depthwise convs' share of their floor."""
import types

import pytest

from chipbench import bench, scopes, trace

DEVICE = {"kind": "TPU v5 lite", "count": 1}
# The step's depthwise work at batch 32 in bf16 (systems/convnext.py).
WORK = {"bytes": 1_797_191_424, "flops": 43_901_190_144}
READERS = ("train.dwconv_ms", "train.dwconv_roofline", "train.layer_norm_ms")


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def _summary(scope_kind_s, launches=4):
    d = scopes.ScopedDeviceSummary(
        "/device:TPU:0", sum(scope_kind_s.values()),
        dict.fromkeys(trace.KINDS, 0.0), 0.0, launches, {}, [],
        scope_kind_s)
    return scopes.ScopedTraceSummary(1.0, [d], [])


def _args():
    run = types.SimpleNamespace(work={"steps": 9, "depthwise": WORK,
                                      "dtype": "bfloat16"})
    return dict(run=run, device=DEVICE, config={}, traffic={})


def test_convnext_readers_on_a_synthetic_summary():
    """Milliseconds per step by scope (a layer norm inside the head
    counts), and the depthwise floor: its bytes over HBM bandwidth,
    which bind."""
    s = _summary({(("depthwise", "conv2d"), "conv"): 0.030,
                  (("depthwise",), "other"): 0.002,
                  (("layer_norm",), "other"): 0.012,
                  (("head", "layer_norm"), "other"): 0.0004,
                  (("pointwise", "conv2d"), "conv"): 0.100}, launches=4)
    assert reader("train.dwconv_ms").read(trace=s, **_args()) == \
        pytest.approx(1e3 * 0.032 / 4)
    assert reader("train.layer_norm_ms").read(trace=s, **_args()) == \
        pytest.approx(1e3 * 0.0124 / 4)
    least = WORK["bytes"] / 819e9
    assert least > WORK["flops"] / 197e12
    assert reader("train.dwconv_roofline").read(trace=s, **_args()) == \
        pytest.approx(100 * least * 4 / 0.032)


@pytest.mark.parametrize("name", READERS)
def test_convnext_readers_read_nothing_without_their_scope(name):
    """A trace without scopes, of a program that names nothing, or of
    one with no depthwise conv or layer norm (the ResNet cells) gives no
    reading and raises nothing."""
    plain = trace.TraceSummary(1.0, [trace.DeviceSummary(
        "/device:TPU:0", 0.05, dict.fromkeys(trace.KINDS, 0.01), 0.0, 4,
        {}, [])], [])
    unnamed = _summary({((), "conv"): 0.04, ((), "other"): 0.01})
    resnet = _summary({(("pointwise", "conv2d"), "conv"): 0.04,
                       (("batch_norm",), "other"): 0.01})
    for t in (None, plain, unnamed, resnet):
        assert reader(name).read(trace=t, **_args()) is None


def test_control_rounding_is_the_float8_cast():
    """The control's e4m3 rounding, written out in float32, equals a cast
    to ``float8_e4m3fn`` and back on every e4m3 value, every midpoint
    between neighbours (ties to even) and a float32 on each side of it,
    and on random values in +-448; scaled, no tensor leaves a NaN."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    ref = bench.load_module(bench.HERE / "configs" / "convnext_b.py")
    grid = np.arange(256, dtype=np.uint8).view(ml_dtypes.float8_e4m3fn)
    e4m3 = np.unique(grid.astype(np.float32))
    e4m3 = e4m3[np.isfinite(e4m3)]
    mids = ((e4m3[1:].astype(np.float64) + e4m3[:-1]) / 2).astype(np.float32)
    rng = np.random.default_rng(0)
    q = np.concatenate([
        e4m3, mids, np.nextafter(mids, np.float32(np.inf)),
        np.nextafter(mids, np.float32(-np.inf)),
        rng.uniform(-448, 448, 20_000).astype(np.float32),
        (rng.standard_normal(20_000) * 2.0 ** -7).astype(np.float32)])
    got = np.asarray(jax.jit(ref.round_e4m3)(jnp.asarray(q)))
    want = q.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    np.testing.assert_array_equal(got, want)
    for x in (jnp.zeros(8), jnp.full(8, 1e-40), jnp.array([3e38, -1.0])):
        assert bool(jnp.all(jnp.isfinite(ref.round_fp8(x))))
