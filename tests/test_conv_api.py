"""The unified conv2d front-end (repro.core.conv_api): every algorithm
cross-checked against ``lax.conv_general_dilated`` over (stride, padding,
dtype), the auto dispatch, and gradients through the MEC custom VJP
against the direct-conv gradient and numerical differences."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ALGORITHMS, MEC_ALGORITHMS, conv2d, conv2d_spec

GRID_ALGS = ["direct", "im2col", "fft", "winograd", "mec", "mec_fused",
             "auto"]


def _rand(shape, seed, dtype=jnp.float32):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _lax_ref(inp, kernel, stride, padding):
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    return lax.conv_general_dilated(
        inp.astype(jnp.float32), kernel.astype(jnp.float32), s, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("algorithm", GRID_ALGS)
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_conv2d_matches_lax(algorithm, stride, padding):
    if algorithm == "winograd" and stride != 1:
        pytest.skip("winograd F(2x2,3x3) is stride-1 only by construction")
    inp = _rand((2, 11, 12, 3), 0)
    ker = _rand((3, 3, 3, 5), 1)             # 3x3 so winograd is eligible
    ref = _lax_ref(inp, ker, stride, padding)
    out = conv2d(inp, ker, stride=stride, padding=padding,
                 algorithm=algorithm)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("algorithm", list(MEC_ALGORITHMS))
def test_conv2d_mec_bf16(algorithm):
    inp = _rand((1, 10, 10, 4), 2, jnp.bfloat16)
    ker = _rand((3, 3, 4, 6), 3, jnp.bfloat16)
    ref = _lax_ref(inp, ker, 1, "SAME")
    out = conv2d(inp, ker, padding="SAME", algorithm=algorithm)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_conv2d_explicit_padding():
    inp = _rand((1, 9, 9, 2), 4)
    ker = _rand((3, 3, 2, 3), 5)
    ref = _lax_ref(inp, ker, 1, [(1, 2), (0, 1)])
    out = conv2d(inp, ker, padding=((1, 2), (0, 1)), algorithm="mec")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    out_int = conv2d(inp, ker, padding=1, algorithm="im2col")
    ref_int = _lax_ref(inp, ker, 1, [(1, 1), (1, 1)])
    np.testing.assert_allclose(np.asarray(out_int), np.asarray(ref_int),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_rejects_bad_requests():
    inp = _rand((1, 8, 8, 2), 6)
    with pytest.raises(ValueError):
        conv2d(inp, _rand((3, 3, 2, 4), 7), stride=2, algorithm="winograd")
    with pytest.raises(ValueError):
        conv2d(inp, _rand((5, 5, 2, 4), 8), algorithm="winograd")
    with pytest.raises(ValueError):
        conv2d(inp, _rand((3, 3, 2, 4), 7), algorithm="toeplitz")
    with pytest.raises(ValueError):  # channel mismatch caught by ConvSpec
        conv2d(inp, _rand((3, 3, 5, 4), 9), algorithm="direct")


def test_auto_dispatch_consults_costmodel():
    from repro.launch.costmodel import (conv2d_algorithm_costs,
                                        pick_conv2d_algorithm)
    inp = _rand((1, 16, 16, 4), 10)
    # 1x1 kernels: lowering is pointless, direct wins
    s1 = conv2d_spec(inp, _rand((1, 1, 4, 8), 11))
    assert pick_conv2d_algorithm(s1, backend="cpu") == "direct"
    # overlapping 3x3 stride-1: MEC saves memory -> picked on CPU
    s3 = conv2d_spec(inp, _rand((3, 3, 4, 8), 12), padding="SAME")
    assert pick_conv2d_algorithm(s3, backend="cpu") == "mec"
    # TPU always routes to the fused no-L-in-HBM Pallas kernel
    assert pick_conv2d_algorithm(s3, backend="tpu") == "mec_fused"
    costs = conv2d_algorithm_costs(s3)
    assert set(costs) == {"direct", "im2col", "mec", "fft", "winograd"}
    assert costs["mec"]["overhead_elems"] < costs["im2col"]["overhead_elems"]
    # every pick is a dispatchable algorithm name
    assert pick_conv2d_algorithm(s3) in ALGORITHMS


# (algorithm, stride, input channels, output channels, dtype, blocking):
# 3 -> 4 channels in f32 on every MEC path and stride, then stride-1 convs
# whose channel counts differ, the cases whose input gradient mec_fused
# runs on its own kernel with the kernel's channel axes swapped, then the
# weight gradient's kernel at each edge of the forward's blocking.
GRAD_CASES = [pytest.param(alg, stride, 3, 4, "float32", None,
                           id=f"{alg}-{stride}")
              for stride in (1, 2)
              for alg in ("mec", "mec_fused")] + [
    pytest.param(alg, 1, i_c, k_c, dtype, None,
                 id=f"{alg}-1-{i_c}to{k_c}-{dtype}")
    for alg in ("mec", "mec_fused")
    for i_c, k_c in ((8, 16), (16, 8))
    for dtype in ("float32", "bfloat16")]
# (name, stride, i_c, k_c, (images, rows, columns), kernel size, w_blk,
#  accumulator budget in bytes, what mec_fused's blocking must show) —
#  a budget None is the device's, w_blk None the planner's.
WGRAD_BLOCKING = [
    # stride 2 with 3 column taps (a 3x3/2 folds to 2: XLA keeps it)
    ("s2", 2, 8, 16, (2, 9, 10), 5, None, None, "plane"),
    # cv4's form: 7x7, stride 2, both strides folded
    ("7x7_s2", 2, 8, 16, (2, 29, 31), 7, None, None, "plane"),
    # o_w = 40 in 16-column blocks, the last one past the row
    ("columns", 1, 4, 8, (2, 9, 40), 3, 16, None, "columns"),
    # 5 images in blocks of 2 or 3, the last one past the batch
    ("ragged_images", 1, 4, 8, (5, 6, 8), 3, None, 102400,
     "ragged_images"),
    # o_h = 21 in 4-row blocks, the last one past the rows
    ("rows", 1, 4, 8, (2, 21, 16), 3, None, 32768, "ragged_rows"),
    # cv12's channels: the kernel's 512 output channels in blocks
    ("channels", 1, 256, 512, (2, 6, 8), 3, None, None, "channels"),
]
GRAD_CASES += [
    pytest.param("mec_fused", stride, i_c, k_c, dtype, blocking,
                 id=f"mec_fused-wgrad-{name}-{dtype}")
    for name, stride, i_c, k_c, *blocking in WGRAD_BLOCKING
    for dtype in ("float32", "bfloat16")]
# bf16: each gradient is rounded once to bf16 (2**-8 relative) from a
# cotangent that is itself bf16; 5 ulps of relative L2 against the f32
# direct conv's gradients of the same bf16 values.
BF16_GRAD_REL_L2 = 5 * 2.0 ** -8


@pytest.mark.parametrize("algorithm,stride,i_c,k_c,dtype,blocking",
                         GRAD_CASES)
def test_mec_grad_matches_direct(algorithm, stride, i_c, k_c, dtype,
                                 blocking, monkeypatch):
    shape, k, plan = (2, 9, 10), 3, None
    if blocking:
        from repro.kernels.mec_conv import (fused_blocks,
                                            fused_weight_grad_refusal)
        from repro.plan import ConvPlan
        from repro.kernels import ops
        shape, k, w_blk, acc, shows = blocking
        if acc is not None:     # rows, then images, fill the accumulator
            monkeypatch.setattr(ops, "accumulator_budget", lambda **_: acc)
        spec = conv2d_spec(jax.ShapeDtypeStruct(shape + (i_c,), dtype),
                           jax.ShapeDtypeStruct((k, k, i_c, k_c), dtype),
                           stride=stride, padding="SAME")
        if w_blk is not None:
            plan = ConvPlan(spec, dtype, "mec_fused", w_blk=w_blk)
        fb = fused_blocks(spec.i_n, spec.i_h, spec.i_w, i_c, k, k, k_c,
                          stride, stride, w_blk or spec.o_w,
                          jnp.dtype(dtype).itemsize)
        _, n_b, n_h, n_w = fb.grid
        assert {
            "plane": fb.hb == spec.o_h,
            "columns": n_w > 1 and spec.o_w % fb.w_blk != 0,
            "ragged_images": n_b > 1 and spec.i_n % fb.nb != 0,
            "ragged_rows": n_h > 1 and spec.o_h % fb.hb != 0,
            "channels": fb.kc < k_c,
        }[shows], fb.describe()
        assert fused_weight_grad_refusal(spec, dtype, w_blk) is None
    inp = _rand(shape + (i_c,), 13, dtype)
    ker = _rand((k, k, i_c, k_c), 14, dtype)
    if blocking:     # outputs of unit scale whatever the kernel's size
        ker = (ker.astype(jnp.float32) / np.sqrt(k * k * i_c)).astype(dtype)

    def loss(alg):
        kw = dict(plan=plan) if alg != "direct" and plan else \
            dict(algorithm=alg)
        return lambda i, k: jnp.sum(jnp.sin(conv2d(
            i, k, stride=stride, padding="SAME", **kw).astype(jnp.float32)))

    gi, gk = jax.grad(loss(algorithm), argnums=(0, 1))(inp, ker)
    ri, rk = jax.grad(loss("direct"), argnums=(0, 1))(
        inp.astype(jnp.float32), ker.astype(jnp.float32))
    assert gi.dtype == gk.dtype == jnp.dtype(dtype)
    for got, ref in ((gi, ri), (gk, rk)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref)
        if dtype == "float32":
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        else:
            rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
            assert rel < BF16_GRAD_REL_L2, rel


def _kernels_in(scope, kernel, fn, *args):
    """The ``pallas_call``s named ``kernel`` that ``fn``'s jaxpr runs
    inside the named scope ``scope``."""
    def walk(jaxpr, stack):
        n = 0
        for eqn in jaxpr.eqns:
            path = f"{stack}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                n += eqn.params["name"] == kernel and scope in path
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                inner = getattr(inner, "jaxpr", inner)
                if inner is not None and hasattr(inner, "eqns"):
                    n += walk(inner, path)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, "")


def _grad_of(algorithm, stride):
    def loss(i, k):
        return jnp.sum(conv2d(i, k, stride=stride, padding="SAME",
                              algorithm=algorithm).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1))


@pytest.mark.parametrize("algorithm,stride,kernels", [
    ("mec_fused", 1, 1), ("mec_fused", 2, 0), ("mec", 1, 0)])
def test_input_grad_runs_on_mec_fused_for_stride_1(algorithm, stride,
                                                    kernels):
    """The input gradient of a stride-1 ``mec_fused`` conv is one
    ``mec_fused`` kernel inside ``mec_input_grad``; a stride-2 conv and
    the pure-JAX ``mec`` keep the XLA path."""
    inp = _rand((2, 9, 10, 8), 15, jnp.bfloat16)
    ker = _rand((3, 3, 8, 16), 16, jnp.bfloat16)
    assert _kernels_in("mec_input_grad", "mec_fused",
                       _grad_of(algorithm, stride), inp, ker) == kernels


@pytest.mark.parametrize("algorithm,stride,shape,kernels", [
    ("mec_fused", 1, (2, 9, 10, 8, 16, 3), 1),
    ("mec_fused", 2, (2, 9, 10, 8, 16, 5), 1),
    ("mec", 1, (2, 9, 10, 8, 16, 3), 0),
    # the f32 dW block of 768 input channels overruns VMEM
    ("mec_fused", 1, (32, 7, 7, 768, 768, 3), 0),
    # a 3x3/2 folds to 2 column taps, under WGRAD_MIN_STRIDED_TAPS
    ("mec_fused", 2, (2, 9, 10, 8, 16, 3), 0)])
def test_weight_grad_runs_on_mec_fused(algorithm, stride, shape, kernels):
    """The weight gradient of a ``mec_fused`` conv, either stride, is one
    ``mec_fused_wgrad`` kernel inside ``mec_weight_grad``; the pure-JAX
    ``mec`` and a geometry ``fused_weight_grad_refusal`` refuses keep
    the XLA path (``_mec_weight_grad``).  Traced, not run."""
    n, h, w, i_c, k_c, k = shape
    inp = jax.ShapeDtypeStruct((n, h, w, i_c), jnp.bfloat16)
    ker = jax.ShapeDtypeStruct((k, k, i_c, k_c), jnp.bfloat16)
    assert _kernels_in("mec_weight_grad", "mec_fused_wgrad",
                       _grad_of(algorithm, stride), inp, ker) == kernels


@pytest.mark.parametrize("algorithm", list(MEC_ALGORITHMS))
def test_mec_precision_reaches_lowered_dots(algorithm):
    """Regression: conv2d used to drop ``precision`` on every MEC
    algorithm (the custom VJP was called without it).  For a bf16 input,
    Precision.HIGHEST must change the lowered dot — and the gradient's
    einsums must carry it too."""
    inp = _rand((1, 8, 8, 3), 30, jnp.bfloat16)
    ker = _rand((3, 3, 3, 4), 31, jnp.bfloat16)

    def lowered(precision, grad=False):
        def f(i, k):
            out = conv2d(i, k, algorithm=algorithm, precision=precision,
                         partition="none")
            return jnp.sum(out.astype(jnp.float32) ** 2)
        fn = jax.grad(f, argnums=(0, 1)) if grad else f
        return jax.jit(fn).lower(inp, ker).as_text()

    assert "HIGHEST" in lowered(jax.lax.Precision.HIGHEST)
    assert "HIGHEST" not in lowered(None)
    assert "HIGHEST" in lowered(jax.lax.Precision.HIGHEST, grad=True)
    assert "HIGHEST" not in lowered(None, grad=True)
    # and the result still matches the oracle
    out = conv2d(inp, ker, algorithm=algorithm,
                 precision=jax.lax.Precision.HIGHEST)
    ref = _lax_ref(inp, ker, 1, "VALID")
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("algorithm", ["fft", "winograd"])
def test_fft_winograd_precision_reaches_lowered_dots(algorithm):
    """Regression: conv2d silently dropped ``precision`` on the fft and
    winograd branches (threaded everywhere else since the MEC fix).
    Mirrors the bf16 MEC check: Precision.HIGHEST must change the
    lowered dot — winograd's transform/product GEMMs and the FFT
    pointwise-multiply both carry it now."""
    inp = _rand((1, 8, 8, 3), 40, jnp.bfloat16)
    ker = _rand((3, 3, 3, 4), 41, jnp.bfloat16)

    def lowered(precision):
        def f(i, k):
            return conv2d(i, k, algorithm=algorithm, precision=precision,
                          partition="none")
        return jax.jit(f).lower(inp, ker).as_text()

    assert "HIGHEST" in lowered(jax.lax.Precision.HIGHEST)
    assert "HIGHEST" not in lowered(None)
    # and the result still matches the oracle
    out = conv2d(inp, ker, algorithm=algorithm,
                 precision=jax.lax.Precision.HIGHEST)
    ref = _lax_ref(inp, ker, 1, "VALID")
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_apply_padding_rejects_negative_pads():
    """Satellite: a negative explicit pad used to surface as an opaque
    jnp.pad trace error; now it is a plain ValueError at the API edge."""
    inp = _rand((1, 8, 8, 2), 42)
    ker = _rand((3, 3, 2, 4), 43)
    for bad in (-1, ((-1, 0), (0, 0)), ((0, 0), (1, -2))):
        with pytest.raises(ValueError, match="non-negative"):
            conv2d(inp, ker, padding=bad, algorithm="direct")
    # zero/positive pads unchanged
    out = conv2d(inp, ker, padding=0, algorithm="direct")
    assert out.shape == (1, 6, 6, 4)


def test_stride_normalizer_is_shared():
    """Satellite: conv_api and spec_of resolve strides through the one
    convspec.normalize_stride — bad strides fail identically."""
    from repro.core.convspec import normalize_stride
    assert normalize_stride(2) == (2, 2)
    assert normalize_stride((1, 3)) == (1, 3)
    assert normalize_stride([2, 1]) == (2, 1)
    with pytest.raises(ValueError, match="strides must be >= 1"):
        normalize_stride(0)
    inp = _rand((1, 8, 8, 2), 44)
    ker = _rand((3, 3, 2, 4), 45)
    with pytest.raises(ValueError, match="strides must be >= 1"):
        conv2d(inp, ker, stride=0, algorithm="direct")
    with pytest.raises(ValueError, match="strides must be >= 1"):
        conv2d(inp, ker, stride=(1, 0), algorithm="mec")


def test_mec_grad_matches_numerical():
    """Central-difference spot check of the custom VJP (both operands)."""
    inp = _rand((1, 6, 6, 2), 15)
    ker = _rand((3, 3, 2, 2), 16)

    def f(i, k):
        return float(jnp.sum(conv2d(i, k, stride=2, padding="VALID",
                                    algorithm="mec") ** 2))

    gi, gk = jax.grad(
        lambda i, k: jnp.sum(conv2d(i, k, stride=2, padding="VALID",
                                    algorithm="mec") ** 2),
        argnums=(0, 1))(inp, ker)
    eps = 1e-3
    rng = np.random.RandomState(17)
    for arr, grad, which in [(inp, gi, 0), (ker, gk, 1)]:
        flat = np.asarray(arr).ravel()
        for idx in rng.choice(flat.size, size=5, replace=False):
            e = np.zeros_like(flat)
            e[idx] = eps
            pert = jnp.asarray(flat + e).reshape(arr.shape)
            pert2 = jnp.asarray(flat - e).reshape(arr.shape)
            args_p = (pert, ker) if which == 0 else (inp, pert)
            args_m = (pert2, ker) if which == 0 else (inp, pert2)
            num = (f(*args_p) - f(*args_m)) / (2 * eps)
            np.testing.assert_allclose(np.asarray(grad).ravel()[idx], num,
                                       rtol=2e-2, atol=2e-2)


def test_training_step_through_mec_is_finite():
    """One SGD step of a tiny conv net through conv2d(algorithm='mec')
    (the examples/train_cnn.py path, miniaturized)."""
    from repro.models.layers import conv2d_layer, init_conv2d
    k1, k2 = jax.random.split(jax.random.key(0))
    params = {"c1": init_conv2d(k1, 3, 3, 1, 4),
              "c2": init_conv2d(k2, 3, 3, 4, 4)}
    imgs = _rand((2, 8, 8, 1), 18)

    def loss_fn(p):
        x = jax.nn.relu(conv2d_layer(p["c1"], imgs, stride=2,
                                     algorithm="mec"))
        x = conv2d_layer(p["c2"], x, stride=2, algorithm="mec")
        return jnp.sum(x ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    assert sum(float(jnp.abs(g).sum()) for g in leaves) > 0
