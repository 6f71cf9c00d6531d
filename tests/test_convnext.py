"""ConvNeXt (``repro.models.convnext``) against the plain float32 reference
of the benchmark's ``convnext_b`` configuration, on the CPU at the
system's tiny size with stochastic depth on; and the whole configuration's
published shapes, counted without running it."""
import importlib.util
import json
import math
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import convnext
from repro.models.layers import gelu, layer_norm

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.systems import convnext as system  # noqa: E402

CONFIG = json.loads((ROOT / "chipbench/configs/convnext_b.json").read_text())
TRAFFIC = json.loads((ROOT / "chipbench/traffic/train_b32.json").read_text())
# One block per stage, dims / 16, 32 x 32 images, 10 classes, batch 4,
# float32: the size the benchmark's own CPU tests run.
TINY, TINY_TRAFFIC = system.tiny(CONFIG, TRAFFIC)
BATCH = TINY_TRAFFIC["batch"]


def _reference():
    path = ROOT / "chipbench/configs/convnext_b.py"
    spec = importlib.util.spec_from_file_location("convnext_b_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_pair():
    """The model's and the reference's logits, loss and gradient on one
    seeded float32 batch with stochastic depth drawn from one key, and
    both trained 3 AdamW steps."""
    ref = _reference()
    params = system.init_state(jax.random.key(3), TINY)
    plans = convnext.plan(TINY, BATCH, jnp.float32)
    images = jax.random.normal(jax.random.key(4), (BATCH, 32, 32, 3))
    labels = jax.random.randint(jax.random.key(5), (BATCH,), 0, 10)
    key = jax.random.key(6)

    def model_loss(p):
        logits = convnext.forward(p, images, plans, key)
        return convnext.cross_entropy(logits, labels), logits

    def ref_loss(p):
        factors = ref.keep_factors(key, TINY, BATCH)
        logits = ref.forward(p, images, factors, TINY)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)), \
            logits

    got = jax.jit(jax.value_and_grad(model_loss, has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(ref_loss, has_aux=True))(params)
    return ref, params, plans, images, labels, key, got, want


# Both sides compute in float32; what remains is summation order (XLA's
# grouped and 1x1 convs at default precision against HIGHEST ones, the
# layer norms' float32 sums).  The widest gap seen is 1e-6 of a leaf's
# largest entry.
TOL = 1e-4


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


def test_tiny_net_drops_blocks_and_plans_depthwise_on_direct(tiny_pair):
    ref, _, plans, _, _, key, _, _ = tiny_pair
    dw = [plans["stages"][i][0]["dw"] for i in range(4)]
    assert all(p.algorithm == "direct" and p.spec.groups == p.spec.i_c
               for p in dw)
    factors = np.asarray(ref.keep_factors(key, TINY, BATCH))
    assert (factors[1:] == 0).any() and (factors[1:] > 1).any()


def test_logits_and_loss_match_reference(tiny_pair):
    *_, ((loss, logits), _), ((want_loss, want_logits), _) = tiny_pair
    _close(logits, want_logits)
    assert abs(float(loss) - float(want_loss)) <= TOL * abs(float(want_loss))


def test_every_gradient_leaf_matches_reference(tiny_pair):
    *_, (_, grads), (_, want) = tiny_pair
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree.leaves(want))
    for (path, g), w in zip(leaves, jax.tree.leaves(want)):
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        _close(g, w)


def test_three_step_update_matches_reference(tiny_pair):
    """The model's ``train_step`` and the reference's AdamW over three
    batches, stochastic depth drawn anew each step from one key."""
    from repro.optim import adamw
    ref, params, plans, images, labels, key, _, _ = tiny_pair
    opt = TINY_TRAFFIC["optimizer"]
    step = jax.jit(convnext.train_step(plans, adamw.AdamWConfig(**opt)))
    batches = [(images * (1 + 0.5 * t), jnp.roll(labels, t))
               for t in range(3)]
    p, state, losses = params, adamw.init(params), []
    for x, y in batches:
        p, state, value = step(p, state, x, y, key)
        losses.append(float(value))
    want = ref.train(params, batches, opt, TINY, key)
    np.testing.assert_allclose(losses, want["losses"], rtol=TOL)
    moved = [float(jnp.linalg.norm(a - b)) for a, b in
             zip(jax.tree.leaves(p), jax.tree.leaves(params))]
    np.testing.assert_allclose(moved, want["update_norms"], rtol=1e-3)


def test_layer_norm_and_gelu_keep_the_input_dtype():
    x = jax.random.normal(jax.random.key(0), (4, 3, 3, 5)) * 2.0 + 1.0
    p = {"scale": jnp.full(5, 1.5), "bias": jnp.full(5, -0.5)}
    y = layer_norm(x.astype(jnp.bfloat16), p, 1e-6)
    x64 = np.asarray(x.astype(jnp.bfloat16), np.float64)
    mean, var = x64.mean(-1, keepdims=True), x64.var(-1, keepdims=True)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               (x64 - mean) / np.sqrt(var + 1e-6) * 1.5 - 0.5,
                               atol=3e-2)
    g = gelu(x.astype(jnp.bfloat16))
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float64), np.asarray(
        jax.nn.gelu(x, approximate=False)), atol=3e-2)


def test_convnext_b_has_published_shapes():
    """Liu et al. 2022, ConvNeXt-B: [3, 3, 27, 3] blocks over dims [128,
    256, 512, 1024], 88,591,464 parameters and 15,354,729,472 multiply-
    adds an image forward at 224 x 224 (the paper's 15.4 G); 72 1x1
    convs, 36 depthwise, and the benchmark's training yardstick of
    92,089,841,664 operations an image."""
    layers = convnext.convs(CONFIG, 32)
    assert len(layers) == 1 + 3 + 3 * 36
    assert sum(k[0] == 1 for _, _, k, _, _ in layers) == 72
    assert [p[-1] for p, *_ in layers].count("dw") == 36
    assert [sum(1 for b in convnext.blocks(CONFIG) if b[0] == i)
            for i in range(4)] == [3, 3, 27, 3]
    params = jax.eval_shape(lambda k: convnext.init(k, CONFIG),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(params)) == \
        88_591_464
    assert params["fc"]["w"].shape == (1024, 1000)
    assert system.forward_flops(CONFIG, 1) == 2 * 15_354_729_472
    assert system.train_flops(CONFIG, 32) == 32 * 92_089_841_664
    assert len(system.pointwise_convs(CONFIG, 32)) == 72
    # Input, kernel and output of 36 depthwise convs, three times, bf16:
    # 1.79 GB a step at batch 32.
    assert round(system.depthwise_work(CONFIG, 32, 2)["bytes"] / 1e9,
                 2) == 1.80
    assert convnext.drop_rates(CONFIG)[0] == 0.0
    assert convnext.drop_rates(CONFIG)[-1] == pytest.approx(0.5)


def test_reference_takes_shapes_from_the_configuration():
    """The reference derives every parameter's shape from the
    configuration alone, agrees with the model's ``init`` on the whole
    ConvNeXt-B, and refuses a tree with one width wrong."""
    ref = _reference()
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    assert sum(math.prod(s) for s in jax.tree.leaves(
        ref.shapes(CONFIG), is_leaf=is_shape)) == 88_591_464
    ref.check_shapes(jax.eval_shape(lambda k: convnext.init(k, CONFIG),
                                    jax.random.key(0)), CONFIG)
    tiny = convnext.init(jax.random.key(0), TINY)
    ref.check_shapes(tiny, TINY)
    dw = tiny["stages"][2][0]["dw"]
    dw["w"] = dw["w"][..., :-1]
    with pytest.raises(ValueError, match="dw"):
        ref.check_shapes(tiny, TINY)
