"""ResNet (``repro.models.resnet``) against the plain float32 reference of
the benchmark's ``resnet101`` configuration, on the CPU at a tiny size;
and the whole configuration's published shapes, counted without running
it."""
import importlib.util
import json
import math
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import resnet
from repro.models.layers import batch_norm

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "chipbench/configs/resnet101.json").read_text())

# One block per stage at a sixteenth of the widths: each stage after the
# first opens with a stride-2 3x3 conv and a stride-2 1x1 projection.
TINY = dict(CONFIG, image_size=32, num_classes=10, depths=[1, 1, 1, 1],
            widths=[4, 8, 16, 32], stem=dict(CONFIG["stem"], width=4))
BATCH = 4


def _reference():
    path = ROOT / "chipbench/configs/resnet101.py"
    spec = importlib.util.spec_from_file_location("resnet101_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_pair():
    """The model's and the reference's logits, loss, gradient and running
    statistics on one seeded float32 batch, with CPU plans."""
    ref = _reference()
    params, stats = resnet.init(jax.random.key(3), TINY)
    plans = resnet.plan(TINY, BATCH, jnp.float32)
    images = jax.random.normal(jax.random.key(4), (BATCH, 32, 32, 3))
    labels = jax.random.randint(jax.random.key(5), (BATCH,), 0, 10)

    def model_loss(p):
        logits, new = resnet.forward(p, stats, images, plans)
        return resnet.cross_entropy(logits, labels), (logits, new)

    def ref_loss(p):
        logits, new = ref.forward(p, stats, images, TINY)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)), \
            (logits, new)

    got = jax.jit(jax.value_and_grad(model_loss, has_aux=True))(params)
    want = jax.jit(jax.value_and_grad(ref_loss, has_aux=True))(params)
    return plans, got, want


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


def test_tiny_net_has_strided_convs(tiny_pair):
    plans = tiny_pair[0]
    strided = {(p.spec.k_h, p.spec.s_h) for p in
               jax.tree.leaves(plans["stages"],
                               is_leaf=lambda x: hasattr(x, "spec"))}
    assert {(3, 2), (1, 2), (3, 1), (1, 1)} <= strided


# Both sides compute in float32.  What remains is summation order (the
# planner's CPU picks, MEC's per-row GEMMs and XLA's direct conv, against
# one HIGHEST-precision XLA conv), carried through five batch norms over as
# few as 4 values a channel; the widest gap seen is 2e-4 of a leaf's
# largest entry, on a batch-norm scale whose gradient is a difference of
# near-equal sums.
TOL = 2e-3


def test_logits_and_loss_match_reference(tiny_pair):
    _, ((loss, (logits, _)), _), ((want_loss, (want_logits, _)), _) = \
        tiny_pair
    _close(logits, want_logits, TOL)
    assert abs(float(loss) - float(want_loss)) <= TOL * abs(float(want_loss))


def test_every_gradient_leaf_matches_reference(tiny_pair):
    _, (_, grads), (_, want) = tiny_pair
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree.leaves(want))
    for (path, g), w in zip(leaves, jax.tree.leaves(want)):
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        _close(g, w, TOL)


def test_running_statistics_match_reference(tiny_pair):
    _, ((_, (_, stats)), _), ((_, (_, want)), _) = tiny_pair
    assert jax.tree.structure(stats) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(stats), jax.tree.leaves(want)):
        _close(got, ref, TOL)


def test_batch_norm_keeps_torchvision_statistics():
    x = jax.random.normal(jax.random.key(0), (4, 3, 3, 5)) * 2.0 + 1.0
    p = {"scale": jnp.full(5, 1.5), "bias": jnp.full(5, -0.5)}
    stats = {"mean": jnp.zeros(5), "var": jnp.ones(5)}
    y, new = batch_norm(x.astype(jnp.bfloat16), p, stats)
    x64 = np.asarray(x.astype(jnp.bfloat16), np.float64).reshape(-1, 5)
    mean, var = x64.mean(0), x64.var(0)
    assert y.dtype == jnp.bfloat16
    # bfloat16 output: 8 bits of mantissa on values of a few units.
    np.testing.assert_allclose(
        np.asarray(y, np.float64).reshape(-1, 5),
        (x64 - mean) / np.sqrt(var + 1e-5) * 1.5 - 0.5, atol=3e-2)
    np.testing.assert_allclose(new["mean"], 0.1 * mean, rtol=1e-5)
    np.testing.assert_allclose(new["var"], 0.9 + 0.1 * x64.var(0, ddof=1),
                               rtol=1e-5)


def test_resnet101_has_published_shapes():
    """He et al. 2016, Table 1, 101-layer column, in torchvision's form:
    104 convs (70 of them 1x1), [3, 4, 23, 3] blocks, 44,549,160
    parameters, 224 x 224 x 3 images and 1,000 classes; and the
    benchmark's training yardstick of 46.57 GFLOP an image."""
    sys.path.insert(0, str(ROOT))
    from chipbench.systems import resnet as system
    convs = resnet.convs(CONFIG, 32)
    assert len(convs) == 104
    assert sum(k[0] == 1 for _, _, k, _, _ in convs) == 70
    assert [sum(1 for b in resnet.blocks(CONFIG) if b[0] == i)
            for i in range(4)] == [3, 4, 23, 3]
    assert convs[0][1] == (32, 224, 224, 3)
    params, stats = jax.eval_shape(lambda k: resnet.init(k, CONFIG),
                                   jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(params)) == \
        44_549_160
    assert params["fc"]["w"].shape == (2048, 1000)
    assert len(jax.tree.leaves(stats)) == 2 * 104
    assert round(system.train_flops(CONFIG, 32) / 32 / 1e9, 2) == 46.57
    assert len(system.pointwise_convs(CONFIG, 32)) == 70


def test_reference_takes_shapes_from_the_configuration():
    """The reference derives every parameter's and running statistic's
    shape from the configuration alone, agrees with the model's ``init``
    on the whole ResNet-101, and refuses a tree with one width wrong."""
    ref = _reference()
    want_params, want_stats = ref.shapes(CONFIG)
    is_shape = lambda x: isinstance(x, tuple)  # noqa: E731
    assert sum(math.prod(s) for s in jax.tree.leaves(
        want_params, is_leaf=is_shape)) == 44_549_160
    params, stats = jax.eval_shape(lambda k: resnet.init(k, CONFIG),
                                   jax.random.key(0))
    ref.check_shapes(params, stats, CONFIG)
    tiny_params, tiny_stats = resnet.init(jax.random.key(0), TINY)
    ref.check_shapes(tiny_params, tiny_stats, TINY)
    conv3 = tiny_params["stages"][2][0]["conv3"]
    conv3["w"] = conv3["w"][..., :-1]
    with pytest.raises(ValueError, match="conv3"):
        ref.check_shapes(tiny_params, tiny_stats, TINY)
