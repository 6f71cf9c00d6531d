"""Grouped convs through ``conv2d`` and the planner: the group count is the
input's channels over the kernel's, only ``direct`` runs a grouped conv
(forward and both gradients XLA's), every planning mode and backend puts
it there, the other algorithms and the Pallas kernel refuse it with a
reason, and a dense conv's plan key, serialization and cost are as before
groups existed."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import conv2d, conv2d_spec
from repro.core.convspec import ConvSpec
from repro.kernels.mec_conv import fused_refusal
from repro.plan import ConvPlan, plan_conv2d, spec_key
from repro.plan.cache import reset_global_plan_cache

C = 6


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    reset_global_plan_cache()
    yield tmp_path
    reset_global_plan_cache()


def _grouped(groups, k=3):
    return ConvSpec(2, 9, 9, C, k, k, C, 1, 1, groups)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("groups", [1, 2, C])
def test_conv2d_grouped_matches_lax(groups, stride):
    """Forward and both gradients, float32, SAME padding, against XLA's
    conv with ``feature_group_count``."""
    x = _rand((2, 9, 10, C), 0)
    w = _rand((3, 3, C // groups, 4 * groups if groups < C else C), 1)

    def ours(x, w):
        return conv2d(x, w, stride=stride, padding="SAME")

    def ref(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=lax.Precision.HIGHEST)

    np.testing.assert_allclose(ours(x, w), ref(x, w), rtol=1e-5, atol=1e-5)
    g = _rand(ref(x, w).shape, 2)
    got = jax.vjp(ours, x, w)[1](g)
    want = jax.vjp(ref, x, w)[1](g)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)
    assert conv2d_spec(x, w, stride=stride, padding="SAME").groups == groups


def test_kernel_channels_must_divide_the_input():
    with pytest.raises(ValueError, match="whole multiple"):
        conv2d(_rand((1, 8, 8, C), 3), _rand((3, 3, 4, 4), 4))
    with pytest.raises(ValueError, match="groups must divide"):
        ConvSpec(1, 8, 8, C, 3, 3, 4, 1, 1, 3).validate()


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("mode", ["analytic", "cached", "measured"])
def test_grouped_spec_is_planned_on_direct(fresh_cache, mode, backend):
    plan = plan_conv2d(_grouped(C), mode=mode, backend=backend, iters=1,
                       warmup=0)
    assert plan.algorithm == "direct"
    assert plan.spec.groups == C
    assert "only direct runs a grouped conv" in plan.explain()


@pytest.mark.parametrize("algorithm", ["mec", "im2col", "fft", "winograd",
                                       "mec_fused"])
def test_grouped_spec_refuses_every_other_algorithm(fresh_cache, algorithm):
    spec = _grouped(2)
    with pytest.raises(ValueError, match="groups=2"):
        ConvPlan(spec, "float32", algorithm)
    with pytest.raises(ValueError, match="no timeable candidate"):
        plan_conv2d(spec, mode="measured", candidates=(algorithm,),
                    iters=1, warmup=0)
    x, w = _rand((2, 9, 9, C), 5), _rand((3, 3, C // 2, C), 6)
    with pytest.raises(ValueError, match="groups=2"):
        conv2d(x, w, algorithm=algorithm)


def test_fused_refusal_gives_the_groups_reason_first():
    dense = ConvSpec(2, 16, 16, 64, 3, 3, 64, 1, 1)
    assert fused_refusal(dense, "bfloat16") is None
    for groups in (2, 64):
        spec = ConvSpec(2, 16, 16, 64, 3, 3, 64, 1, 1, groups)
        for w_blk in (None, 8):
            assert fused_refusal(spec, "bfloat16", w_blk).startswith(
                f"groups={groups}")


# Plan keys of today's geometries: the cell of a cached plan, the
# committed plans.json and every report key on them.
@pytest.mark.parametrize("spec,key", [
    (ConvSpec(32, 230, 230, 3, 7, 7, 64, 2, 2),
     "32x230x230x3-k7x7x64-s2x2"),
    (ConvSpec(32, 58, 58, 64, 3, 3, 64, 1, 1), "32x58x58x64-k3x3x64-s1x1"),
    (ConvSpec(32, 56, 56, 256, 1, 1, 64, 1, 1),
     "32x56x56x256-k1x1x64-s1x1"),
    (ConvSpec(32, 56, 56, 128, 2, 2, 256, 2, 2),
     "32x56x56x128-k2x2x256-s2x2"),
    (ConvSpec(32, 62, 62, 128, 7, 7, 128, 1, 1, 128),
     "32x62x62x128-k7x7x128-s1x1-g128"),
])
def test_spec_key_is_unchanged_for_dense_specs(spec, key):
    assert spec_key(spec) == key
    plan = ConvPlan(spec, "bfloat16", "direct")
    assert ConvPlan.from_json(plan.to_json()) == plan
    assert ("groups" in plan.to_dict()["spec"]) == (spec.groups > 1)


def test_grouped_costs_divide_by_groups():
    from repro.core.memory import conv_flops
    from repro.launch.costmodel import conv2d_algorithm_costs
    dense, grouped = _grouped(1), _grouped(C)
    assert conv_flops(dense) == C * conv_flops(grouped)
    assert set(conv2d_algorithm_costs(grouped)) == {"direct"}


def test_grouped_spec_splits_by_batch_not_channel():
    from repro.parallel.conv import partition_viable
    assert partition_viable(_grouped(1), "channel", 2)
    assert not partition_viable(_grouped(2), "channel", 2)
    assert partition_viable(_grouped(2), "batch", 2)
