"""Distributed conv execution layer (repro.parallel.conv, DESIGN.md §6):
property-style equivalence against the single-device conv2d oracle on a
1-device mesh, a 4-fake-device subprocess sweep over {partition, stride,
kernel, dtype} including jax.grad through the halo exchange, the
rules-aware conv_api routing, the partition cost model, and the
make_host_mesh regression."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.conv_api import conv2d
from repro.core.convspec import ConvSpec
from repro.launch.costmodel import conv_partition_costs, pick_conv_partition
from repro.launch.mesh import make_host_mesh
from repro.parallel.axes import ShardingRules, use_rules
from repro.parallel.conv import (COMPOSITE_PARTITIONS, PARTITIONS,
                                 conv_partition_specs, default_axis,
                                 normalize_partition, partition_name,
                                 partition_viable, sharded_conv2d,
                                 spatial_halo_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(shape, seed, dtype=jnp.float32):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _oracle(inp, kernel, stride):
    return conv2d(inp, kernel, stride=stride, algorithm="direct",
                  partition="none")


# ---------------------------------------------------------------------------
# make_host_mesh regression (satellite): explicit shape without axes used
# to pass axes=None straight into Mesh() and crash.
# ---------------------------------------------------------------------------

def test_make_host_mesh_shape_without_axes():
    mesh = make_host_mesh(shape=(1,))
    assert mesh.axis_names == ("ax0",)
    mesh2 = make_host_mesh(shape=(1, 1))
    assert mesh2.axis_names == ("ax0", "ax1")
    assert make_host_mesh(shape=(1,), axes=("tp",)).axis_names == ("tp",)
    assert make_host_mesh().axis_names == ("data",)
    with pytest.raises(ValueError):
        make_host_mesh(shape=(1, 1), axes=("only_one",))
    with pytest.raises(ValueError):
        make_host_mesh(shape=(jax.device_count() + 1,))


# ---------------------------------------------------------------------------
# property-style oracle equivalence on a 1-device mesh (the shard_map /
# ppermute path runs for real; multi-device behaviour is covered by the
# subprocess sweep below)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
       st.sampled_from(PARTITIONS),
       st.sampled_from(["float32", "bfloat16"]),
       st.integers(1, 3), st.integers(0, 2), st.integers(0, 3))
def test_sharded_matches_oracle_property(k, s, partition, dtype, mult,
                                         extra_w, seed):
    i_h = s * (k + mult)               # spatial-viable: s | i_h, halo <= i_h
    i_w = i_h + extra_w
    if i_w < k:
        i_w = k
    inp = _rand((2, i_h, i_w, 3), seed, dtype)
    ker = _rand((k, k, 3, 4), seed + 100, dtype)
    mesh = make_host_mesh(shape=(1,))
    out = sharded_conv2d(inp, ker, stride=s, algorithm="mec",
                         partition=partition, mesh=mesh)
    ref = _oracle(inp, ker, s)
    assert out.shape == ref.shape
    tol = 5e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([3, 5]), st.sampled_from([1, 2]),
       st.sampled_from(PARTITIONS), st.integers(0, 3))
def test_sharded_grad_matches_oracle_property(k, s, partition, seed):
    i_h = s * (k + 2)
    inp = _rand((2, i_h, i_h + 1, 2), seed, jnp.float32)
    ker = _rand((k, k, 2, 4), seed + 50, jnp.float32)
    mesh = make_host_mesh(shape=(1,))

    def loss(fn):
        return lambda i, kk: jnp.sum(jnp.sin(fn(i, kk)))

    gi, gk = jax.grad(loss(lambda i, kk: sharded_conv2d(
        i, kk, stride=s, algorithm="mec", partition=partition, mesh=mesh)),
        argnums=(0, 1))(inp, ker)
    ri, rk = jax.grad(loss(lambda i, kk: _oracle(i, kk, s)),
                      argnums=(0, 1))(inp, ker)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(ri),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               rtol=2e-4, atol=2e-4)


def test_sharded_conv2d_every_algorithm_backend():
    """Partitioning composes with every conv2d algorithm backend."""
    inp = _rand((2, 12, 12, 3), 0)
    ker = _rand((3, 3, 3, 4), 1)
    mesh = make_host_mesh(shape=(1,))
    ref = _oracle(inp, ker, 1)
    for alg in ("direct", "im2col", "fft", "winograd", "mec", "mec_fused",
                "auto"):
        out = sharded_conv2d(inp, ker, algorithm=alg, partition="spatial",
                             mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"algorithm={alg}")


def test_explicit_partition_rejects_bad_geometry():
    mesh = make_host_mesh(shape=(1,))
    inp = _rand((1, 9, 9, 2), 2)
    ker = _rand((3, 3, 2, 4), 3)
    # i_h=9, stride 2: per-device rows are not a stride multiple
    with pytest.raises(ValueError):
        sharded_conv2d(inp, ker, stride=2, partition="spatial", mesh=mesh)
    with pytest.raises(ValueError):
        sharded_conv2d(inp, ker, partition="toeplitz", mesh=mesh)


# ---------------------------------------------------------------------------
# composite (2-D) partitions: normalization, axis resolution, specs, and
# oracle equivalence on a (1,1) 2-axis mesh (the real 2x2 sweep runs in
# the subprocess test below)
# ---------------------------------------------------------------------------

def test_normalize_partition_and_name_roundtrip():
    assert normalize_partition("spatial") == ("spatial",)
    assert normalize_partition(("batch", "spatial")) == ("batch", "spatial")
    assert normalize_partition(["batch", "channel"]) == ("batch", "channel")
    for comp in COMPOSITE_PARTITIONS:
        assert normalize_partition(partition_name(comp)) == comp
    assert partition_name("batch") == "batch"
    with pytest.raises(ValueError):
        normalize_partition(("spatial", "batch"))   # non-canonical order
    with pytest.raises(ValueError):
        normalize_partition(("batch", "batch"))
    with pytest.raises(ValueError):
        normalize_partition(("batch", "toeplitz"))
    with pytest.raises(ValueError):
        normalize_partition(("batch", "spatial", "channel"))


def test_composite_partition_viability():
    spec = ConvSpec(4, 16, 16, 3, 3, 3, 8, 1, 1)
    assert partition_viable(spec, ("batch", "spatial"), (4, 4))
    assert not partition_viable(spec, ("batch", "spatial"), (3, 4))
    assert not partition_viable(spec, ("batch", "spatial"), (4, 5))
    assert partition_viable(spec, ("batch", "channel"), (2, 8))
    assert not partition_viable(spec, ("batch", "channel"), (2, 3))
    assert partition_viable(spec, ("spatial", "channel"), (2, 2))
    # component count must match the n_dev tuple
    with pytest.raises(ValueError):
        partition_viable(spec, ("batch", "spatial"), 4)
    with pytest.raises(ValueError):
        partition_viable(spec, "batch", (2, 2))


def test_composite_default_axis_resolution():
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    assert default_axis(("batch", "spatial"), mesh) == ("data", "model")
    assert default_axis(("batch", "channel"), mesh) == ("data", "model")
    # both spatial and channel prefer the TP axis; the second component
    # falls through to the only unclaimed axis
    assert default_axis(("spatial", "channel"), mesh) == ("model", "data")
    # a 1-D mesh cannot host two distinct sub-axes
    with pytest.raises(ValueError):
        default_axis(("batch", "spatial"), make_host_mesh(shape=(1,)))


def test_composite_conv_partition_specs():
    from jax.sharding import PartitionSpec as P
    assert conv_partition_specs(("batch", "spatial"), ("data", "model")) == \
        (P("data", "model"), P(None, None, None, None),
         P("data", "model", None, None))
    assert conv_partition_specs(("batch", "channel"), ("data", "model")) == \
        (P("data", None), P(None, None, None, "model"),
         P("data", None, None, "model"))
    assert conv_partition_specs(("spatial", "channel"), ("model", "data")) == \
        (P(None, "model"), P(None, None, None, "data"),
         P(None, "model", None, "data"))
    with pytest.raises(ValueError):
        conv_partition_specs(("batch", "spatial"), "data")


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
       st.sampled_from(COMPOSITE_PARTITIONS),
       st.sampled_from(["float32", "bfloat16"]), st.integers(0, 3))
def test_composite_matches_oracle_property(k, s, partition, dtype, seed):
    i_h = s * (k + 2)
    inp = _rand((2, i_h, i_h + 1, 3), seed, dtype)
    ker = _rand((k, k, 3, 4), seed + 100, dtype)
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    out = sharded_conv2d(inp, ker, stride=s, algorithm="mec",
                         partition=partition, mesh=mesh)
    ref = _oracle(inp, ker, s)
    assert out.shape == ref.shape
    tol = 5e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([3, 5]), st.sampled_from([1, 2]),
       st.sampled_from(COMPOSITE_PARTITIONS), st.integers(0, 2))
def test_composite_grad_matches_oracle_property(k, s, partition, seed):
    i_h = s * (k + 2)
    inp = _rand((2, i_h, i_h + 1, 2), seed, jnp.float32)
    ker = _rand((k, k, 2, 4), seed + 50, jnp.float32)
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))

    def loss(fn):
        return lambda i, kk: jnp.sum(jnp.sin(fn(i, kk)))

    gi, gk = jax.grad(loss(lambda i, kk: sharded_conv2d(
        i, kk, stride=s, algorithm="mec", partition=partition, mesh=mesh)),
        argnums=(0, 1))(inp, ker)
    ri, rk = jax.grad(loss(lambda i, kk: _oracle(i, kk, s)),
                      argnums=(0, 1))(inp, ker)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(ri),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               rtol=2e-4, atol=2e-4)


def test_auto_with_bad_explicit_axis_raises():
    """partition='auto' must not swallow an explicit-axis typo into a
    silent single-device fallback."""
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    inp, ker = _rand((2, 8, 8, 2), 20), _rand((3, 3, 2, 4), 21)
    with pytest.raises(ValueError, match="not in mesh axes"):
        sharded_conv2d(inp, ker, partition="auto", axis="bogus", mesh=mesh)
    with pytest.raises(ValueError, match="not in mesh axes"):
        sharded_conv2d(inp, ker, partition="auto",
                       axis=("data", "bogus"), mesh=mesh)
    with pytest.raises(ValueError, match="distinct"):
        sharded_conv2d(inp, ker, partition="auto",
                       axis=("data", "data"), mesh=mesh)
    # a 1-tuple axis is the same as its string
    out = sharded_conv2d(inp, ker, partition="batch", axis=("data",),
                         mesh=mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(inp, ker, 1)),
                               rtol=1e-4, atol=1e-4)


def test_composite_explicit_rejects_bad_geometry_and_axes():
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    inp = _rand((3, 9, 9, 2), 11)          # i_n=3: 1-way batch still fine
    ker = _rand((3, 3, 2, 4), 12)
    out = sharded_conv2d(inp, ker, partition=("batch", "spatial"), mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(inp, ker, 1)),
                               rtol=1e-4, atol=1e-4)
    # axis tuple must match the component count and be distinct
    with pytest.raises(ValueError):
        sharded_conv2d(inp, ker, partition=("batch", "spatial"),
                       axis="data", mesh=mesh)
    with pytest.raises(ValueError):
        sharded_conv2d(inp, ker, partition=("batch", "spatial"),
                       axis=("data", "data"), mesh=mesh)


# ---------------------------------------------------------------------------
# hoisted validation (satellite): a typo'd algorithm/solution raises at
# the call site, BEFORE any shard_map tracing starts
# ---------------------------------------------------------------------------

def _forbid_shard_map(monkeypatch):
    import repro.parallel.conv as pconv

    def boom(*a, **kw):
        raise AssertionError("shard_map entered before validation")

    monkeypatch.setattr(pconv, "shard_map", boom)


def test_bad_algorithm_raises_before_tracing_1d(monkeypatch):
    _forbid_shard_map(monkeypatch)
    mesh = make_host_mesh(shape=(1,))
    inp, ker = _rand((2, 8, 8, 2), 0), _rand((3, 3, 2, 4), 1)
    with pytest.raises(ValueError, match="unknown algorithm 'toeplitz'"):
        sharded_conv2d(inp, ker, algorithm="toeplitz", partition="batch",
                       mesh=mesh)
    with pytest.raises(ValueError, match="unknown MEC solution 'Z'"):
        sharded_conv2d(inp, ker, algorithm="mec", solution="Z",
                       partition="spatial", mesh=mesh)


def test_bad_algorithm_raises_before_tracing_2d(monkeypatch):
    _forbid_shard_map(monkeypatch)
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    inp, ker = _rand((2, 8, 8, 2), 2), _rand((3, 3, 2, 4), 3)
    with pytest.raises(ValueError, match="unknown algorithm 'toeplitz'"):
        sharded_conv2d(inp, ker, algorithm="toeplitz",
                       partition=("batch", "spatial"), mesh=mesh)
    with pytest.raises(ValueError, match="unknown MEC solution 'Z'"):
        sharded_conv2d(inp, ker, algorithm="mec", solution="Z",
                       partition=("batch", "channel"), mesh=mesh)


def test_no_mesh_is_a_noop():
    inp = _rand((1, 8, 8, 2), 4)
    ker = _rand((3, 3, 2, 4), 5)
    out = sharded_conv2d(inp, ker, padding="SAME", partition="spatial")
    ref = conv2d(inp, ker, padding="SAME", algorithm="direct",
                 partition="none")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# conv_api routing: partition=None is rules-aware, "none" never routes
# ---------------------------------------------------------------------------

def test_conv2d_rules_aware_routing(monkeypatch):
    import repro.parallel.conv as pconv
    calls = []
    orig = pconv.sharded_conv2d

    def spy(*a, **kw):
        calls.append(kw.get("partition"))
        return orig(*a, **kw)

    monkeypatch.setattr(pconv, "sharded_conv2d", spy)
    inp = _rand((2, 8, 8, 2), 6)
    ker = _rand((3, 3, 2, 4), 7)
    ref = conv2d(inp, ker, algorithm="direct", partition="none")
    # outside any rules: partition=None must not touch the parallel layer
    conv2d(inp, ker, algorithm="mec")
    assert calls == []
    mesh = make_host_mesh()
    rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                          dp_axes=("data",), ep_axis=None, tp_axis=None)
    with use_rules(rules):
        out = conv2d(inp, ker, algorithm="mec")
    assert calls == ["auto"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # explicit partition routes even without rules installed
    conv2d(inp, ker, algorithm="mec", partition="batch")
    assert calls == ["auto", "batch"]


def test_auto_degrades_on_unnamed_multi_axis_mesh():
    """partition='auto' must fall back to single-device (not raise) when
    no mesh axis can be resolved — e.g. rules over a generated-name
    2-D host mesh."""
    mesh = make_host_mesh(shape=(1, 1))        # axes ("ax0", "ax1")
    rules = ShardingRules(mesh=mesh, rules={}, dp_axes=(),
                          ep_axis=None, tp_axis=None)
    inp = _rand((2, 8, 8, 2), 9)
    ker = _rand((3, 3, 2, 4), 10)
    ref = conv2d(inp, ker, algorithm="direct", partition="none")
    with use_rules(rules):
        out = conv2d(inp, ker, algorithm="mec")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_layer_partition_passthrough():
    from repro.models.layers import conv2d_layer, init_conv2d
    p = init_conv2d(jax.random.key(0), 3, 3, 2, 4)
    x = _rand((2, 8, 8, 2), 8)
    mesh = make_host_mesh()
    rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                          dp_axes=("data",), ep_axis=None, tp_axis=None)
    ref = conv2d_layer(p, x)
    with use_rules(rules):
        out = conv2d_layer(p, x, partition="batch")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# cost model: viability, halo bytes, picking
# ---------------------------------------------------------------------------

def test_partition_viability_rules():
    spec = ConvSpec(4, 16, 16, 3, 3, 3, 8, 1, 1)
    assert partition_viable(spec, "batch", 4)
    assert not partition_viable(spec, "batch", 3)
    assert partition_viable(spec, "channel", 8)
    assert not partition_viable(spec, "channel", 3)
    assert partition_viable(spec, "spatial", 4)
    assert not partition_viable(spec, "spatial", 5)
    # stride must divide the per-device rows
    s2 = ConvSpec(1, 18, 18, 3, 3, 3, 8, 2, 2)
    assert not partition_viable(s2, "spatial", 2)   # 9 rows, stride 2
    assert partition_viable(ConvSpec(1, 20, 20, 3, 3, 3, 8, 2, 2),
                            "spatial", 2)
    # halo must fit in one neighbour
    assert not partition_viable(ConvSpec(1, 16, 16, 3, 11, 11, 8, 1, 1),
                                "spatial", 8)       # halo 10 > 2 rows
    with pytest.raises(ValueError):
        partition_viable(spec, "toeplitz", 2)


def test_conv_partition_costs_fields():
    spec = ConvSpec(2, 16, 16, 3, 5, 5, 8, 1, 1)
    costs = conv_partition_costs(spec, 4, dtype_bytes=4)
    assert set(costs) == set(PARTITIONS)
    halo = spatial_halo_rows(5, 1)
    assert costs["spatial"]["halo_bytes_per_device"] == \
        2 * halo * 16 * 3 * 4
    # batch/channel exchange no halo
    assert costs["batch"]["halo_bytes_per_device"] == 0
    assert costs["channel"]["halo_bytes_per_device"] == 0
    # channel does NOT shrink the compact L; batch and spatial do
    from repro.core.memory import mec_overhead
    assert costs["channel"]["per_device_overhead_elems"] == mec_overhead(spec)
    assert costs["batch"]["per_device_overhead_elems"] < mec_overhead(spec)
    assert costs["spatial"]["per_device_overhead_elems"] < mec_overhead(spec)
    # backward comm: batch psums the kernel, channel psums the input
    assert costs["batch"]["comm_bytes_bwd_per_device"] == 5 * 5 * 3 * 8 * 4
    assert costs["channel"]["comm_bytes_bwd_per_device"] == \
        2 * 16 * 16 * 3 * 4


def test_pick_conv_partition_preferences():
    sizes = {p: 4 for p in PARTITIONS}
    # batch divisible -> embarrassingly parallel wins
    assert pick_conv_partition(ConvSpec(4, 16, 16, 3, 3, 3, 8), sizes) == \
        "batch"
    # batch=1: spatial's halo is far cheaper than channel's input psum
    assert pick_conv_partition(ConvSpec(1, 16, 16, 3, 3, 3, 8), sizes) == \
        "spatial"
    # spatial non-viable (odd rows) -> channel
    assert pick_conv_partition(ConvSpec(1, 15, 16, 3, 3, 3, 8), sizes) == \
        "channel"
    # nothing viable -> None (caller goes single-device)
    assert pick_conv_partition(ConvSpec(1, 15, 16, 3, 3, 3, 9), sizes) is None
    # 1-way axes are never a partition
    assert pick_conv_partition(ConvSpec(4, 16, 16, 3, 3, 3, 8),
                               {p: 1 for p in PARTITIONS}) is None


def test_default_axis_resolution():
    mesh = make_host_mesh()          # 1-D ("data",)
    for p in PARTITIONS:
        assert default_axis(p, mesh) == "data"
    mesh2 = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    assert default_axis("batch", mesh2) == "data"
    assert default_axis("channel", mesh2) == "model"
    assert default_axis("spatial", mesh2) == "model"


def test_composite_partition_costs_fields():
    spec = ConvSpec(4, 16, 16, 3, 5, 5, 8, 1, 1)
    costs = conv_partition_costs(spec, (2, 2), dtype_bytes=4)
    assert set(costs) == set(COMPOSITE_PARTITIONS)
    halo = spatial_halo_rows(5, 1)
    bs = costs[("batch", "spatial")]
    # the halo rides the LOCAL batch shard: i_n/2 samples worth of rows
    assert bs["halo_bytes_per_device"] == 2 * halo * 16 * 3 * 4
    assert bs["n_dev"] == 4 and bs["n_dev_axes"] == [2, 2]
    assert bs["viable"] is True
    # kernel replicated on both axes -> full-kernel psum + halo back
    assert bs["comm_bytes_bwd_per_device"] == \
        bs["halo_bytes_per_device"] + 5 * 5 * 3 * 8 * 4
    # batch x channel: each psum operand is the other component's shard
    bc = costs[("batch", "channel")]
    assert bc["halo_bytes_per_device"] == 0
    assert bc["comm_bytes_fwd_per_device"] == 0
    assert bc["comm_bytes_bwd_per_device"] == \
        (5 * 5 * 3 * 8 * 4) // 2 + (4 * 16 * 16 * 3 * 4) // 2
    # both shrinks apply to the local compact-L overhead
    from repro.core.memory import mec_overhead
    assert bs["per_device_overhead_elems"] < mec_overhead(spec)
    # flops split by the device product
    from repro.core.memory import conv_flops
    for entry in costs.values():
        assert entry["flops_per_device"] == conv_flops(spec) / 4
    with pytest.raises(ValueError):
        conv_partition_costs(spec, (2, 2, 2))


def test_pick_conv_partition_selects_composite():
    # i_n=2: 4-way batch is non-viable, but batch x spatial (2, 2) is —
    # and its halo-only comm beats channel's full-input psum.
    spec = ConvSpec(2, 16, 16, 3, 3, 3, 8, 1, 1)
    sizes = {"batch": 4, "channel": 4, "spatial": 4,
             ("batch", "spatial"): (2, 2)}
    assert pick_conv_partition(spec, sizes) == ("batch", "spatial")
    # a viable 1-D batch split is free -> still preferred over composites
    sizes4 = dict(sizes, batch=2)
    assert pick_conv_partition(ConvSpec(2, 16, 16, 3, 3, 3, 8), sizes4) == \
        "batch"
    # composites with a 1-way sub-axis never compete
    assert pick_conv_partition(
        spec, {("batch", "spatial"): (1, 4)}) is None
    # a misspelled / non-canonical candidate key raises instead of being
    # silently skipped (parallelism must never be lost quietly)
    with pytest.raises(ValueError, match="unknown partition candidate"):
        pick_conv_partition(spec, {"bach": 4})
    with pytest.raises(ValueError, match="unknown partition candidate"):
        pick_conv_partition(spec, {("spatial", "batch"): (2, 2)})
    # ... and so does a value whose shape does not match its key
    with pytest.raises(ValueError, match="takes 2 axis sizes"):
        pick_conv_partition(spec, {("batch", "spatial"): 4})
    with pytest.raises(ValueError, match="takes one axis size"):
        pick_conv_partition(spec, {"batch": (2, 2)})


# ---------------------------------------------------------------------------
# the real thing: 4 fake host devices in a subprocess
# ---------------------------------------------------------------------------

def test_sharded_conv_multidevice_subprocess():
    """sharded_conv2d == single-device oracle (fwd + grad) on a real
    4-device mesh for every partition axis x {stride, kernel, dtype}."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conv_api import conv2d
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.axes import ShardingRules, use_rules
        from repro.parallel.conv import sharded_conv2d

        mesh = make_host_mesh()          # (4,) "data"
        worst = {"fwd": 0.0, "gi": 0.0, "gk": 0.0, "rules": 0.0}
        cases = 0
        for part in ("batch", "channel", "spatial"):
            for k in (1, 3, 5):
                for s in (1, 2):
                    for dt in ("float32", "bfloat16"):
                        i_h = 4 * s * max(k, 2)      # 4-way spatial viable
                        rng = np.random.RandomState(cases)
                        x = jnp.asarray(rng.randn(4, i_h, i_h + 3, 3), dt)
                        kk = jnp.asarray(rng.randn(k, k, 3, 8), dt)
                        ref = conv2d(x, kk, stride=s, algorithm="direct",
                                     partition="none")
                        out = sharded_conv2d(x, kk, stride=s,
                                             algorithm="mec",
                                             partition=part, mesh=mesh)
                        tol_ref = jnp.maximum(jnp.max(jnp.abs(ref)), 1.0)
                        err = float(jnp.max(jnp.abs(
                            out.astype(jnp.float32)
                            - ref.astype(jnp.float32))) / tol_ref)
                        if dt == "float32":
                            worst["fwd"] = max(worst["fwd"], err)
                        assert err < (5e-2 if dt == "bfloat16" else 1e-4), \\
                            (part, k, s, dt, err)
                        cases += 1
        # grads through every partition (incl. the halo transpose)
        for part in ("batch", "channel", "spatial"):
            rng = np.random.RandomState(99)
            x = jnp.asarray(rng.randn(4, 12, 13, 3), jnp.float32)
            kk = jnp.asarray(rng.randn(3, 3, 3, 8), jnp.float32)
            loss = lambda f: (lambda a, b: jnp.sum(jnp.sin(f(a, b))))
            gi, gk = jax.grad(loss(lambda a, b: sharded_conv2d(
                a, b, algorithm="mec", partition=part, mesh=mesh)),
                argnums=(0, 1))(x, kk)
            ri, rk = jax.grad(loss(lambda a, b: conv2d(
                a, b, algorithm="direct", partition="none")),
                argnums=(0, 1))(x, kk)
            worst["gi"] = max(worst["gi"], float(jnp.max(jnp.abs(gi - ri))))
            worst["gk"] = max(worst["gk"], float(jnp.max(jnp.abs(gk - rk))))
        # rules-aware transparent routing on the real mesh
        rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                              dp_axes=("data",), ep_axis=None, tp_axis=None)
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(4, 10, 10, 3), jnp.float32)
        kk = jnp.asarray(rng.randn(3, 3, 3, 8), jnp.float32)
        ref = conv2d(x, kk, padding="SAME", algorithm="direct",
                     partition="none")
        with use_rules(rules):
            out = jax.jit(lambda a, b: conv2d(a, b, padding="SAME",
                                              algorithm="mec"))(x, kk)
        worst["rules"] = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"cases": cases, **worst}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["cases"] == 36
    assert res["gi"] < 2e-4 and res["gk"] < 2e-4, res
    assert res["rules"] < 1e-4, res


def test_composite_conv_multidevice_subprocess():
    """Composite 2-D partitions == single-device oracle (fwd + grad
    through the halo) on a real 2x2 data x model mesh for every
    composite mode x {stride, kernel, dtype}, plus the
    conv2d(partition=tuple) front-end routing."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conv_api import conv2d
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.axes import ShardingRules, use_rules
        from repro.parallel.conv import COMPOSITE_PARTITIONS, sharded_conv2d

        mesh = make_host_mesh(shape=(2, 2), axes=("data", "model"))
        worst = {"fwd": 0.0, "gi": 0.0, "gk": 0.0, "front": 0.0}
        cases = 0
        for part in COMPOSITE_PARTITIONS:
            for k in (1, 3, 5):
                for s in (1, 2):
                    for dt in ("float32", "bfloat16"):
                        # 2-way spatial viable: 2 | i_h, s | i_h/2,
                        # halo <= i_h/2; batch 4 % 2; channel 8 % 2
                        i_h = 2 * s * max(k, 2)
                        rng = np.random.RandomState(cases)
                        x = jnp.asarray(rng.randn(4, i_h, i_h + 3, 3), dt)
                        kk = jnp.asarray(rng.randn(k, k, 3, 8), dt)
                        ref = conv2d(x, kk, stride=s, algorithm="direct",
                                     partition="none")
                        out = sharded_conv2d(x, kk, stride=s,
                                             algorithm="mec",
                                             partition=part, mesh=mesh)
                        tol_ref = jnp.maximum(jnp.max(jnp.abs(ref)), 1.0)
                        err = float(jnp.max(jnp.abs(
                            out.astype(jnp.float32)
                            - ref.astype(jnp.float32))) / tol_ref)
                        if dt == "float32":
                            worst["fwd"] = max(worst["fwd"], err)
                        assert err < (5e-2 if dt == "bfloat16" else 1e-4), \\
                            (part, k, s, dt, err)
                        cases += 1
        # grads through every composite (incl. the halo transpose on the
        # spatial sub-axis)
        for part in COMPOSITE_PARTITIONS:
            rng = np.random.RandomState(99)
            x = jnp.asarray(rng.randn(4, 12, 13, 3), jnp.float32)
            kk = jnp.asarray(rng.randn(3, 3, 3, 8), jnp.float32)
            loss = lambda f: (lambda a, b: jnp.sum(jnp.sin(f(a, b))))
            gi, gk = jax.grad(loss(lambda a, b: sharded_conv2d(
                a, b, algorithm="mec", partition=part, mesh=mesh)),
                argnums=(0, 1))(x, kk)
            ri, rk = jax.grad(loss(lambda a, b: conv2d(
                a, b, algorithm="direct", partition="none")),
                argnums=(0, 1))(x, kk)
            worst["gi"] = max(worst["gi"], float(jnp.max(jnp.abs(gi - ri))))
            worst["gk"] = max(worst["gk"], float(jnp.max(jnp.abs(gk - rk))))
        # the conv2d front-end takes the tuple (and partition_axis tuple)
        rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                              dp_axes=("data",), ep_axis="model",
                              tp_axis="model")
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(4, 12, 12, 3), jnp.float32)
        kk = jnp.asarray(rng.randn(3, 3, 3, 8), jnp.float32)
        ref = conv2d(x, kk, padding="SAME", algorithm="direct",
                     partition="none")
        with use_rules(rules):
            out = jax.jit(lambda a, b: conv2d(
                a, b, padding="SAME", algorithm="mec",
                partition=("batch", "spatial")))(x, kk)
            out2 = jax.jit(lambda a, b: conv2d(
                a, b, padding="SAME", algorithm="mec",
                partition=("spatial", "channel"),
                partition_axis=("model", "data")))(x, kk)
        worst["front"] = float(max(jnp.max(jnp.abs(out - ref)),
                                   jnp.max(jnp.abs(out2 - ref))))
        print(json.dumps({"cases": cases, **worst}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["cases"] == 36
    assert res["gi"] < 2e-4 and res["gk"] < 2e-4, res
    assert res["front"] < 1e-4, res


# ----------------------------------------------------- halo edge cases

def test_zero_halo_when_stride_covers_kernel():
    # k_h == s_h: adjacent output windows tile the input exactly, so no
    # rows cross the shard boundary (and overshoot clamps at zero).
    assert spatial_halo_rows(3, 3) == 0
    assert spatial_halo_rows(2, 3) == 0
    spec = ConvSpec(1, 12, 12, 3, 3, 3, 8, 3, 3)
    assert partition_viable(spec, "spatial", 4)
    c = conv_partition_costs(spec, 4)["spatial"]
    assert c["viable"]
    assert c["halo_bytes_per_device"] == 0.0
    assert c["comm_bytes_fwd_per_device"] == 0.0
    # backward still psums the kernel cotangent over the spatial axis
    assert c["comm_bytes_bwd_per_device"] == 3 * 3 * 3 * 8 * 4
    # the local Eq. 3 overhead uses the halo-free 3-row shard
    import dataclasses
    from repro.core import memory
    lspec = dataclasses.replace(spec, i_h=3)
    assert c["per_device_overhead_elems"] == float(
        memory.mec_overhead(lspec))


def test_single_row_shard_halo_equals_full_local_height():
    # i_h=4 split 4 ways with k_h=2, s_h=1: each device owns ONE input
    # row and needs exactly one more — the halo IS the local height.
    # Viability is the boundary case halo <= h_loc, not halo < h_loc.
    spec = ConvSpec(2, 4, 8, 3, 2, 2, 4, 1, 1)
    assert spatial_halo_rows(2, 1) == 1
    assert partition_viable(spec, "spatial", 4)
    c = conv_partition_costs(spec, 4)["spatial"]
    assert c["viable"]
    # every exchange ships one full local row per batch element
    assert c["halo_bytes_per_device"] == 2 * 1 * 8 * 3 * 4
    import dataclasses
    from repro.core import memory
    lspec = dataclasses.replace(spec, i_h=2)    # 1 owned + 1 halo row
    assert c["per_device_overhead_elems"] == float(
        memory.mec_overhead(lspec))
    assert c["per_device_im2col_elems"] == float(
        memory.im2col_overhead(lspec))
    # sharper than the rows: more devices than rows can never split
    assert not partition_viable(spec, "spatial", 8)
    # ...and a halo exceeding the local height is rejected (k_h=3 needs
    # 2 neighbour rows from a 1-row shard: multi-hop, not supported)
    tall_kernel = ConvSpec(2, 4, 8, 3, 3, 3, 4, 1, 1)
    assert not partition_viable(tall_kernel, "spatial", 4)


def test_single_row_shard_matches_oracle():
    # The boundary geometry above must also be numerically right.
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conv_api import conv2d
        from repro.parallel.conv import sharded_conv2d
        rng = np.random.RandomState(7)
        inp = jnp.asarray(rng.randn(2, 4, 8, 3), jnp.float32)
        ker = jnp.asarray(rng.randn(2, 2, 3, 4), jnp.float32)
        ref = conv2d(inp, ker, algorithm="direct")
        out = sharded_conv2d(inp, ker, partition="spatial")
        print(float(jnp.max(jnp.abs(out - ref))))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert float(out.stdout.strip().splitlines()[-1]) < 1e-4
