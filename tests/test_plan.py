"""repro.plan (DESIGN.md §7): ConvPlan JSON round-trip stability
(property-tested across algorithms/partitions), cache-hit determinism
(process LRU + on-disk JSON), the thin-executor guarantee —
``conv2d(plan=)`` bit-identical to the equivalent kwargs call for every
algorithm (and every partition, in a 4-device subprocess) — and the
plan CLI's baseline gate."""
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

from repro.core.conv_api import conv2d, conv2d_spec
from repro.core.convspec import ConvSpec
from repro.kernels.ops import pick_w_blk
from repro.plan import (ConvPlan, PlanCache, eligible_candidates,
                        plan_conv2d, resolve_cached_plan, spec_key)
from repro.plan.cache import reset_global_plan_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ALGOS = ("direct", "im2col", "fft", "winograd", "mec", "mec_fused")
# (partition, axes) combos a plan may carry — None through composite.
_PARTITIONS = (
    (None, None),
    (("batch",), ("data",)),
    (("channel",), ("model",)),
    (("spatial",), ("model",)),
    (("batch", "spatial"), ("data", "model")),
    (("batch", "channel"), ("data", "model")),
    (("spatial", "channel"), ("model", "data")),
)


def _rand(shape, seed, dtype=jnp.float32):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """Point the global plan cache at an empty tmpdir for this test."""
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    reset_global_plan_cache()
    yield tmp_path
    reset_global_plan_cache()


# --------------------------------------------------------------- round-trip

@settings(max_examples=60, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 8),
       st.integers(1, 2),
       st.sampled_from(_ALGOS), st.sampled_from(["A", "B", "auto"]),
       st.sampled_from(["float32", "bfloat16"]),
       st.sampled_from([None, "DEFAULT", "HIGH", "HIGHEST"]),
       st.sampled_from(_PARTITIONS), st.sampled_from(["analytic",
                                                      "measured", "cached"]))
def test_plan_json_roundtrip_property(n, c, kc, s, algorithm, solution,
                                      dtype, precision, part, mode):
    """from_json(to_json(p)) == p for every algorithm x partition x
    precision x mode combination (the JSON is the wire format of the
    disk cache AND the committed baseline — it must be lossless)."""
    k = 3
    spec = ConvSpec(n, 8 * s, 8 * s, c, k, k, kc, s, s)
    w_blk = pick_w_blk(spec.o_w, spec.k_c) if algorithm == "mec_fused" \
        else None
    plan = ConvPlan(spec=spec, dtype=dtype, algorithm=algorithm,
                    solution=solution, w_blk=w_blk, precision=precision,
                    partition=part[0], partition_axes=part[1],
                    backend="cpu", mode=mode)
    again = ConvPlan.from_json(plan.to_json())
    assert again == plan
    # and a second trip is a fixed point
    assert ConvPlan.from_json(again.to_json()) == again
    assert again.cache_key() == plan.cache_key()


def test_plan_rejects_malformed():
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    with pytest.raises(ValueError):
        ConvPlan(spec=spec, dtype="float32", algorithm="auto")  # unresolved
    with pytest.raises(ValueError):
        ConvPlan(spec=spec, dtype="float32", algorithm="toeplitz")
    with pytest.raises(ValueError):
        ConvPlan(spec=spec, dtype="float32", algorithm="mec", solution="Z")
    with pytest.raises(ValueError):
        ConvPlan(spec=spec, dtype="float32", algorithm="mec",
                 precision="SOMETIMES")
    with pytest.raises(ValueError):   # partition without axes
        ConvPlan(spec=spec, dtype="float32", algorithm="mec",
                 partition=("batch",))
    with pytest.raises(ValueError):   # axis count mismatch
        ConvPlan(spec=spec, dtype="float32", algorithm="mec",
                 partition=("batch", "spatial"), partition_axes=("data",))
    p = plan_conv2d(spec)
    doc = p.to_dict()
    doc["plan_version"] = 999
    with pytest.raises(ValueError, match="plan_version"):
        ConvPlan.from_dict(doc)


def test_plan_conv2d_analytic_matches_costmodel():
    from repro.core.mec import pick_solution
    from repro.launch.costmodel import pick_conv2d_algorithm
    spec = ConvSpec(1, 16, 16, 4, 3, 3, 8, 1, 1)
    plan = plan_conv2d(spec)
    assert plan.algorithm == pick_conv2d_algorithm(spec)
    assert plan.mode == "analytic"
    if plan.algorithm == "mec":
        assert plan.solution == pick_solution(spec)
    # the TPU pick is a Pallas kernel and must carry a resolved w_blk
    tpu = plan_conv2d(spec, backend="tpu")
    assert tpu.algorithm == "mec_fused"
    assert tpu.w_blk == pick_w_blk(spec.o_w, spec.k_c)
    # explain() carries the why: Eq. 2-4 overheads + the winner mark
    text = plan.explain()
    assert "overhead" in text and plan.algorithm in text
    assert "im2col" in text and "Eq. 4" in text


# ---------------------------------------------------- thin-executor identity

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("algorithm", _ALGOS)
def test_conv2d_plan_bit_identical_to_kwargs(algorithm, stride):
    """The acceptance bar: for every algorithm, executing through a
    ConvPlan produces EXACTLY the bits the kwargs call produces."""
    if algorithm == "winograd" and stride != 1:
        pytest.skip("winograd is stride-1 only")
    inp = _rand((2, 11, 12, 3), 0)
    ker = _rand((3, 3, 3, 5), 1)
    spec = conv2d_spec(inp, ker, stride=stride, padding="SAME")
    plan = ConvPlan(
        spec=spec, dtype="float32", algorithm=algorithm,
        w_blk=(pick_w_blk(spec.o_w, spec.k_c)
               if algorithm == "mec_fused" else None))
    out_plan = conv2d(inp, ker, stride=stride, padding="SAME", plan=plan)
    out_kw = conv2d(inp, ker, stride=stride, padding="SAME",
                    algorithm=algorithm, partition="none")
    assert out_plan.dtype == out_kw.dtype
    assert bool(jnp.all(out_plan == out_kw)), algorithm


def test_conv2d_auto_equals_planned_auto(fresh_cache):
    """conv2d(plan=plan_conv2d(spec)) == conv2d(algorithm='auto') to the
    bit — the kwargs auto path IS the cached analytic plan."""
    for dtype in (jnp.float32, jnp.bfloat16):
        inp = _rand((1, 10, 10, 3), 2, dtype)
        ker = _rand((3, 3, 3, 4), 3, dtype)
        spec = conv2d_spec(inp, ker, padding="SAME")
        plan = plan_conv2d(spec, dtype=dtype)
        out_plan = conv2d(inp, ker, padding="SAME", plan=plan)
        out_auto = conv2d(inp, ker, padding="SAME", algorithm="auto",
                          partition="none")
        assert bool(jnp.all(out_plan == out_auto))


def test_plan_execution_validates_geometry_and_dtype():
    inp = _rand((1, 10, 10, 3), 4)
    ker = _rand((3, 3, 3, 4), 5)
    plan = plan_conv2d(conv2d_spec(inp, ker, padding="SAME"))
    with pytest.raises(ValueError, match="geometry mismatch"):
        conv2d(inp, ker, padding="VALID", plan=plan)   # wrong padding
    with pytest.raises(ValueError, match="geometry mismatch"):
        conv2d(inp, ker, stride=2, padding="SAME", plan=plan)
    with pytest.raises(ValueError, match="dtype mismatch"):
        conv2d(inp.astype(jnp.bfloat16), ker.astype(jnp.bfloat16),
               padding="SAME", plan=plan)


def test_plan_precision_wins_over_kwargs():
    """The plan's precision reaches the lowered dots (and the kwargs
    precision is ignored when a plan is passed — plan wins)."""
    inp = _rand((1, 8, 8, 3), 6, jnp.bfloat16)
    ker = _rand((3, 3, 3, 4), 7, jnp.bfloat16)
    spec = conv2d_spec(inp, ker)
    plan_hi = ConvPlan(spec=spec, dtype="bfloat16", algorithm="mec",
                       precision="HIGHEST")
    plan_def = ConvPlan(spec=spec, dtype="bfloat16", algorithm="mec")
    hi = jax.jit(lambda i, k: conv2d(i, k, plan=plan_hi)) \
        .lower(inp, ker).as_text()
    lo = jax.jit(lambda i, k: conv2d(i, k, precision=jax.lax.Precision.HIGHEST,
                                     plan=plan_def)) \
        .lower(inp, ker).as_text()
    assert "HIGHEST" in hi
    assert "HIGHEST" not in lo            # kwargs precision ignored


# ----------------------------------------------------------------- caching

def test_cached_mode_hit_determinism(fresh_cache, monkeypatch):
    spec = ConvSpec(2, 12, 12, 3, 3, 3, 8, 1, 1)
    first = plan_conv2d(spec, mode="cached")
    assert first.algorithm == plan_conv2d(spec, mode="analytic").algorithm
    # the hit is served from the LRU: breaking the costmodel must not
    # change (or even touch) the decision
    import repro.launch.costmodel as cm

    def boom(*a, **kw):
        raise AssertionError("cache hit recomputed the analytic plan")

    monkeypatch.setattr(cm, "pick_conv2d_algorithm", boom)
    second = plan_conv2d(spec, mode="cached")
    assert second == first


def test_cache_survives_process_via_disk(fresh_cache):
    spec = ConvSpec(1, 16, 16, 4, 5, 5, 8, 1, 1)
    plan = plan_conv2d(spec, mode="cached")
    files = list(fresh_cache.glob("*.json"))
    assert len(files) == 1, "cached plan must land on disk"
    # a brand-new cache object (fresh process simulation) reads it back
    fresh = PlanCache(path=files[0])
    assert fresh.get(plan.cache_key()) == plan
    # and the disk document is the documented JSON wire format
    doc = json.loads(files[0].read_text())
    assert doc["plan_cache_version"] == 1
    assert plan.cache_key() in doc["plans"]
    # an entry naming an algorithm this version does not have is skipped as
    # stale, and the rest of the file still loads
    doc["plans"]["gone"] = dict(doc["plans"][plan.cache_key()],
                                algorithm="toeplitz")
    files[0].write_text(json.dumps(doc))
    again = PlanCache(path=files[0])
    assert again.get("gone") is None
    assert again.get(plan.cache_key()) == plan


def test_cache_lru_and_corruption_tolerance(tmp_path):
    cache = PlanCache(path=tmp_path / "plans.json", max_entries=2)
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    plans = [ConvPlan(spec=spec, dtype="float32", algorithm=alg)
             for alg in ("direct", "im2col", "mec")]
    for i, p in enumerate(plans):
        cache.put(f"k{i}", p)
    assert len(cache) == 2                  # LRU trimmed the oldest
    assert cache.get("k0") is None
    assert cache.get("k2") == plans[2]
    # corrupt disk file degrades to empty, never raises
    (tmp_path / "bad.json").write_text("{not json")
    assert PlanCache(path=tmp_path / "bad.json").get("k2") is None


def test_conv2d_auto_populates_global_cache(fresh_cache):
    from repro.plan.cache import global_plan_cache
    inp = _rand((1, 9, 9, 2), 8)
    ker = _rand((3, 3, 2, 4), 9)
    conv2d(inp, ker, algorithm="auto", partition="none")
    spec = conv2d_spec(inp, ker)
    key = f"{spec_key(spec)}|float32|{jax.default_backend()}"
    assert global_plan_cache().get(key) is not None
    # a second call is a pure cache hit returning the same decision
    assert resolve_cached_plan(spec).cache_key() == key


def test_cached_mode_never_serves_conflicting_hit(fresh_cache):
    """Review regression: the key is spec|dtype|backend, so a hit whose
    precision (or partition) conflicts with the request must be
    recomputed, never served silently."""
    spec = ConvSpec(1, 12, 12, 3, 3, 3, 8, 1, 1)
    base = plan_conv2d(spec, mode="cached")
    assert base.precision is None
    hi = plan_conv2d(spec, mode="cached", precision=jax.lax.Precision.HIGHEST)
    assert hi.precision == "HIGHEST"          # not the stale base hit
    again = plan_conv2d(spec, mode="cached", precision="HIGHEST")
    assert again == hi                         # new decision now cached
    # and back: a no-precision request recomputes rather than serving hi
    assert plan_conv2d(spec, mode="cached").precision is None
    # explicit partition request against a partition-free hit recomputes
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.axes import ShardingRules, use_rules
    rules = ShardingRules(mesh=make_host_mesh(), rules={},
                          dp_axes=("data",), ep_axis=None, tp_axis=None)
    with use_rules(rules):
        part = plan_conv2d(spec, mode="cached", partition="batch")
    assert part.partition == ("batch",)


def test_partitioned_plans_never_persist_to_disk(fresh_cache):
    """Review regression: the disk fingerprint has no mesh topology, so
    partitioned plans must stay in the process LRU only."""
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.axes import ShardingRules, use_rules
    spec = ConvSpec(2, 8, 8, 2, 3, 3, 4, 1, 1)
    rules = ShardingRules(mesh=make_host_mesh(), rules={},
                          dp_axes=("data",), ep_axis=None, tp_axis=None)
    with use_rules(rules):
        plan = plan_conv2d(spec, mode="cached", partition="batch")
    assert plan.partition == ("batch",)
    for f in fresh_cache.glob("*.json"):
        doc = json.loads(f.read_text())
        for stored in doc["plans"].values():
            assert stored["partition"] is None


def test_cached_hit_invalidated_by_budget_change(fresh_cache, monkeypatch):
    """Review regression: a cached Pallas plan bakes in w_blk, so a
    changed REPRO_MEC_ACC_BYTES (or device budget) must invalidate the
    hit rather than silently keep the stale block size."""
    from repro.kernels.ops import ACC_BYTES_ENV, pick_w_blk
    monkeypatch.delenv(ACC_BYTES_ENV, raising=False)
    spec = ConvSpec(1, 40, 40, 4, 3, 3, 8, 1, 1)
    first = plan_conv2d(spec, mode="cached", backend="tpu")
    assert first.algorithm == "mec_fused"
    assert first.w_blk == pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)
    monkeypatch.setenv(ACC_BYTES_ENV, str(4 * spec.k_c * 8))  # 8 columns
    second = plan_conv2d(spec, mode="cached", backend="tpu")
    assert second.w_blk == 8 != first.w_blk


def test_cached_hit_respects_explicit_partition_axis(fresh_cache):
    """Review regression: an explicit partition_axis differing from the
    hit's recorded axes must recompute, not serve the wrong axes."""
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.axes import ShardingRules, use_rules
    spec = ConvSpec(2, 8, 8, 2, 3, 3, 4, 1, 1)
    mesh = make_host_mesh(shape=(1, 1), axes=("data", "model"))
    rules = ShardingRules(mesh=mesh, rules={}, dp_axes=("data",),
                          ep_axis="model", tp_axis="model")
    with use_rules(rules):
        a = plan_conv2d(spec, mode="cached", partition="batch",
                        partition_axis="data")
        assert a.partition_axes == ("data",)
        b = plan_conv2d(spec, mode="cached", partition="batch",
                        partition_axis="model")
        assert b.partition_axes == ("model",)


def test_pick_measured_noise_margin():
    """Review regression: a sub-margin 'win' is timer jitter — the
    analytic pick must hold unless beaten decisively."""
    from repro.plan import pick_measured
    assert pick_measured({"mec": 101.4, "im2col": 101.3}, "mec") == "mec"
    assert pick_measured({"mec": 140.0, "im2col": 100.0}, "mec") == "im2col"
    assert pick_measured({"mec": 104.0, "im2col": 100.0}, "mec") == "mec"
    # analytic absent from the candidate set: plain argmin
    assert pick_measured({"im2col": 100.0, "fft": 90.0}, "mec") == "fft"


def test_plan_execution_rejects_backend_mismatch():
    """Review regression: a TPU plan must not silently interpret its
    Pallas kernel on CPU — backend drift raises at execution."""
    inp = _rand((1, 10, 10, 3), 60)
    ker = _rand((3, 3, 3, 4), 61)
    spec = conv2d_spec(inp, ker)
    tpu_plan = plan_conv2d(spec, backend="tpu")
    with pytest.raises(ValueError, match="backend mismatch"):
        conv2d(inp, ker, plan=tpu_plan)


def test_measure_candidates_stays_on_warning_free_path(fresh_cache,
                                                       monkeypatch, recwarn):
    """Review regression: measured-mode planning used to trip the
    REPRO_MEC_ACC_BYTES deprecation warning through the kernels' kwargs
    fallback — the planner must stay silent (it IS the plan path)."""
    from repro.kernels.ops import ACC_BYTES_ENV
    monkeypatch.setenv(ACC_BYTES_ENV, "4096")
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    n_before = len(recwarn)
    plan = plan_conv2d(spec, mode="measured", iters=1, warmup=1,
                       candidates=("direct", "mec", "mec_fused"))
    assert plan.mode == "measured"
    assert not [w for w in recwarn.list[n_before:]
                if issubclass(w.category, DeprecationWarning)]


# ---------------------------------------------------------------- measured

def test_measured_mode_picks_a_timed_winner(fresh_cache):
    spec = ConvSpec(1, 10, 10, 2, 3, 3, 4, 1, 1)
    candidates = ("direct", "mec", "im2col")
    plan = plan_conv2d(spec, mode="measured", candidates=candidates,
                       iters=1, warmup=1)
    assert plan.mode == "measured"
    assert plan.algorithm in candidates
    # eligibility filter: winograd never offered on a strided spec
    strided = ConvSpec(1, 10, 10, 2, 3, 3, 4, 2, 2)
    assert "winograd" not in eligible_candidates(strided)
    assert "winograd" in eligible_candidates(spec)


def test_tpu_candidates_and_pick_follow_the_mosaic_rule():
    """The one Pallas kernel is a candidate, and a geometry it cannot
    take is routed to direct by the kernel's own rule, which explain()
    reports."""
    from repro.kernels.mec_conv import fused_refusal
    from repro.launch.costmodel import pick_conv2d_algorithm
    spec = ConvSpec(1, 10, 10, 4, 3, 3, 4, 1, 1)
    assert eligible_candidates(spec) == ("direct", "im2col", "fft",
                                         "winograd", "mec", "mec_fused")
    assert fused_refusal(spec, "float32") is None
    assert pick_conv2d_algorithm(spec, "tpu") == "mec_fused"
    # 4096 input channels: even one 128-channel block of the 3x3
    # kernel, double-buffered, overruns VMEM, so the rule refuses it
    wide = ConvSpec(1, 3, 1002, 4096, 3, 3, 2048, 1, 1)
    why = fused_refusal(wide, "float32")
    assert "exceeds VMEM" in why
    assert pick_conv2d_algorithm(wide, "tpu") == "direct"
    plan = plan_conv2d(wide, backend="tpu")
    assert plan.algorithm == "direct"
    assert "mec_fused not taken on tpu" in plan.explain()


# Every distinct conv of ResNet-101 at batch 32 (as repro.models.resnet
# pads them), resnet101_t3's cv4, MEC Table 2's cv1-cv12, then the
# transposed conv (the input gradient) of each stride-1 kxk conv among
# them: geometry, then for bfloat16 and float32 the TPU pick, the
# planner's w_blk, mec_fused's grid and whether the input gradient and
# the weight gradient run on a kernel.
TPU_PICKS = [
    ((32, 230, 230, 3, 7, 7, 64, 2, 2),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 56, 56, 64, 1, 1, 64, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 58, 58, 64, 3, 3, 64, 1, 1),
     ("mec_fused", 56, (1, 32, 1, 1), True, True),
     ("mec_fused", 56, (1, 32, 1, 1), True, True)),
    ((32, 56, 56, 64, 1, 1, 256, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 56, 56, 256, 1, 1, 64, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 56, 56, 256, 1, 1, 128, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 58, 58, 128, 3, 3, 128, 2, 2),
     ("mec_fused", 28, (1, 8, 1, 1), False, False),
     ("mec_fused", 28, (1, 16, 1, 1), False, False)),
    ((32, 28, 28, 128, 1, 1, 512, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 56, 56, 256, 1, 1, 512, 2, 2),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 28, 28, 512, 1, 1, 128, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 30, 30, 128, 3, 3, 128, 1, 1),
     ("mec_fused", 28, (1, 8, 1, 1), True, True),
     ("mec_fused", 28, (1, 8, 1, 1), True, True)),
    ((32, 28, 28, 512, 1, 1, 256, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 30, 30, 256, 3, 3, 256, 2, 2),
     ("mec_fused", 14, (1, 7, 1, 1), False, False),
     ("mec_fused", 14, (2, 11, 1, 1), False, False)),
    ((32, 14, 14, 256, 1, 1, 1024, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 28, 28, 512, 1, 1, 1024, 2, 2),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 14, 14, 1024, 1, 1, 256, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 16, 16, 256, 3, 3, 256, 1, 1),
     ("mec_fused", 14, (1, 4, 1, 1), True, True),
     ("mec_fused", 14, (2, 4, 1, 1), True, True)),
    ((32, 14, 14, 1024, 1, 1, 512, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 16, 16, 512, 3, 3, 512, 2, 2),
     ("mec_fused", 7, (4, 8, 1, 1), False, False),
     ("mec_fused", 7, (4, 8, 1, 1), False, False)),
    ((32, 7, 7, 512, 1, 1, 2048, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 14, 14, 1024, 1, 1, 2048, 2, 2),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 7, 7, 2048, 1, 1, 512, 1, 1),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 9, 9, 512, 3, 3, 512, 1, 1),
     ("mec_fused", 7, (4, 2, 1, 1), True, True),
     ("mec_fused", 7, (4, 4, 1, 1), True, True)),
    ((32, 224, 224, 64, 7, 7, 64, 2, 2),
     ("mec_fused", 109, (1, 32, 4, 1), False, True),
     ("mec_fused", 109, (1, 32, 7, 1), False, True)),
    ((32, 227, 227, 3, 11, 11, 96, 4, 4),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 231, 231, 3, 11, 11, 96, 4, 4),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 227, 227, 3, 7, 7, 64, 2, 2),
     ("direct", None, None, False, False),
     ("direct", None, None, False, False)),
    ((32, 24, 24, 96, 5, 5, 256, 1, 1),
     ("mec_fused", 20, (1, 11, 1, 1), True, True),
     ("mec_fused", 20, (2, 4, 1, 1), True, True)),
    ((32, 12, 12, 256, 3, 3, 512, 1, 1),
     ("mec_fused", 10, (2, 3, 1, 1), True, True),
     ("mec_fused", 10, (4, 3, 1, 1), True, True)),
    ((32, 224, 224, 3, 3, 3, 64, 1, 1),
     ("direct", None, None, True, False), ("direct", None, None, True, False)),
    ((32, 112, 112, 64, 3, 3, 128, 1, 1),
     ("mec_fused", 110, (1, 32, 4, 1), True, True),
     ("mec_fused", 110, (1, 32, 4, 1), True, True)),
    ((32, 56, 56, 64, 3, 3, 64, 1, 1),
     ("mec_fused", 54, (1, 32, 1, 1), True, True),
     ("mec_fused", 54, (1, 32, 1, 1), True, True)),
    ((32, 28, 28, 128, 3, 3, 128, 1, 1),
     ("mec_fused", 26, (1, 8, 1, 1), True, True),
     ("mec_fused", 26, (1, 8, 1, 1), True, True)),
    ((32, 14, 14, 256, 3, 3, 256, 1, 1),
     ("mec_fused", 12, (1, 4, 1, 1), True, True),
     ("mec_fused", 12, (2, 3, 1, 1), True, True)),
    ((32, 7, 7, 512, 3, 3, 512, 1, 1),
     ("mec_fused", 5, (4, 2, 1, 1), True, True),
     ("mec_fused", 5, (4, 2, 1, 1), True, True)),
    ((32, 60, 60, 64, 3, 3, 64, 1, 1),
     ("mec_fused", 58, (1, 32, 1, 1), True, True),
     ("mec_fused", 58, (1, 32, 1, 1), True, True)),
    ((32, 32, 32, 128, 3, 3, 128, 1, 1),
     ("mec_fused", 30, (1, 8, 1, 1), True, True),
     ("mec_fused", 30, (1, 8, 1, 1), True, True)),
    ((32, 18, 18, 256, 3, 3, 256, 1, 1),
     ("mec_fused", 16, (1, 4, 1, 1), True, True),
     ("mec_fused", 16, (2, 4, 1, 1), True, True)),
    ((32, 11, 11, 512, 3, 3, 512, 1, 1),
     ("mec_fused", 9, (4, 3, 1, 1), True, True),
     ("mec_fused", 9, (4, 5, 1, 1), True, True)),
    ((32, 28, 28, 256, 5, 5, 96, 1, 1),
     ("mec_fused", 24, (1, 7, 1, 1), True, True),
     ("mec_fused", 24, (1, 16, 1, 1), True, True)),
    ((32, 14, 14, 512, 3, 3, 256, 1, 1),
     ("mec_fused", 12, (2, 3, 1, 1), True, True),
     ("mec_fused", 12, (2, 6, 1, 1), True, True)),
    ((32, 226, 226, 64, 3, 3, 3, 1, 1),
     ("mec_fused", 224, (1, 32, 13, 1), False, True),
     ("mec_fused", 224, (1, 32, 13, 1), False, True)),
    ((32, 114, 114, 128, 3, 3, 64, 1, 1),
     ("mec_fused", 112, (1, 32, 4, 1), True, True),
     ("mec_fused", 112, (1, 32, 4, 1), True, True)),
]


@pytest.mark.parametrize("spec,dtype,pick", [
    pytest.param(ConvSpec(*geometry), dtype, pick,
                 id=f"{spec_key(ConvSpec(*geometry))}-{dtype}")
    for geometry, *picks in TPU_PICKS
    for dtype, pick in zip(("bfloat16", "float32"), picks)])
def test_tpu_plans_mec_fused_for_table2_and_resnet_stages(spec, dtype, pick):
    """The TPU pick, the planner's block, the kernel's grid and where the
    input and weight gradients run, as the kernel's rules
    (``fused_refusal``, ``fused_weight_grad_refusal``) decide them;
    traced nothing, so each case costs milliseconds."""
    from repro.core.conv_api import fused_input_grad_refusal
    from repro.kernels.mec_conv import (fused_blocks, fused_refusal,
                                        fused_weight_grad_refusal)
    from repro.launch.costmodel import pick_conv2d_algorithm
    from repro.plan.convplan import _pallas_w_blk
    algorithm, w_blk, grid, grad_on_kernel, wgrad_on_kernel = pick
    assert pick_conv2d_algorithm(spec, "tpu", dtype=dtype) == algorithm
    assert (fused_refusal(spec, dtype) is None) == \
        (algorithm == "mec_fused")
    assert _pallas_w_blk(spec, algorithm) == w_blk
    if algorithm == "mec_fused":
        s = spec
        fb = fused_blocks(s.i_n, s.i_h, s.i_w, s.i_c, s.k_h, s.k_w, s.k_c,
                          s.s_h, s.s_w, w_blk, jnp.dtype(dtype).itemsize)
        assert fb.grid == grid
    assert (fused_input_grad_refusal(spec, dtype) is None) == \
        grad_on_kernel
    # a conv that runs forward on mec_fused, at the planner's block
    assert (algorithm == "mec_fused" and fused_weight_grad_refusal(
        spec, dtype, w_blk) is None) == wgrad_on_kernel


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_tpu_explain_prints_the_blocking_and_the_input_gradient(dtype):
    """explain() of a TPU plan prints mec_fused's blocking and where the
    gradients run: the input gradient on the kernel for cv12, stride 1,
    and on XLA for cv4, stride 2; the weight gradient on its kernel for
    both, and on XLA for a 3x3/2 and where 768 channels' f32 dW block
    overruns VMEM."""
    plan = plan_conv2d(ConvSpec(32, 9, 9, 512, 3, 3, 512), backend="tpu",
                       dtype=dtype)
    assert plan.algorithm == "mec_fused"
    assert "mec_fused blocking:" in plan.explain()
    assert "image(s) x 7 row(s)" in plan.explain()
    assert ("input gradient: the mec_fused kernel on the transposed conv "
            "32x11x11x512-k3x3x512-s1x1") in plan.explain()
    assert ("weight gradient: the mec_fused_wgrad kernel on the forward's "
            "blocking") in plan.explain()
    cv4 = plan_conv2d(ConvSpec(32, 224, 224, 64, 7, 7, 64, 2, 2),
                      backend="tpu", dtype=dtype).explain()
    assert "input gradient: XLA (_mec_input_grad), stride (2, 2)" in cv4
    assert "weight gradient: the mec_fused_wgrad kernel" in cv4
    s2 = plan_conv2d(ConvSpec(32, 58, 58, 128, 3, 3, 128, 2, 2),
                     backend="tpu", dtype=dtype).explain()
    assert ("weight gradient: XLA (_mec_weight_grad), stride 2x2 folds 3 "
            "kernel columns into 2 taps") in s2
    wide = plan_conv2d(ConvSpec(32, 9, 9, 768, 3, 3, 768), backend="tpu",
                       dtype=dtype)
    assert wide.algorithm == "mec_fused"
    assert ("weight gradient: XLA (_mec_weight_grad), a step's working set "
            "with the f32 dW block 3x3x768x128") in wide.explain()


def test_tpu_sends_3_channel_inputs_to_direct():
    """The ResNet stem (7x7/2 on 224 px of 3 channels) goes to XLA's conv
    on TPU, with the channel rule as its reason; cv4, the same kernel on
    64 channels, and a 4-channel input keep the fused kernel."""
    from repro.kernels.mec_conv import FUSED_MIN_CHANNELS, fused_refusal
    from repro.launch.costmodel import pick_conv2d_algorithm
    assert FUSED_MIN_CHANNELS == 4
    stem = ConvSpec(32, 230, 230, 3, 7, 7, 64, 2, 2)
    assert pick_conv2d_algorithm(stem, "tpu", dtype="bfloat16") == "direct"
    why = fused_refusal(stem, "bfloat16")
    assert why.startswith("3 input channel(s), under 4")
    assert why in plan_conv2d(stem, backend="tpu",
                              dtype="bfloat16").explain()
    cv4 = ConvSpec(32, 224, 224, 64, 7, 7, 64, 2, 2)
    assert pick_conv2d_algorithm(cv4, "tpu", dtype="bfloat16") == \
        "mec_fused"
    four = ConvSpec(32, 230, 230, 4, 7, 7, 64, 2, 2)
    assert fused_refusal(four, "bfloat16") is None


# -------------------------------------------------------------- partitions

def test_plan_records_partition_and_executor_consumes_it(fresh_cache):
    """Under installed rules the plan captures partition + mesh axes at
    plan time; conv2d(plan=) then routes through the distributed layer
    with exactly that decision — and matches the kwargs sharded call to the
    bit (1-device mesh; the 4-device grid runs in the subprocess
    test)."""
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.axes import ShardingRules, use_rules
    mesh = make_host_mesh()               # (1,) "data"
    rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                          dp_axes=("data",), ep_axis=None, tp_axis=None)
    inp = _rand((2, 8, 8, 2), 10)
    ker = _rand((3, 3, 2, 4), 11)
    spec = conv2d_spec(inp, ker, padding="SAME")
    with use_rules(rules):
        plan = plan_conv2d(spec, partition="batch")
        assert plan.partition == ("batch",)
        assert plan.partition_axes == ("data",)
        out_plan = conv2d(inp, ker, padding="SAME", plan=plan)
        out_kw = conv2d(inp, ker, padding="SAME", algorithm=plan.algorithm,
                        partition="batch")
    assert bool(jnp.all(out_plan == out_kw))
    # round-trip preserves the partition decision exactly
    assert ConvPlan.from_json(plan.to_json()) == plan
    # without rules the partition plan cannot be made
    with pytest.raises(ValueError, match="needs an installed mesh"):
        plan_conv2d(spec, partition="batch")


@pytest.mark.slow
def test_plan_vs_kwargs_multidevice_subprocess():
    """Acceptance grid on a real 4-device mesh: for every algorithm x
    partition combination, conv2d(plan=plan_conv2d(spec)) is
    bit-identical to the equivalent kwargs call."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_PLAN_CACHE_DIR"] = os.environ.get("TMPDIR", "/tmp")
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conv_api import conv2d, conv2d_spec
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.axes import ShardingRules, use_rules
        from repro.plan import plan_conv2d

        mesh = make_host_mesh(shape=(2, 2), axes=("data", "model"))
        rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                              dp_axes=("data",), ep_axis="model",
                              tp_axis="model")
        cases = 0
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 12, 12, 3), jnp.float32)
        kk = jnp.asarray(rng.randn(3, 3, 3, 8), jnp.float32)
        spec = conv2d_spec(x, kk, padding="SAME")
        with use_rules(rules):
            for part, axis in [("batch", None), ("channel", None),
                               ("spatial", None),
                               (("batch", "spatial"), None),
                               (("batch", "channel"), None),
                               (("spatial", "channel"), ("model", "data"))]:
                plan = plan_conv2d(spec, partition=part,
                                   partition_axis=axis)
                for alg in ("direct", "im2col", "mec", "mec_fused"):
                    import dataclasses
                    p = dataclasses.replace(plan, algorithm=alg)
                    out_p = conv2d(x, kk, padding="SAME", plan=p)
                    out_k = conv2d(x, kk, padding="SAME", algorithm=alg,
                                   partition=part, partition_axis=axis)
                    assert bool(jnp.all(out_p == out_k)), (part, alg)
                    cases += 1
        print(json.dumps({"cases": cases}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["cases"] == 24


# ------------------------------------------------------------------- CLI

def test_plan_cli_build_and_gate(tmp_path):
    from repro.plan.__main__ import build_plans, compare_plans, main
    doc = build_plans(["smoke"])
    assert set(doc["plans"]) == {"smoke/s3x3", "smoke/s5x5",
                                 "smoke/s11x11", "smoke/w520"}
    for plan in doc["plans"].values():
        assert plan["algorithm"] != "auto"
    # identical docs gate clean
    failures, _ = compare_plans(doc, json.loads(json.dumps(doc)))
    assert failures == []
    # a flipped algorithm fails loudly
    drifted = json.loads(json.dumps(doc))
    drifted["plans"]["smoke/s3x3"]["algorithm"] = "im2col"
    failures, _ = compare_plans(drifted, doc)
    assert any("algorithm changed" in f for f in failures)
    # a missing cell is a coverage regression
    shrunk = json.loads(json.dumps(doc))
    del shrunk["plans"]["smoke/s5x5"]
    failures, _ = compare_plans(shrunk, doc)
    assert any("missing" in f for f in failures)
    # end-to-end through main(): write then self-check
    out = tmp_path / "plans.json"
    assert main(["--suites", "smoke", "--out", str(out)]) == 0
    assert main(["--suites", "smoke", "--baseline", str(out)]) == 0
    drifted_path = tmp_path / "drift.json"
    drifted_path.write_text(json.dumps(drifted))
    assert main(["--suites", "smoke", "--baseline", str(drifted_path)]) == 1


def test_bench_records_plan_per_cell():
    from repro.bench.harness import measure
    from repro.bench.scenarios import Scenario
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    sc = Scenario(name="tiny", spec=spec, run_spec=spec,
                  algorithms=("direct",))
    rec = measure(sc, "direct", iters=1, warmup=1, with_hlo=False,
                  with_timing=False)
    assert rec["plan"]["algorithm"] == rec["auto_algorithm"]
    assert rec["plan"]["spec"] == rec["spec"]


# ---------------------------------------------------- measured stage 2

def test_tune_measured_grids_the_mec_solution(fresh_cache):
    from repro.plan import tune_measured
    spec = ConvSpec(1, 10, 10, 2, 3, 3, 4, 1, 1)
    plan, detail = tune_measured(spec, candidates=("mec",),
                                 iters=1, warmup=1, record=False,
                                 calibration=None)
    assert plan.algorithm == "mec" and plan.mode == "measured"
    tuning = detail["tuning"]
    assert tuning["knob"] == "solution" and tuning["algorithm"] == "mec"
    assert set(tuning["trials"]) == {"A", "B"}
    assert tuning["picked"] in ("A", "B")
    assert plan.solution == tuning["picked"]
    # the analytic default only loses its knob with decisive evidence
    from repro.plan.convplan import pick_measured
    assert tuning["picked"] == pick_measured(
        {k: v["us_median"] for k, v in tuning["trials"].items()},
        tuning["default"])
    assert detail["candidate_us"].keys() == {"mec"}
    assert detail["skipped"] == {}


def test_tune_measured_grids_pallas_w_blk(fresh_cache):
    from repro.plan import tune_measured
    # o_w = 32: the default block takes the whole row, and its half is
    # a second, sublane-aligned trial
    spec = ConvSpec(1, 8, 34, 2, 3, 3, 4, 1, 1)
    plan, detail = tune_measured(spec, candidates=("mec_fused",),
                                 iters=1, warmup=1, interpret=True,
                                 record=False, calibration=None)
    assert plan.algorithm == "mec_fused"
    tuning = detail["tuning"]
    assert tuning["knob"] == "w_blk"
    assert len(tuning["trials"]) >= 2
    assert str(tuning["default"]) in tuning["trials"]
    assert plan.w_blk == int(tuning["picked"])


def test_measured_skips_are_counted_not_dropped(fresh_cache, monkeypatch):
    from repro.plan import convplan, measure_candidates_detailed

    def boom(trial, inp, ker, iters, warmup, interpret):
        if trial.algorithm == "mec":
            raise RuntimeError("compile exploded")
        return {"iters": 1, "warmup": 1, "us_median": 10.0,
                "us_min": 10.0, "us_mean": 10.0, "us_std": 0.0,
                "us_rel_spread": 0.0}

    monkeypatch.setattr(convplan, "_time_trial", boom)
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    with pytest.warns(UserWarning, match="measured planning skips mec"):
        mc = measure_candidates_detailed(
            spec, candidates=("direct", "mec"), record=False)
    assert mc.times == {"direct": 10.0}
    assert mc.skipped["mec"].startswith("RuntimeError")
    # a kernel candidate the kernel's geometry rule refuses is skipped
    # the same loud way, and never timed at all
    from repro.kernels import mec_conv
    monkeypatch.setattr(mec_conv, "fused_refusal",
                        lambda spec, dtype, w_blk=None: "overruns VMEM")
    with pytest.warns(UserWarning,
                      match="measured planning skips mec_fused"):
        mc = measure_candidates_detailed(
            spec, candidates=("mec_fused",), record=False)
    assert mc.times == {}
    assert mc.skipped["mec_fused"] == "overruns VMEM"


def test_tune_measured_raises_when_nothing_timeable(fresh_cache,
                                                    monkeypatch):
    from repro.plan import convplan, tune_measured

    def boom(trial, inp, ker, iters, warmup, interpret):
        raise RuntimeError("no backend")

    monkeypatch.setattr(convplan, "_time_trial", boom)
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    with pytest.warns(UserWarning):
        with pytest.raises(ValueError, match="no timeable candidate"):
            tune_measured(spec, candidates=("direct", "mec"),
                          record=False, calibration=None)


def test_measured_trials_feed_the_calibration_store(fresh_cache,
                                                    monkeypatch):
    from repro.plan import CalibrationStore, tune_measured
    from repro.plan.calibrate import reset_calibration_cache
    monkeypatch.delenv("REPRO_CALIBRATION", raising=False)
    reset_calibration_cache()
    spec = ConvSpec(1, 10, 10, 2, 3, 3, 4, 1, 1)
    tune_measured(spec, candidates=("direct", "mec"), iters=1, warmup=1)
    disk = CalibrationStore().load()
    cell = disk.cell_times(spec)
    assert set(cell) >= {"direct", "mec"}
    reset_calibration_cache()


def test_pick_measured_spread_widens_the_margin():
    from repro.plan import pick_measured
    times = {"mec": 130.0, "im2col": 100.0}
    # 30% gap beats the 5% floor...
    assert pick_measured(times, "mec") == "im2col"
    # ...but not the 40% observed jitter of the winner
    assert pick_measured(times, "mec",
                         spreads={"im2col": 0.4}) == "mec"
    # the analytic candidate's own jitter counts too
    assert pick_measured(times, "mec", spreads={"mec": 0.35}) == "mec"
    # quiet measurements keep the floor exactly
    assert pick_measured(times, "mec",
                         spreads={"mec": 0.01, "im2col": 0.0}) == "im2col"
    # absurd spreads are capped, not infinite vetoes
    assert pick_measured({"mec": 500.0, "im2col": 100.0}, "mec",
                         spreads={"im2col": 7.0}) == "im2col"
