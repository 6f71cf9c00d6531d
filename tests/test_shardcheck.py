"""Distributed-conv collective contract checker (repro.analysis.shardcheck,
DESIGN.md §8): contract derivation units (trim_reshard /
expected_collectives / verify_collectives), skip semantics, the plan
hook, and seeded-mutation subprocess tests proving the checker actually
catches a deleted halo exchange and a dropped VJP transpose."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.shardcheck import (COLLECTIVE_KINDS,
                                       SCALAR_REDUCE_ALLOWANCE_BYTES,
                                       check_plan_contract, check_sharding,
                                       expected_collectives, trim_reshard,
                                       verify_collectives)
from repro.core.convspec import ConvSpec
from repro.plan.convplan import ConvPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# o_h=14 splits evenly 2 ways; halo 2 rows; trim shifts f=1 row.
SPEC = ConvSpec(2, 16, 16, 3, 3, 3, 4, 1, 1)


def _costs(spec, sizes):
    from repro.launch.costmodel import conv_partition_costs
    return conv_partition_costs(
        spec, sizes if isinstance(sizes, tuple) else sizes, 4)


# ---------------------------------------------------------------------------
# contract derivation
# ---------------------------------------------------------------------------

def test_trim_reshard_even_split_prices_the_shift():
    # r=8 rows/device, o_h=14 -> f = 8 - ceil(14/2) = 1 shifted row of
    # i_n_loc * o_w * k_c_loc output elements.
    reason, slab = trim_reshard(SPEC, ("spatial",), (2,), 4)
    assert reason is None
    assert slab == SPEC.i_n * 1 * SPEC.o_w * SPEC.k_c * 4
    # non-spatial partitions never trim
    assert trim_reshard(SPEC, ("batch",), (2,), 4) == (None, 0.0)
    # k_h == s_h tiles exactly: nothing trimmed
    exact = ConvSpec(1, 12, 12, 3, 3, 3, 8, 3, 3)
    assert trim_reshard(exact, ("spatial",), (2,), 4) == (None, 0.0)


def test_trim_reshard_uneven_output_fwd_only():
    spec = ConvSpec(1, 18, 18, 3, 4, 4, 4, 1, 1)       # o_h=15, odd
    reason, slab = trim_reshard(spec, ("spatial",), (2,), 4)
    assert reason is not None and "gather+slice" in reason
    assert slab == 1 * 1 * spec.o_w * spec.k_c * 4     # still finite
    # ...so the grad direction stays verifiable, fwd does not
    req, opt, un_fwd = expected_collectives(spec, "spatial", 2, 4, "fwd")
    assert un_fwd is not None
    req, opt, un_grad = expected_collectives(spec, "spatial", 2, 4, "grad")
    assert un_grad is None


def test_trim_reshard_multiway_shift_unpriceable():
    import math
    spec = ConvSpec(1, 16, 16, 3, 5, 5, 4, 1, 1)       # 4-way: f=1
    reason, slab = trim_reshard(spec, ("spatial",), (4,), 4)
    assert reason is not None and "multiple sources" in reason
    assert math.isnan(slab)
    # neither direction can be priced
    for direction in ("fwd", "grad"):
        _, _, un = expected_collectives(spec, "spatial", 4, 4, direction)
        assert un is not None


def test_expected_collectives_match_costmodel():
    for part, sizes in (("batch", (2,)), ("channel", (2,)),
                        ("spatial", (2,)), (("batch", "spatial"), (2, 2)),
                        (("batch", "channel"), (2, 2))):
        entry = _costs(SPEC, sizes if len(sizes) > 1 else sizes[0])[
            part if isinstance(part, tuple) else part]
        halo = entry["halo_bytes_per_device"]
        psum = entry["comm_bytes_bwd_per_device"] - halo
        req_f, opt_f, un_f = expected_collectives(SPEC, part, sizes, 4,
                                                  "fwd")
        req_g, opt_g, un_g = expected_collectives(SPEC, part, sizes, 4,
                                                  "grad")
        assert un_f is None and un_g is None, (part, un_f, un_g)
        assert req_f["collective-permute"] == halo
        assert req_g["collective-permute"] == 2 * halo          # + VJP
        assert req_f["all-reduce"] == 0.0
        assert req_g["all-reduce"] == psum
        for kind in ("all-gather", "all-to-all", "reduce-scatter"):
            assert req_f[kind] == req_g[kind] == 0.0            # never
        assert opt_g["collective-permute"] == 2 * opt_f["collective-permute"]


def test_expected_collectives_replica_combine_on_oversized_mesh():
    """Mesh axes the partition leaves free replicate the cell; the
    shard_map transpose sums the one gradient that has no modeled psum
    over them, and the costmodel prices that all-reduce as required."""
    from repro.launch.costmodel import replica_combine_bytes
    # spatial: the input gradient pays its local shard bytes
    assert replica_combine_bytes(SPEC, ("spatial",), (2,), 4) == \
        SPEC.i_n * (SPEC.i_h // 2) * SPEC.i_w * SPEC.i_c * 4
    # pure channel: the kernel gradient pays its local shard bytes
    assert replica_combine_bytes(SPEC, ("channel",), (2,), 4) == \
        SPEC.k_h * SPEC.k_w * SPEC.i_c * (SPEC.k_c // 2) * 4
    # any channel composite: both gradients merge into modeled psums
    assert replica_combine_bytes(SPEC, ("batch", "channel"), (2, 2), 4) \
        == 0.0
    # exact-size mesh (replicated_ways=1): the modeled psum alone
    req, opt, _ = expected_collectives(SPEC, "spatial", 2, 4, "grad")
    entry = _costs(SPEC, 2)["spatial"]
    assert req["all-reduce"] == entry["comm_bytes_bwd_per_device"] - \
        entry["halo_bytes_per_device"]
    assert opt["all-reduce"] == 0.0
    req16, opt16, _ = expected_collectives(SPEC, "spatial", 2, 4, "grad",
                                           replicated_ways=16)
    assert req16["all-reduce"] == req["all-reduce"] + \
        replica_combine_bytes(SPEC, ("spatial",), (2,), 4)
    assert opt16["all-reduce"] == 0.0
    # fwd never combines gradients
    req, _, _ = expected_collectives(SPEC, "spatial", 2, 4, "fwd",
                                     replicated_ways=16)
    assert req["all-reduce"] == 0.0


def test_expected_collectives_rejects_bad_inputs():
    with pytest.raises(ValueError, match="unknown direction"):
        expected_collectives(SPEC, "spatial", 2, 4, "backward")
    with pytest.raises(ValueError, match="component"):
        expected_collectives(SPEC, ("batch", "spatial"), 2, 4, "fwd")


# ---------------------------------------------------------------------------
# verification rules
# ---------------------------------------------------------------------------

def _zero():
    return {k: 0.0 for k in COLLECTIVE_KINDS}


def test_verify_collectives_exact_and_optional():
    req = dict(_zero(), **{"collective-permute": 100.0})
    opt = dict(_zero(), **{"collective-permute": 40.0})
    ok = dict.fromkeys(COLLECTIVE_KINDS, 0)
    assert verify_collectives(dict(ok, **{"collective-permute": 100}),
                              req, "fwd", optional=opt) == []
    # required + optional (GSPMD chose to rebalance) also exact-matches
    assert verify_collectives(dict(ok, **{"collective-permute": 140}),
                              req, "fwd", optional=opt) == []
    # anything in between is a mismatch, and the message is actionable
    (v,) = verify_collectives(dict(ok, **{"collective-permute": 120}),
                              req, "fwd", optional=opt)
    assert v.rule == "collective-bytes-mismatch"
    assert "VJP transpose" in v.message


def test_verify_collectives_missing_and_unexpected():
    req = dict(_zero(), **{"collective-permute": 100.0,
                           "all-reduce": 200.0})
    got = {"collective-permute": 0, "all-reduce": 0, "all-gather": 64}
    viol = verify_collectives(got, req, "grad", label="cell")
    rules = {v.rule for v in viol}
    assert rules == {"missing-collective", "unexpected-collective"}
    permute = next(v for v in viol if "collective-permute" in v.message)
    assert "lax.ppermute" in permute.message
    assert "VJP transpose" in permute.message       # grad direction hint
    psum = next(v for v in viol if "all-reduce" in v.message)
    assert "psum" in psum.message
    gather = next(v for v in viol if "all-gather" in v.message)
    assert "reshard" in gather.message and "conv_partition_specs" \
        in gather.message


def test_verify_collectives_scalar_allowance_grad_only():
    req = dict(_zero(), **{"all-reduce": 200.0})
    over = {"all-reduce": 200 + SCALAR_REDUCE_ALLOWANCE_BYTES}
    assert verify_collectives(over, req, "grad") == []
    assert len(verify_collectives(over, req, "fwd")) == 1
    way_over = {"all-reduce": 200 + SCALAR_REDUCE_ALLOWANCE_BYTES + 1}
    assert len(verify_collectives(way_over, req, "grad")) == 1


def test_verify_collectives_sub_f32_width():
    # CPU hoists the bf16->f32 upcast above the collective: 2x the
    # declared width is admissible for dtype_bytes=2, nothing else is.
    req = dict(_zero(), **{"collective-permute": 100.0})
    assert verify_collectives({"collective-permute": 200}, req, "fwd",
                              dtype_bytes=2) == []
    assert len(verify_collectives({"collective-permute": 200}, req, "fwd",
                                  dtype_bytes=4)) == 1
    assert len(verify_collectives({"collective-permute": 150}, req, "fwd",
                                  dtype_bytes=2)) == 1


# ---------------------------------------------------------------------------
# skip semantics (this pytest process has one device: every real
# lowering must degrade to a recorded skip, never a crash or a pass)
# ---------------------------------------------------------------------------

def test_check_sharding_skips_are_recorded():
    one_way = check_sharding(SPEC, "spatial", 1)
    assert one_way.skipped and "1-way" in one_way.skipped
    assert one_way.record["verdict"] == "skipped"
    assert one_way.ok                        # a skip is not a failure...
    assert one_way.record["verdict"] != "pass"   # ...and not a pass

    bad_geo = check_sharding(ConvSpec(1, 15, 16, 3, 3, 3, 4, 1, 1),
                             "spatial", 2)
    assert "partition_viable" in bad_geo.skipped

    import jax
    too_big = check_sharding(SPEC, "spatial", jax.device_count() + 1)
    assert "xla_force_host_platform_device_count" in too_big.skipped


def test_check_sharding_rejects_bad_arguments():
    with pytest.raises(ValueError, match="n_dev"):
        check_sharding(SPEC, "spatial")
    with pytest.raises(ValueError, match="axis size"):
        check_sharding(SPEC, ("batch", "spatial"), 2)
    from repro.launch.mesh import make_host_mesh
    with pytest.raises(ValueError, match="axes"):
        check_sharding(SPEC, "spatial", mesh=make_host_mesh(shape=(1,)))


def test_plan_hook_skips_without_mesh():
    from repro.analysis.shardcheck import assert_plan_contract
    bare = ConvPlan(spec=SPEC, dtype="float32", algorithm="mec")
    res = check_plan_contract(bare)
    assert res.skipped == "no partition"
    assert assert_plan_contract(bare) is None
    parted = ConvPlan(spec=SPEC, dtype="float32", algorithm="mec",
                      partition=("spatial",), partition_axes=("data",))
    res = check_plan_contract(parted)       # no rules installed here
    assert res.skipped and "no installed mesh" in res.skipped
    assert assert_plan_contract(parted) is None


# ---------------------------------------------------------------------------
# the real thing: forced 2-device lowerings in subprocesses
# ---------------------------------------------------------------------------

def _run(prog, timeout=900):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_check_sharding_end_to_end_2dev_subprocess():
    """Unmutated executor: every partition honors the contract on a real
    2-device mesh, in both directions, and a declared precision flows
    through every lowered GEMM."""
    res = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        from repro.analysis.shardcheck import check_sharding
        from repro.core.convspec import ConvSpec
        spec = ConvSpec(2, 16, 16, 3, 3, 3, 4, 1, 1)
        out = {}
        for part in ("batch", "channel", "spatial"):
            chk = check_sharding(spec, part, 2, precision="HIGHEST")
            out[part] = {"verdict": chk.record["verdict"],
                         "violations": chk.record["violations"],
                         "flow": chk.record["precision_flow"]}
        bf16 = check_sharding(spec, "spatial", 2, dtype="bfloat16")
        out["bf16"] = {"verdict": bf16.record["verdict"],
                       "violations": bf16.record["violations"]}
        print(json.dumps(out))
    """)
    for part in ("batch", "channel", "spatial", "bf16"):
        assert res[part]["verdict"] == "pass", (part, res[part])
    for part in ("batch", "channel", "spatial"):
        flow = res[part]["flow"]
        assert flow["dot_ops"] > 0 and flow["unannotated_dot_ops"] == 0
        assert flow["hlo_dots"] > 0 and flow["hlo_unannotated"] == 0


def test_shardcheck_flags_deleted_halo_exchange_subprocess():
    """Seeded mutation 1: neuter lax.ppermute inside sharded_conv2d (the
    halo never ships).  The checker must fail BOTH directions with an
    actionable missing-collective message naming the mechanism."""
    res = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import jax.numpy as jnp
        from jax import lax as real_lax
        import repro.parallel.conv as pconv
        from repro.analysis.shardcheck import check_sharding
        from repro.core.convspec import ConvSpec

        class NoHalo:
            def __getattr__(self, n):
                return getattr(real_lax, n)
            @staticmethod
            def ppermute(x, axis_name, perm):
                return jnp.zeros_like(x)     # halo deleted

        pconv.lax = NoHalo()
        chk = check_sharding(ConvSpec(2, 16, 16, 3, 3, 3, 4, 1, 1),
                             "spatial", 2)
        print(json.dumps({"verdict": chk.record["verdict"],
                          "violations": chk.record["violations"]}))
    """)
    assert res["verdict"] == "fail"
    fwd = [v for v in res["violations"] if "] fwd:" in v]
    grad = [v for v in res["violations"] if "] grad:" in v]
    assert fwd and grad
    for v in fwd + grad:
        assert "missing-collective" in v
        assert "lax.ppermute" in v and "sharded_conv2d" in v


def test_shardcheck_flags_dropped_vjp_transpose_subprocess():
    """Seeded mutation 2: the forward halo exchange is intact but its
    VJP transpose is dropped (custom_vjp returning a zero cotangent).
    The forward program must still verify; the grad program must fail
    naming the transpose — and the plan_conv2d hook must refuse the
    plan with a ShardCheckError."""
    res = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import jax
        import jax.numpy as jnp
        from jax import lax as real_lax
        import repro.parallel.conv as pconv
        from repro.analysis.shardcheck import (ShardCheckError,
                                               assert_plan_contract,
                                               check_sharding)
        from repro.core.convspec import ConvSpec
        from repro.launch.mesh import make_host_mesh
        from repro.plan.convplan import ConvPlan

        def leaky_ppermute(x, axis_name, perm):
            @jax.custom_vjp
            def f(v):
                return real_lax.ppermute(v, axis_name, perm)
            def fwd(v):
                return real_lax.ppermute(v, axis_name, perm), None
            def bwd(_, g):
                return (jnp.zeros_like(g),)  # transpose permute dropped
            f.defvjp(fwd, bwd)
            return f(x)

        class LeakyVJP:
            def __getattr__(self, n):
                return getattr(real_lax, n)
            ppermute = staticmethod(leaky_ppermute)

        pconv.lax = LeakyVJP()
        spec = ConvSpec(2, 16, 16, 3, 3, 3, 4, 1, 1)
        chk = check_sharding(spec, "spatial", 2)
        plan = ConvPlan(spec=spec, dtype="float32", algorithm="mec",
                        partition=("spatial",), partition_axes=("data",))
        try:
            assert_plan_contract(plan, mesh=make_host_mesh())
            hook = "no-raise"
        except ShardCheckError as e:
            hook = "raised" if "permute" in str(e) else "raised-unnamed"
        print(json.dumps({"verdict": chk.record["verdict"],
                          "violations": chk.record["violations"],
                          "hook": hook}))
    """)
    assert res["verdict"] == "fail"
    assert res["hook"] == "raised"
    # the forward halo is intact: every violation is in the grad program
    assert res["violations"], res
    for v in res["violations"]:
        assert "] grad:" in v
        assert "collective-permute" in v and "VJP transpose" in v
