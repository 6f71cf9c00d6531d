"""Numerics contract checker (repro.analysis.numcheck, DESIGN.md §8.5):
signature extraction + detector units, the narrow-widen taint pass, skip
semantics, the plan hook, the measured error probe vs the f64 oracle
(property-tested across backends x dtypes x seeds with tolerances drawn
from the contracts, never this file), the fft/winograd output-cast HLO
regression, and three seeded-mutation subprocess tests proving the
checker catches a dropped ``preferred_element_type``, a stray mid-chain
downcast, and a neutered f32 weight-grad accumulation — each naming the
culprit op."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import numcheck
from repro.analysis.numcheck import (NUMCHECK_ALGORITHMS, NumCheckError,
                                     assert_plan_numerics, cast_kind,
                                     cell_numcheck, check_numerics,
                                     error_probe, extract_signature,
                                     f64_conv2d, f64_conv2d_grads,
                                     hlo_convert_counts,
                                     narrow_widen_findings, probe_spec,
                                     signature_findings)
from repro.core.numerics import CONTRACT_DTYPES, contract_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = probe_spec()

# ---------------------------------------------------------------------------
# units: cast classification, HLO convert counting, the f64 oracle
# ---------------------------------------------------------------------------

def test_cast_kind_classification():
    assert cast_kind("float32", "bfloat16") == "narrow"
    assert cast_kind("float16", "float32") == "widen"
    assert cast_kind("bfloat16", "float16") == "reformat"
    assert cast_kind("float32", "float32") == "same"
    assert cast_kind("float32", "complex64") == "complexify"
    assert cast_kind("complex64", "float32") == "realify"
    assert cast_kind("complex128", "complex64") == "complex-narrow"
    assert cast_kind("complex64", "complex128") == "complex-widen"
    assert cast_kind("int32", "float32") == "other"


def test_hlo_convert_counts_parses_fusion_lines():
    hlo = textwrap.dedent("""\
        %fused = bf16[2,14,14,4]{3,2,1,0} convert(f32[2,14,14,4]{3,2,1,0} %y)
        %w = f32[3,3,3,4]{3,2,1,0} convert(bf16[3,3,3,4]{3,2,1,0} %k)
        %z = bf16[2,14,14,4]{3,2,1,0} convert(f32[2,14,14,4]{3,2,1,0} %q)
        %noise = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
    """)
    counts = hlo_convert_counts(hlo)
    assert counts[("f32", "bf16")] == 2
    assert counts[("bf16", "f32")] == 1


def test_f64_oracle_matches_lax_conv():
    rng = np.random.RandomState(0)
    x = rng.randn(SPEC.i_n, SPEC.i_h, SPEC.i_w, SPEC.i_c).astype(np.float32)
    k = rng.randn(SPEC.k_h, SPEC.k_w, SPEC.i_c, SPEC.k_c).astype(np.float32)
    ref = jax.lax.conv_general_dilated(
        x, k, (SPEC.s_h, SPEC.s_w), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    got = f64_conv2d(x.astype(np.float64), k.astype(np.float64),
                     SPEC.s_h, SPEC.s_w)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_f64_oracle_grads_match_jax():
    rng = np.random.RandomState(1)
    x = rng.randn(SPEC.i_n, SPEC.i_h, SPEC.i_w, SPEC.i_c).astype(np.float32)
    k = rng.randn(SPEC.k_h, SPEC.k_w, SPEC.i_c, SPEC.k_c).astype(np.float32)

    def loss(xv, kv):
        o = jax.lax.conv_general_dilated(
            xv, kv, (SPEC.s_h, SPEC.s_w), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        return jnp.sum(o * o)

    dx_j, dk_j = jax.grad(loss, argnums=(0, 1))(x, k)
    x64, k64 = x.astype(np.float64), k.astype(np.float64)
    g64 = 2.0 * f64_conv2d(x64, k64, SPEC.s_h, SPEC.s_w)
    dx, dk = f64_conv2d_grads(x64, k64, g64, SPEC.s_h, SPEC.s_w)
    np.testing.assert_allclose(dx, np.asarray(dx_j), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dk, np.asarray(dk_j), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# units: signature extraction + static detectors
# ---------------------------------------------------------------------------

def test_extract_signature_sees_dot_and_casts():
    def f(a, b):
        y = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return y.astype(a.dtype)

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((4, 8), "bfloat16"),
        jax.ShapeDtypeStruct((8, 2), "bfloat16"))
    sig = extract_signature(closed)
    [dot] = sig["dots"]
    assert dot["op"] == "dot_general"
    assert dot["operands"] == ["bfloat16", "bfloat16"]
    assert dot["out"] == "float32"
    assert dot["preferred_element_type"] == "float32"
    assert not dot["pallas"]
    assert ("float32", "bfloat16") in [(c["src"], c["dst"])
                                       for c in sig["casts"]]


def _findings(sig, algorithm, direction, dtype):
    return signature_findings(sig, contract_for(algorithm), direction, dtype)


def test_detector_accumulation_fires_on_sub_f32_output():
    sig = {"dots": [{"op": "dot_general",
                     "operands": ["bfloat16", "bfloat16"],
                     "out": "bfloat16", "preferred_element_type": None,
                     "precision": None, "pallas": False}],
           "casts": []}
    rules = [v.rule for v in _findings(sig, "im2col", "grad", "bfloat16")]
    assert "accumulation" in rules


def test_detector_disallowed_dtype_and_f64_leak():
    sig = {"dots": [],
           "casts": [{"op": "convert_element_type", "src": "float32",
                      "dst": "bfloat16", "kind": "narrow", "pallas": False},
                     {"op": "convert_element_type", "src": "float32",
                      "dst": "float64", "kind": "widen", "pallas": False}]}
    rules = {v.rule for v in _findings(sig, "mec", "fwd", "float32")}
    # bf16 in an f32 program is a stray downcast; f64 is its own rule.
    assert rules == {"disallowed-dtype", "f64-leak"}


def test_detector_pallas_accum_requires_explicit_preferred_type():
    sig = {"dots": [{"op": "dot_general",
                     "operands": ["float16", "float16"],
                     "out": "float32", "preferred_element_type": None,
                     "precision": None, "pallas": True}],
           "casts": []}
    rules = [v.rule for v in _findings(sig, "mec_fused", "grad", "float16")]
    assert "pallas-accum" in rules
    # the same dot with the annotation is clean
    sig["dots"][0]["preferred_element_type"] = "float32"
    assert not _findings(sig, "mec_fused", "grad", "float16")


def test_detector_output_cast_count():
    base = {"op": "convert_element_type", "src": "float32",
            "dst": "bfloat16", "kind": "narrow", "pallas": False}
    # zero narrows: accumulator never narrowed
    rules = [v.rule for v in _findings({"dots": [], "casts": []},
                                       "im2col", "fwd", "bfloat16")]
    assert "output-cast-count" in rules
    # exactly one: clean
    assert not _findings({"dots": [], "casts": [dict(base)]},
                         "im2col", "fwd", "bfloat16")
    # two: double rounding
    rules = [v.rule for v in _findings(
        {"dots": [], "casts": [dict(base), dict(base)]},
        "im2col", "fwd", "bfloat16")]
    assert "output-cast-count" in rules
    # grad direction never counts output narrows
    assert not _findings({"dots": [], "casts": []},
                         "im2col", "grad", "bfloat16")


def test_narrow_widen_taint_fires_through_structural_ops_only():
    def bad(x):
        y = x.astype(jnp.bfloat16)
        y = y.reshape(2, 8).T
        return y.astype(jnp.float32)

    def ok(x):
        y = x.astype(jnp.bfloat16)
        z = y * y                       # arithmetic consumes the taint
        return z.astype(jnp.float32)

    s = jax.ShapeDtypeStruct((4, 4), "float32")
    bad_v = narrow_widen_findings(jax.make_jaxpr(bad)(s), "fwd")
    assert [v.rule for v in bad_v] == ["narrow-widen"]
    assert "bfloat16" in bad_v[0].message
    assert not narrow_widen_findings(jax.make_jaxpr(ok)(s), "fwd")


# ---------------------------------------------------------------------------
# the checker: contracts, skips, passing cells, the bench/plan wiring
# ---------------------------------------------------------------------------

def test_every_swept_backend_declares_a_contract():
    for alg in NUMCHECK_ALGORITHMS:
        c = contract_for(alg)
        assert c is not None, alg
        for dtype in CONTRACT_DTYPES:
            assert c.tolerance(dtype, "fwd") > 0
            assert c.tolerance(dtype, "grad") >= c.tolerance(dtype, "fwd")
        allowed = c.allowed_dtypes("bfloat16")
        assert "bfloat16" in allowed and "float32" in allowed
        assert ("complex64" in allowed) == c.complex_pair


def test_check_numerics_skips_are_not_failures():
    unknown = check_numerics(SPEC, "does_not_exist", "float32", probe=False)
    assert unknown.ok and unknown.skipped and \
        unknown.record["verdict"] == "skipped"
    from repro.core.convspec import ConvSpec
    off = ConvSpec(2, 16, 16, 3, 5, 5, 4, 1, 1)
    wino = check_numerics(off, "winograd", "float32", probe=False)
    assert wino.skipped and "3x3" in wino.skipped


@pytest.mark.parametrize("alg", ["im2col", "fft", "mec", "mec_fused"])
def test_static_contract_passes_bf16(alg):
    res = check_numerics(SPEC, alg, "bfloat16", interpret=True, probe=False)
    assert res.ok and not res.skipped, res.render()
    fwd = res.record["directions"]["fwd"]
    assert fwd["dots"] >= 1
    assert fwd["narrows_to_input"] == 1
    if alg == "mec_fused":
        assert fwd["pallas_dots"] >= 1


def test_cell_numcheck_is_reduced_and_memoized():
    numcheck._CELL_CACHE.clear()
    a = cell_numcheck(SPEC, "im2col", "bfloat16", interpret=True)
    assert set(a) == {"verdict", "skipped_reason", "violations"}
    assert a["verdict"] == "pass"
    b = cell_numcheck(SPEC, "im2col", "bfloat16", interpret=True)
    assert a == b and len(numcheck._CELL_CACHE) == 1


class _FakePlan:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_assert_plan_numerics_hook(monkeypatch):
    # auto / unresolved plans are not checkable -> silently fine
    assert_plan_numerics(_FakePlan(algorithm="auto", spec=SPEC,
                                   dtype="float32"))
    assert_plan_numerics(_FakePlan(algorithm=None, spec=SPEC,
                                   dtype="float32"))
    # a healthy resolved plan passes (and is duck-typed, no repro.plan)
    assert_plan_numerics(_FakePlan(algorithm="im2col", spec=SPEC,
                                   dtype="bfloat16", solution="auto",
                                   precision=None))
    # a failing check raises and the verdict is memoized
    calls = []

    def fake_check(spec, algorithm, dtype="float32", **kw):
        calls.append(algorithm)
        return numcheck.NumCheck(algorithm, dtype,
                                 [numcheck.ContractViolation(
                                     "accumulation", "grad", "boom")],
                                 {"verdict": "fail"})

    monkeypatch.setattr(numcheck, "check_numerics", fake_check)
    bad = _FakePlan(algorithm="im2col", spec="fake-spec-for-hook-test",
                    dtype="bfloat16", solution="auto", precision=None)
    with pytest.raises(NumCheckError, match="accumulation"):
        assert_plan_numerics(bad)
    with pytest.raises(NumCheckError):
        assert_plan_numerics(bad)           # cached verdict, no re-trace
    assert len(calls) == 1


def test_plan_conv2d_asserts_the_contract():
    # the real wiring: plan_conv2d runs the hook before returning a plan
    from repro.plan.convplan import plan_conv2d
    plan = plan_conv2d(SPEC, dtype="bfloat16", mode="analytic")
    assert plan.algorithm            # resolved and contract-clean


# ---------------------------------------------------------------------------
# measured error budgets (tolerances from the contract, never this file)
# ---------------------------------------------------------------------------

ALGS_ST = st.sampled_from(NUMCHECK_ALGORITHMS)
DTYPES_ST = st.sampled_from(["float32", "bfloat16"])
SEEDS_ST = st.integers(min_value=0, max_value=3)


@settings(max_examples=10, deadline=None)
@given(ALGS_ST, DTYPES_ST, SEEDS_ST)
def test_property_probe_error_within_contract_budget(alg, dtype, seed):
    c = contract_for(alg)
    errs = error_probe(SPEC, alg, dtype, interpret=True, seed=seed)
    assert errs["fwd_err"] <= c.tolerance(dtype, "fwd"), (alg, dtype, errs)
    grad_tol = c.tolerance(dtype, "grad")
    assert errs["din_err"] <= grad_tol, (alg, dtype, errs)
    assert errs["dk_err"] <= grad_tol, (alg, dtype, errs)


# ---------------------------------------------------------------------------
# fft / winograd output round-trip: exactly one final narrowing cast
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alg", ["fft", "winograd"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_output_roundtrip_single_narrow(alg, dtype):
    """The f32 (or c64) pipeline must narrow back to the input dtype
    exactly once in the jaxpr (a second narrow would be double rounding).
    The contract lives at the jaxpr level: how many converts a backend's
    optimized HLO keeps after fusion is the compiler's business."""
    from repro.core.conv_api import conv2d

    def fwd(xv, kv):
        return conv2d(xv, kv, stride=(SPEC.s_h, SPEC.s_w), algorithm=alg,
                      partition="none")

    x_s = jax.ShapeDtypeStruct((SPEC.i_n, SPEC.i_h, SPEC.i_w, SPEC.i_c),
                               dtype)
    k_s = jax.ShapeDtypeStruct((SPEC.k_h, SPEC.k_w, SPEC.i_c, SPEC.k_c),
                               dtype)
    sig = extract_signature(jax.make_jaxpr(fwd)(x_s, k_s))
    narrows = [c for c in sig["casts"]
               if c["kind"] == "narrow" and c["dst"] == dtype]
    assert len(narrows) == 1, narrows
    assert narrows[0]["src"] == "float32", narrows


# ---------------------------------------------------------------------------
# seeded mutations: the checker must fail naming the culprit op
# ---------------------------------------------------------------------------

def _run(prog, timeout=900):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                         env=env, cwd=REPO, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_MUTATION_HEADER = """
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        import jax.numpy as jnp
        from repro.analysis.numcheck import check_numerics, probe_spec
"""


def test_mutation_dropped_preferred_element_type_is_caught():
    """Strip ``preferred_element_type`` off im2col's GEMM (the PR 4/PR 5
    bug class): the bf16 cell must fail with an accumulation violation
    naming the dot."""
    res = _run(_MUTATION_HEADER + """
        import repro.core.im2col as im2col_mod

        class _BareDotJnp:
            def __getattr__(self, name):
                return getattr(jnp, name)
            def dot(self, a, b, precision=None, preferred_element_type=None):
                return jnp.dot(a, b, precision=precision)

        im2col_mod.jnp = _BareDotJnp()
        chk = check_numerics(probe_spec(), "im2col", "bfloat16",
                             probe=False)
        print(json.dumps({"verdict": chk.record["verdict"],
                          "violations": chk.record["violations"]}))
    """)
    assert res["verdict"] == "fail"
    acc = [v for v in res["violations"] if v.startswith("[accumulation]")]
    assert acc and any("dot_general" in v for v in acc), res["violations"]


def test_mutation_stray_mid_chain_downcast_is_caught():
    """Insert a stray bf16 round-trip after ``mec_lower`` in an f32
    program: disallowed-dtype (naming the convert) plus the
    narrow-widen taint must both fire."""
    res = _run(_MUTATION_HEADER + """
        import repro.core.mec as mec_mod
        import repro.core.conv_api as conv_api

        _orig = mec_mod.mec_lower
        def leaky_lower(inp, k_w, s_w):
            low = _orig(inp, k_w, s_w)
            return low.astype(jnp.bfloat16).astype(low.dtype)
        mec_mod.mec_lower = leaky_lower
        conv_api.mec_lower = leaky_lower

        chk = check_numerics(probe_spec(), "mec", "float32", probe=False)
        print(json.dumps({"verdict": chk.record["verdict"],
                          "violations": chk.record["violations"]}))
    """)
    assert res["verdict"] == "fail"
    rules = {v.split("]")[0].lstrip("[") for v in res["violations"]}
    assert "disallowed-dtype" in rules, res["violations"]
    assert "narrow-widen" in rules, res["violations"]
    assert any("convert_element_type" in v and "bfloat16" in v
               for v in res["violations"]), res["violations"]


def test_mutation_neutered_weight_grad_accumulation_is_caught():
    """Replace the VJP's f32-accumulating weight grad with a bf16
    einsum: the grad direction must fail with an accumulation violation
    naming the dot (the forward stays clean)."""
    res = _run(_MUTATION_HEADER + """
        from jax import lax
        import repro.core.conv_api as conv_api

        def bf16_wgrad(inp, g, s_h, s_w, k_h, k_w, precision=None):
            low = conv_api.mec_lower(inp, k_w, s_w)
            o_h = g.shape[1]
            gb = g.astype(jnp.bfloat16)
            lowb = low.astype(jnp.bfloat16)
            rows = []
            for r in range(k_h):
                lr = lax.slice_in_dim(lowb, r, r + s_h * (o_h - 1) + 1,
                                      stride=s_h, axis=2)
                rows.append(jnp.einsum("nwhjc,nhwo->jco", lr, gb))
            return jnp.stack(rows, axis=0)

        conv_api._mec_weight_grad = bf16_wgrad
        chk = check_numerics(probe_spec(), "mec", "bfloat16", probe=False)
        fwd_only = check_numerics(probe_spec(), "mec", "bfloat16",
                                  probe=False, directions=("fwd",))
        print(json.dumps({"verdict": chk.record["verdict"],
                          "violations": chk.record["violations"],
                          "fwd_verdict": fwd_only.record["verdict"]}))
    """)
    assert res["fwd_verdict"] == "pass"
    assert res["verdict"] == "fail"
    acc = [v for v in res["violations"]
           if v.startswith("[accumulation] grad")]
    assert acc and any("dot_general" in v for v in acc), res["violations"]
