"""Tests for the repro.analysis subsystem (DESIGN.md §8): the HLO
memory auditor, the static Pallas geometry checker, and the
repo-invariant lint pass."""
import dataclasses
import json
import pathlib
import textwrap

import pytest

from repro.analysis import lint
from repro.analysis.pallas_check import (PALLAS_ALGORITHMS,
                                         PallasCheckError, assert_plan,
                                         check_geometry, check_plan)
from repro.core.convspec import ConvSpec
from repro.plan.convplan import ConvPlan

SMALL = ConvSpec(1, 14, 14, 4, 3, 3, 8)
STRIDED = ConvSpec(1, 23, 23, 3, 11, 11, 8, 4, 4)


# ---------------------------------------------------------------------------
# pallas_check
# ---------------------------------------------------------------------------

def test_pallas_check_accepts_all_committed_plans():
    """Acceptance criterion: every plan in the committed baseline passes."""
    from repro.analysis.memaudit import DEFAULT_PLANS, load_plans
    root = pathlib.Path(__file__).resolve().parents[1]
    plans = load_plans(root / DEFAULT_PLANS)
    assert len(plans) >= 15
    for name, plan in plans.items():
        result = check_plan(plan)
        assert result.ok, f"{name}: {result.render()}"


@pytest.mark.parametrize("alg", PALLAS_ALGORITHMS)
@pytest.mark.parametrize("spec", [SMALL, STRIDED],
                         ids=["3x3", "11x11s4"])
def test_pallas_check_accepts_planner_geometries(alg, spec):
    """Planner-derived w_blk on every Pallas variant must check clean,
    and the mirror must actually model kernels (non-empty geometry)."""
    result = check_geometry(spec, alg, None, "float32")
    assert result.ok, result.render()
    assert result.pallas and result.kernels
    assert result.vmem_bytes > 0
    expected = 2 if alg == "mec_lowered" else 1
    assert len(result.kernels) == expected


def test_pallas_check_rejects_oversized_w_blk():
    """Acceptance criterion: a deliberately-oversized w_blk is rejected
    statically — ConvPlan itself doesn't validate w_blk against o_w, so
    the checker is the gate."""
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec_fused",
                    w_blk=SMALL.o_w * 4)
    result = check_plan(plan)
    assert not result.ok
    assert {v.rule for v in result.violations} == {"w-blk-out-of-range"}
    with pytest.raises(PallasCheckError, match="w-blk-out-of-range"):
        assert_plan(plan)


def test_pallas_check_rejects_vmem_overrun():
    big = ConvSpec(1, 64, 4096, 64, 3, 3, 256)
    result = check_geometry(big, "mec_fused", 512, "float32",
                            vmem_budget=1 << 16, acc_budget=1 << 20)
    assert not result.ok
    assert any(v.rule == "vmem-budget-overrun" for v in result.violations)


def test_pallas_check_rejects_accumulator_overrun():
    result = check_geometry(SMALL, "mec_fused", SMALL.o_w, "float32",
                            acc_budget=4)   # 12*8*4 f32 >> 4 bytes
    assert any(v.rule == "accumulator-overrun"
               for v in result.violations)


def test_pallas_check_non_pallas_trivially_ok():
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec",
                    solution="A")
    result = check_plan(plan)
    assert result.ok and not result.pallas and not result.kernels


def test_pallas_check_fused2_fallback_geometry():
    """k_h < s_h (halo < 0): fused2 falls back to the v1 kernel — the
    mirror must model what actually runs."""
    spec = ConvSpec(1, 16, 16, 2, 1, 1, 4, 2, 2)
    result = check_geometry(spec, "mec_fused2", None, "float32")
    assert result.ok, result.render()
    assert result.kernels[0].name == "mec_fused"


# The resnet101_t3 stages as the benchmark runs them (3x3 inputs
# pre-padded), batch 32: (spec, convs of the stage).
RESNET_STAGES = {
    "cv4": (ConvSpec(32, 224, 224, 64, 7, 7, 64, 2, 2), 1),
    "cv9": (ConvSpec(32, 58, 58, 64, 3, 3, 64), 3),
    "cv10": (ConvSpec(32, 30, 30, 128, 3, 3, 128), 4),
    "cv11": (ConvSpec(32, 16, 16, 256, 3, 3, 256), 23),
    "cv12": (ConvSpec(32, 9, 9, 512, 3, 3, 512), 3),
}


def _pallas_call_geometry(spec, dtype, w_blk):
    """Grid and block shapes of the pallas_call the fused wrapper traces."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.mec_conv import mec_conv_fused_pallas
    x = jax.ShapeDtypeStruct((spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                             jnp.dtype(dtype))
    k = jax.ShapeDtypeStruct((spec.k_h, spec.k_w, spec.i_c, spec.k_c),
                             jnp.dtype(dtype))
    jaxpr = jax.make_jaxpr(lambda a, b: mec_conv_fused_pallas(
        a, b, (spec.s_h, spec.s_w), w_blk=w_blk, interpret=True))(x, k)

    def find(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                inner = getattr(inner, "jaxpr", inner)
                if inner is not None and hasattr(inner, "eqns"):
                    found = find(inner)
                    if found is not None:
                        return found
        return None

    gm = find(jaxpr.jaxpr).params["grid_mapping"]
    blocks = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in gm.block_mappings]
    return tuple(gm.grid), dict(zip(("input", "kernel", "output"), blocks))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("stage", sorted(RESNET_STAGES))
def test_fused_mirror_matches_pallas_call(stage, dtype):
    """The checker's mec_fused mirror derives its grid and blocks from
    the kernel's own blocking, and they are the pallas_call's."""
    from repro.kernels.ops import pick_w_blk
    spec, _ = RESNET_STAGES[stage]
    w_blk = pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)
    result = check_geometry(spec, "mec_fused", w_blk, dtype)
    assert result.ok, result.render()
    (kernel,) = result.kernels
    grid, blocks = _pallas_call_geometry(spec, dtype, w_blk)
    assert kernel.grid == grid
    assert kernel.blocks == blocks


def test_fused_blocking_engages_on_every_stage():
    """Many output rows per grid step: each tap's dot has M >= 256, cv4
    takes >= 8 rows a step, cv11 and cv12 whole planes of several
    images, and a forward's 34 convs take a few hundred steps (one row
    a step took 84,224)."""
    from repro.kernels.mec_conv import fused_blocks
    from repro.kernels.ops import pick_w_blk
    steps = 0
    for name, (s, count) in RESNET_STAGES.items():
        fb = fused_blocks(s.i_n, s.i_h, s.i_w, s.i_c, s.k_h, s.k_w, s.k_c,
                          s.s_h, s.s_w,
                          pick_w_blk(s.o_w, s.k_c, _warn_env=False), 2)
        assert fb.dot_rows >= 256, (name, fb.describe())
        assert fb.padded_flop_share < 0.6, (name, fb.describe())
        steps += count * fb.steps
        if name == "cv4":
            assert fb.hb >= 8, fb.describe()
        if name in ("cv11", "cv12"):
            assert fb.hb == s.o_h and fb.nb > 1, fb.describe()
    assert steps < 1000, steps


def test_plan_conv2d_never_returns_rejected_pallas_plan(monkeypatch):
    """The planner wiring: a Pallas pick whose geometry fails the static
    check raises at plan time instead of faulting at execute time."""
    from repro.plan import convplan

    def bad_w_blk(spec, algorithm):
        return None if algorithm not in convplan._PALLAS_ALGOS \
            else spec.o_w * 10
    monkeypatch.setattr(convplan, "_pallas_w_blk", bad_w_blk)
    monkeypatch.setattr(
        "repro.launch.costmodel.pick_conv2d_algorithm",
        lambda spec, backend, **kw: "mec_fused")
    with pytest.raises(PallasCheckError):
        convplan.plan_conv2d(SMALL, mode="analytic")


def test_measure_candidates_skips_rejected_pallas(monkeypatch):
    from repro.plan import convplan

    def bad_w_blk(spec, algorithm):
        return None if algorithm not in convplan._PALLAS_ALGOS \
            else spec.o_w * 10
    monkeypatch.setattr(convplan, "_pallas_w_blk", bad_w_blk)
    with pytest.warns(UserWarning, match="measured planning skips"):
        times = convplan.measure_candidates(
            SMALL, candidates=("direct", "mec_fused"), iters=1, warmup=0)
    assert "direct" in times and "mec_fused" not in times


# ---------------------------------------------------------------------------
# memaudit
# ---------------------------------------------------------------------------

def test_memaudit_single_cell_passes():
    from repro.analysis.memaudit import audit_plan
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec",
                    solution="A")
    rec, failures = audit_plan("unit/small", plan)
    assert failures == []
    assert rec["verdict"] == "pass"
    assert rec["source"] == "memory_analysis"
    assert rec["predicted_overhead_bytes"] == \
        SMALL.i_n * SMALL.o_w * SMALL.i_h * SMALL.k_w * SMALL.i_c * 4
    assert rec["measured_temp_bytes"] >= rec["predicted_overhead_bytes"]


def test_memaudit_im2col_exact():
    """im2col is the calibration cell: XLA materializes exactly the
    Toeplitz matrix, ratio 1.000."""
    from repro.analysis.memaudit import audit_plan
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="im2col")
    rec, failures = audit_plan("unit/im2col", plan)
    assert failures == []
    assert rec["ratio"] == pytest.approx(1.0, abs=0.02)


def test_memaudit_report_schema_and_crosscheck():
    from repro.analysis.memaudit import run_audit
    from repro.bench.report import validate_report
    plans = {"unit/small": ConvPlan(spec=SMALL, dtype="float32",
                                    algorithm="mec", solution="A")}
    doc, failures = run_audit(plans=plans)
    assert failures == []
    assert validate_report(doc) == []
    assert doc["suite"] == "memaudit"
    # mec cell => an im2col companion record + a mec<im2col crosscheck
    algs = {r["algorithm"] for r in doc["results"]}
    assert algs == {"mec", "im2col"}
    (cc,) = doc["crosscheck"]
    assert cc["ok"] == "yes"
    assert cc["mec_temp_bytes"] < cc["im2col_temp_bytes"]


def test_memaudit_detects_model_drift():
    """If the implementation's footprint leaves the model's band, the
    auditor fails — simulated by shrinking the prediction (equivalent to
    an Eq. 3 regression)."""
    from repro.analysis import memaudit
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec",
                    solution="A")
    orig = memaudit.memory.algorithm_overhead
    try:
        memaudit.memory.algorithm_overhead = \
            lambda s, a, padding="VALID": orig(s, a, padding) // 10
        rec, failures = memaudit.audit_plan("unit/drift", plan)
    finally:
        memaudit.memory.algorithm_overhead = orig
    assert rec["verdict"] == "fail" and failures


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

def _lint_src(tmp_path, source, rel="src/repro/somefile.py"):
    p = tmp_path / "f.py"
    p.write_text(textwrap.dedent(source))
    return lint.lint_file(p, rel)


def test_lint_redetects_pr4_dropped_kwarg(tmp_path):
    """Acceptance criterion: reverting the PR-4 fix shape — a conv entry
    point accepting precision and never forwarding it — is re-detected."""
    findings = _lint_src(tmp_path, """
        def mec_conv2d(inp, kernel, stride=1, precision=None):
            return _run(inp, kernel, stride)
        """)
    assert [f.rule for f in findings] == ["accepted-kwarg-not-forwarded"]
    assert findings[0].symbol == "mec_conv2d:precision"


def test_lint_forwarded_and_underscore_params_ok(tmp_path):
    assert _lint_src(tmp_path, """
        def conv(inp, kernel, precision=None, _debug=False, **kw):
            return run(inp, kernel, precision=precision, **kw)
        """) == []


def test_lint_stub_bodies_exempt(tmp_path):
    assert _lint_src(tmp_path, """
        def iface(a, b):
            ...

        def iface2(a, b):
            raise NotImplementedError

        def iface3(a, b):
            \"\"\"doc\"\"\"
            pass
        """) == []


def test_lint_suppression_comment(tmp_path):
    findings = _lint_src(tmp_path, """
        def conv(inp, kernel, precision=None):  # lint-ignore: accepted-kwarg-not-forwarded
            return run(inp, kernel)
        """)
    assert findings == []


def test_lint_environ_read_flagged_outside_compat(tmp_path):
    src = """
        import os
        FLAG = os.environ.get("REPRO_FLAG")
        OTHER = os.getenv("OTHER")
        THIRD = os.environ["THIRD"]
        """
    findings = _lint_src(tmp_path, src)
    assert [f.rule for f in findings] == \
        ["raw-environ-read-outside-compat"] * 3
    # the same reads inside the compat shim (or plan cache) are allowed
    assert _lint_src(tmp_path, src, rel="src/repro/core/compat.py") == []
    assert _lint_src(tmp_path, src, rel="src/repro/plan/cache.py") == []


def test_lint_environ_write_not_flagged(tmp_path):
    assert _lint_src(tmp_path, """
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        """) == []


def test_lint_deprecated_acc_bytes_env(tmp_path):
    findings = _lint_src(
        tmp_path, """
        import os
        v = os.environ.get("REPRO_MEC_ACC_BYTES")
        """, rel="src/repro/core/compat.py")   # allowed file: env rule off
    assert [f.rule for f in findings] == ["deprecated-acc-bytes-env"]


def test_lint_bare_dot_precision_flagged_in_numeric_core(tmp_path):
    src = """
        import jax.numpy as jnp
        def f(a, b):
            return jnp.einsum("ij,jk->ik", a, b)
        """
    findings = _lint_src(tmp_path, src, rel="src/repro/core/x.py")
    assert [f.rule for f in findings] == ["no-bare-dot-precision"]
    assert findings[0].symbol == "f:jnp.einsum"
    # same call inside kernels/parallel is in scope too...
    assert _lint_src(tmp_path, src, rel="src/repro/parallel/x.py") != []
    # ...but bench/launch glue may use backend defaults
    assert _lint_src(tmp_path, src, rel="src/repro/bench/x.py") == []


def test_lint_bare_dot_precision_annotated_or_splat_ok(tmp_path):
    assert _lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp
        def f(a, b, kw):
            x = jnp.dot(a, b, precision="highest")
            y = jnp.einsum("ij,jk->ik", a, b,
                           preferred_element_type=jnp.float32)
            z = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), **kw)
            return x + y + z
        """, rel="src/repro/kernels/x.py") == []


def test_lint_bare_dot_precision_suppression(tmp_path):
    assert _lint_src(tmp_path, """
        import jax.numpy as jnp
        def f(a, b):
            return jnp.dot(a, b)  # lint-ignore: no-bare-dot-precision
        """, rel="src/repro/core/x.py") == []


def test_lint_baseline_roundtrip_and_fixed_detection(tmp_path):
    f1 = lint.Finding("accepted-kwarg-not-forwarded", "src/a.py",
                      "f:x", 3, "msg")
    f2 = lint.Finding("raw-environ-read-outside-compat", "src/b.py",
                      "os.getenv:K", 9, "msg")
    path = tmp_path / "baseline.json"
    lint.write_baseline([f1, f2], path)
    keys = lint.load_baseline(path)
    assert keys == sorted([f1.key(), f2.key()])
    # f2 fixed, f3 new
    f3 = lint.Finding("deprecated-acc-bytes-env", "src/c.py",
                      "os.getenv:REPRO_MEC_ACC_BYTES", 1, "msg")
    split = lint.apply_baseline([f1, f3], keys)
    assert split["new"] == [f3]
    assert split["grandfathered"] == [f1]
    assert split["fixed"] == [f2.key()]


def test_lint_baseline_version_gate(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"lint_baseline_version": 99,
                                "findings": []}))
    with pytest.raises(ValueError, match="version"):
        lint.load_baseline(path)


def test_lint_tree_is_clean_against_committed_baseline():
    """Acceptance criterion: the lint suite starts green on a clean
    checkout — every current finding is grandfathered or suppressed."""
    root = lint.repo_root()
    baseline = lint.load_baseline(
        root / "benchmarks/baselines/lint_baseline.json")
    split = lint.apply_baseline(lint.lint_tree(root), baseline)
    assert split["new"] == [], [f.render() for f in split["new"]]


# ---------------------------------------------------------------------------
# autotune trial replay (the --suite pallas coverage extension)
# ---------------------------------------------------------------------------

def test_autotune_stage2_w520_grid_passes_geometry():
    """The committed w520 cell tuned w_blk=520, past pick_w_blk's 512
    default cap — every stage-2 grid candidate the autotuner trials must
    be geometry-admissible, including that over-cap one."""
    from repro.plan.convplan import _pallas_w_blk, _stage2_trials
    spec = ConvSpec(1, 3, 522, 3, 3, 3, 8, 1, 1)       # o_w = 520
    assert _pallas_w_blk(spec, "mec_fused") == 512
    knob, plans = _stage2_trials(spec, "float32", "mec_fused", None, "cpu")
    assert knob == "w_blk"
    assert set(plans) == {"256", "512", "520"}
    for label, trial in plans.items():
        res = check_geometry(trial.spec, "mec_fused", trial.w_blk,
                             "float32")
        assert res.ok, f"w_blk={label}: {res.render()}"


def test_committed_autotune_trials_replay_clean():
    """Every (Pallas) w_blk the committed BENCH_autotune.json actually
    trialed replays through the static geometry gate."""
    root = pathlib.Path(__file__).resolve().parents[1]
    doc = json.loads((root / "BENCH_autotune.json").read_text())
    replayed = 0
    for r in doc["results"]:
        tuning = r.get("tuning")
        if not tuning or tuning.get("algorithm") not in PALLAS_ALGORITHMS:
            continue
        spec = ConvSpec(**r["run_spec"])
        for label, t in tuning["trials"].items():
            res = check_geometry(spec, tuning["algorithm"], t.get("w_blk"),
                                 r["dtype"])
            assert res.ok, f"{r['scenario']} w_blk={label}: {res.render()}"
            replayed += 1
    assert replayed >= 3      # the w520 grid alone contributes three


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_lint_and_pallas_suites_green():
    from repro.analysis.__main__ import main
    assert main(["--suite", "lint"]) == 0
    assert main(["--suite", "pallas"]) == 0
