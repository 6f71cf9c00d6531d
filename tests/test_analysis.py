"""Tests for the repro.analysis subsystem (DESIGN.md §8): the HLO
memory auditor and the repo-invariant lint pass; and for mec_fused's
geometry rule (``repro.kernels.mec_conv.fused_refusal``), which the
planner asks of every kernel plan."""
import json
import pathlib
import textwrap

import pytest

from repro.analysis import lint
from repro.core.convspec import ConvSpec
from repro.kernels import ops
from repro.kernels.mec_conv import fused_refusal
from repro.plan.convplan import ConvPlan

SMALL = ConvSpec(1, 14, 14, 4, 3, 3, 8)
STRIDED = ConvSpec(1, 23, 23, 3, 11, 11, 8, 4, 4)


def _planner_w_blk(spec):
    return ops.pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)


# ---------------------------------------------------------------------------
# mec_fused's geometry rule (repro.kernels.mec_conv.fused_refusal)
# ---------------------------------------------------------------------------

def test_fused_refusal_admits_every_committed_plan_geometry():
    """Every plan in the committed baseline runs, and every one of its
    geometries would run on mec_fused at the planner's block."""
    from repro.analysis.memaudit import DEFAULT_PLANS, load_plans
    root = pathlib.Path(__file__).resolve().parents[1]
    plans = load_plans(root / DEFAULT_PLANS)
    assert len(plans) >= 15
    for name, plan in plans.items():
        if plan.algorithm == "mec_fused":
            assert fused_refusal(plan.spec, plan.dtype, plan.w_blk) is None
        why = fused_refusal(plan.spec, plan.dtype,
                            _planner_w_blk(plan.spec))
        assert why is None, f"{name}: {why}"


@pytest.mark.parametrize("spec", [SMALL, STRIDED],
                         ids=["3x3", "11x11s4"])
def test_fused_refusal_admits_planner_geometries(spec):
    """The planner's block runs, and its blocking holds a working set."""
    from repro.kernels.mec_conv import fused_blocks
    w_blk = _planner_w_blk(spec)
    assert fused_refusal(spec, "float32", w_blk) is None
    fb = fused_blocks(spec.i_n, spec.i_h, spec.i_w, spec.i_c, spec.k_h,
                      spec.k_w, spec.k_c, spec.s_h, spec.s_w, w_blk, 4)
    assert 0 < fb.vmem_bytes <= ops.vmem_bytes()


def test_fused_refusal_rejects_oversized_w_blk():
    """A deliberately oversized w_blk is refused — ConvPlan itself does
    not validate w_blk against o_w, so the rule is the gate, and the
    planner raises on such a plan."""
    from repro.plan.convplan import _assert_kernel_runs
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec_fused",
                    w_blk=SMALL.o_w * 4)
    why = fused_refusal(plan.spec, plan.dtype, plan.w_blk)
    assert why == f"w_blk={SMALL.o_w * 4} outside [1, o_w={SMALL.o_w}]"
    with pytest.raises(ValueError, match="outside"):
        _assert_kernel_runs(plan)


def test_fused_refusal_rejects_vmem_overrun(monkeypatch):
    big = ConvSpec(1, 64, 4096, 64, 3, 3, 256)
    monkeypatch.setattr(ops, "vmem_bytes", lambda: 1 << 16)
    monkeypatch.setattr(ops, "accumulator_budget",
                        lambda _warn_env=True: 1 << 20)
    assert "exceeds VMEM" in fused_refusal(big, "float32", 512)


def test_fused_refusal_rejects_accumulator_overrun(monkeypatch):
    monkeypatch.setattr(ops, "accumulator_budget",
                        lambda _warn_env=True: 4)   # < one f32 row
    why = fused_refusal(SMALL, "float32", SMALL.o_w)
    assert "accumulator budget 4 B" in why


def test_fused_refusal_rejects_unaligned_column_blocks():
    """Several column blocks must start on whole sublane tiles: 8 rows
    in float32, 16 in bfloat16."""
    wide = ConvSpec(1, 3, 42, 4, 3, 3, 8)           # o_w = 40
    assert fused_refusal(wide, "float32", 20) is not None
    assert fused_refusal(wide, "float32", 24) is None
    assert fused_refusal(wide, "bfloat16", 24) is not None
    assert fused_refusal(wide, "bfloat16", 32) is None


def test_kernel_rule_skips_plans_without_a_kernel():
    """The planner asks the kernel's rule of mec_fused plans only."""
    from repro.plan.convplan import _kernel_refusal
    plan = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec",
                    solution="A", w_blk=None)
    assert _kernel_refusal(plan) is None
    bad = ConvPlan(spec=SMALL, dtype="float32", algorithm="mec_fused",
                   w_blk=SMALL.o_w * 4)
    assert _kernel_refusal(bad) == fused_refusal(SMALL, "float32",
                                                 SMALL.o_w * 4)


# The resnet101_t3 stages as the benchmark runs them (3x3 inputs
# pre-padded), batch 32: (spec, convs of the stage).
RESNET_STAGES = {
    "cv4": (ConvSpec(32, 224, 224, 64, 7, 7, 64, 2, 2), 1),
    "cv9": (ConvSpec(32, 58, 58, 64, 3, 3, 64), 3),
    "cv10": (ConvSpec(32, 30, 30, 128, 3, 3, 128), 4),
    "cv11": (ConvSpec(32, 16, 16, 256, 3, 3, 256), 23),
    "cv12": (ConvSpec(32, 9, 9, 512, 3, 3, 512), 3),
}


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
        return None if algorithm != "mec_fused" else spec.o_w * 10
    monkeypatch.setattr(convplan, "_pallas_w_blk", bad_w_blk)
    monkeypatch.setattr(
        "repro.launch.costmodel.pick_conv2d_algorithm",
        lambda spec, backend, **kw: "mec_fused")
    with pytest.raises(ValueError, match="mec_fused cannot run the plan"):
        convplan.plan_conv2d(SMALL, mode="analytic")


def test_measure_candidates_skips_rejected_pallas(monkeypatch):
    from repro.plan import convplan

    def bad_w_blk(spec, algorithm):
        return None if algorithm != "mec_fused" else spec.o_w * 10
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
# autotune trial replay
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
        why = fused_refusal(trial.spec, "float32", trial.w_blk)
        assert why is None, f"w_blk={label}: {why}"


def test_committed_autotune_trials_replay_clean():
    """Every mec_fused w_blk the committed BENCH_autotune.json actually
    trialed is one the kernel's rule admits."""
    root = pathlib.Path(__file__).resolve().parents[1]
    doc = json.loads((root / "BENCH_autotune.json").read_text())
    replayed = 0
    for r in doc["results"]:
        tuning = r.get("tuning")
        if not tuning or tuning.get("algorithm") != "mec_fused":
            continue
        spec = ConvSpec(**r["run_spec"])
        for label, t in tuning["trials"].items():
            why = fused_refusal(spec, r["dtype"], t["w_blk"])
            assert why is None, f"{r['scenario']} w_blk={label}: {why}"
            replayed += 1
    assert replayed >= 3      # the w520 grid alone contributes three


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_lint_and_pallas_suites_green():
    """The lint suite is green; the kernel's geometry rule has no suite
    of its own (its sweeps are the tests above)."""
    from repro.analysis.__main__ import main
    assert main(["--suite", "lint"]) == 0
    with pytest.raises(SystemExit):
        main(["--suite", "pallas"])
