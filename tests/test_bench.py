"""repro.bench: scenario-registry completeness, report schema validation,
determinism of the analytic memory/flops fields, and the regression
gate's pass/fail behaviour (including the committed CI smoke baseline)."""
import copy
import json
import pathlib

import pytest

from repro.bench import (CV_LAYERS, RESNET101_WEIGHTS, SUITES,
                         ALGORITHM_VARIANTS, resolve_suite, validate_report)
from repro.bench.check import compare
from repro.bench.harness import measure
from repro.bench.report import make_report
from repro.bench.scenarios import Scenario
from repro.core.convspec import ConvSpec

REPO = pathlib.Path(__file__).resolve().parents[1]
BASELINE = REPO / "benchmarks" / "baselines" / "smoke.json"


# ---------------------------------------------------------------- registry

def test_table2_suite_has_every_paper_layer():
    names = {sc.name for sc in resolve_suite("table2")}
    assert len(CV_LAYERS) == 12
    assert names == set(CV_LAYERS)


def test_every_registered_suite_resolves():
    for suite in SUITES:
        scenarios = resolve_suite(suite)
        assert scenarios, suite
        for sc in scenarios:
            assert sc.algorithms, (suite, sc.name)
            sc.spec.validate()
            sc.run_spec.validate()


def test_resnet101_suite_carries_paper_weights():
    weights = {sc.name: sc.weight for sc in resolve_suite("resnet101")}
    assert weights == RESNET101_WEIGHTS


def test_smoke_suite_covers_every_algorithm_variant():
    algs = set()
    for sc in resolve_suite("smoke"):
        algs.update(sc.algorithms)
    assert algs == set(ALGORITHM_VARIANTS)


def test_unknown_suite_rejected():
    with pytest.raises(KeyError):
        resolve_suite("nope")


# ------------------------------------------------------------ report schema

def _tiny_scenario():
    spec = ConvSpec(1, 8, 8, 2, 3, 3, 4, 1, 1)
    return Scenario(name="tiny", spec=spec, run_spec=spec,
                    algorithms=("direct", "im2col", "mecA", "mec_fused"))


@pytest.fixture(scope="module")
def tiny_doc():
    sc = _tiny_scenario()
    recs = [measure(sc, alg, iters=1, warmup=1) for alg in sc.algorithms]
    return make_report("smoke", recs, {"iters": 1, "warmup": 1})


def test_emitted_report_is_schema_valid(tiny_doc):
    assert validate_report(tiny_doc) == []
    # and survives a JSON round-trip (what check/CI actually consume)
    assert validate_report(json.loads(json.dumps(tiny_doc))) == []


def test_schema_rejects_malformed_reports(tiny_doc):
    bad = copy.deepcopy(tiny_doc)
    del bad["results"][0]["overhead_bytes"]
    assert any("overhead_bytes" in e for e in validate_report(bad))
    bad = copy.deepcopy(tiny_doc)
    bad["results"][0]["flops"] = "lots"
    assert any("flops" in e for e in validate_report(bad))
    bad = copy.deepcopy(tiny_doc)
    bad["schema_version"] = 99
    assert any("schema_version" in e for e in validate_report(bad))
    assert validate_report({"suite": "x"})  # no results at all


def test_memory_and_flops_fields_deterministic():
    sc = _tiny_scenario()
    runs = [[measure(sc, alg, with_hlo=False, with_timing=False)
             for alg in sc.algorithms] for _ in range(2)]
    assert runs[0] == runs[1]
    by_alg = {r["algorithm"]: r for r in runs[0]}
    # Eq. 2 vs Eq. 3 on the tiny spec: im2col strictly bigger, fused zero.
    assert by_alg["im2col"]["overhead_bytes"] > \
        by_alg["mecA"]["overhead_bytes"] > 0
    assert by_alg["mec_fused"]["overhead_bytes"] == 0
    assert by_alg["direct"]["flops"] == by_alg["mecA"]["flops"]


# ----------------------------------------------------------- check gating

def test_check_passes_against_itself(tiny_doc):
    failures, _ = compare(copy.deepcopy(tiny_doc), copy.deepcopy(tiny_doc))
    assert failures == []


def test_check_fails_on_perturbed_memory_overhead(tiny_doc):
    bad = copy.deepcopy(tiny_doc)
    bad["results"][1]["overhead_bytes"] += 4
    failures, _ = compare(bad, tiny_doc, schema_only_on_timing=True)
    assert any("overhead_bytes" in f for f in failures)


def test_check_fails_on_lost_coverage(tiny_doc):
    shrunk = copy.deepcopy(tiny_doc)
    shrunk["results"] = shrunk["results"][1:]
    failures, _ = compare(shrunk, tiny_doc, schema_only_on_timing=True)
    assert any("missing" in f for f in failures)


def test_check_timing_tolerance_and_schema_only(tiny_doc):
    slow = copy.deepcopy(tiny_doc)
    slow["results"][0]["us_per_call"] = \
        tiny_doc["results"][0]["us_per_call"] * 10
    failures, _ = compare(slow, tiny_doc, timing_rtol=1.0)
    assert any("us_per_call regressed" in f for f in failures)
    failures, _ = compare(slow, tiny_doc, schema_only_on_timing=True)
    assert failures == []
    # hlo drift is informational, never a failure
    drift = copy.deepcopy(tiny_doc)
    drift["results"][0]["hlo_bytes"] = 12345.0
    failures, notes = compare(drift, tiny_doc, schema_only_on_timing=True)
    assert failures == []
    assert any("hlo_bytes" in n for n in notes)


# ------------------------------------------------------- committed baseline

def test_committed_smoke_baseline_is_valid_and_complete():
    doc = json.loads(BASELINE.read_text())
    assert validate_report(doc) == []
    assert doc["suite"] == "smoke"
    got = {(r["scenario"], r["algorithm"]) for r in doc["results"]}
    want = {(sc.name, alg) for sc in resolve_suite("smoke")
            for alg in sc.algorithms}
    assert got == want


def test_dist_suite_layers_and_smoke_cells():
    """The dist suite covers cv1-cv12 at 2/8/256-way, the composite 2-D
    analytic cells, plus the 2-device smoke cells (one per 1-D partition
    mode) and the 2x2 composite smoke cells (DESIGN.md §6)."""
    dist = resolve_suite("dist")
    names = {sc.name for sc in dist}
    for layer in CV_LAYERS:
        for n in (2, 8, 256):
            assert f"{layer}_d{n}" in names
        assert f"{layer}_bs2x2" in names
    for part in ("batch", "channel", "spatial"):
        sc = next(s for s in dist if s.name == f"smoke2_{part}")
        assert sc.partition == part and sc.n_dev == 2
    for a, b in (("batch", "spatial"), ("batch", "channel"),
                 ("spatial", "channel")):
        sc = next(s for s in dist if s.name == f"smoke4_{a}_{b}")
        assert sc.partition == (a, b) and sc.n_dev == (2, 2)
    assert all(sc.partition is not None for sc in dist)


def test_dist_composite_measure_emits_analytic_fields():
    """A composite 2-D cell carries partition 'batch+spatial', the
    device product in n_dev, the per-sub-axis split in n_dev_axes, and
    halo bytes scaled by the local batch shard — without needing 4 real
    devices."""
    sc = next(s for s in resolve_suite("dist") if s.name == "cv9_bs2x2")
    rec = measure(sc, "mecB", with_hlo=False, with_timing=False)
    assert rec["partition"] == "batch+spatial"
    assert rec["n_dev"] == 4 and rec["n_dev_axes"] == [2, 2]
    # halo = (k_h - s_h) rows x the 4-sample local batch shard
    assert rec["halo_bytes_per_device"] == 4 * 2 * 56 * 64 * 4
    assert rec["per_device_overhead_elems"] > 0
    assert rec["comm_bytes_per_device"] >= rec["halo_bytes_per_device"]
    doc = make_report("dist", [rec], {})
    assert validate_report(doc) == []


def test_dist_measure_emits_analytic_fields_without_devices():
    """A 256-way cell on this 1-device process still carries the exact
    per-device/halo analytics (timing/HLO skipped), and the report
    schema accepts the block."""
    sc = next(s for s in resolve_suite("dist") if s.name == "cv9_d256")
    rec = measure(sc, "mecB", with_hlo=True, with_timing=True)
    assert rec["partition"] == "spatial" and rec["n_dev"] == 256
    assert rec["us_per_call"] is None and rec["hlo_flops"] is None
    # halo = (k_h - s_h) input rows per device: 2 * 56 * 64 * 4 bytes
    assert rec["halo_bytes_per_device"] == 2 * 56 * 64 * 4
    assert rec["per_device_overhead_elems"] > 0
    assert rec["comm_bytes_per_device"] >= rec["halo_bytes_per_device"]
    doc = make_report("dist", [rec], {})
    assert validate_report(doc) == []


def test_dist_fields_gated_exactly_by_check():
    sc = next(s for s in resolve_suite("dist") if s.name == "cv9_d2")
    rec = measure(sc, "mecB", with_hlo=False, with_timing=False)
    doc = make_report("dist", [rec], {})
    base = json.loads(json.dumps(doc))
    fails, _ = compare(doc, base, schema_only_on_timing=True)
    assert fails == []
    doc2 = json.loads(json.dumps(doc))
    doc2["results"][0]["halo_bytes_per_device"] += 1
    fails, _ = compare(doc2, base, schema_only_on_timing=True)
    assert any("halo_bytes_per_device" in f for f in fails)


def test_dist_record_missing_sibling_field_rejected():
    sc = next(s for s in resolve_suite("dist") if s.name == "cv9_d2")
    rec = measure(sc, "mecB", with_hlo=False, with_timing=False)
    broken = dict(rec)
    del broken["halo_bytes_per_device"]
    errs = validate_report(make_report_unchecked("dist", [broken]))
    assert any("distributed cell missing" in e for e in errs)
    # n_dev_axes postdates the first dist baselines: a record without it
    # (a pre-composite baseline) must still validate
    legacy = dict(rec)
    del legacy["n_dev_axes"]
    assert validate_report(make_report_unchecked("dist", [legacy])) == []


def make_report_unchecked(suite, results):
    from repro.bench.report import SCHEMA_VERSION, environment_fingerprint
    return {"schema_version": SCHEMA_VERSION, "suite": suite,
            "environment": environment_fingerprint(), "harness": {},
            "results": results}


def test_committed_dist_baseline_is_valid():
    doc = json.loads((REPO / "benchmarks" / "baselines" /
                      "dist.json").read_text())
    assert validate_report(doc) == []
    assert doc["suite"] == "dist"
    # 12 layers x {2,8,256}-way 1-D + 12 batch x spatial + 3 batch x
    # channel + 2 spatial x channel analytic cells, and (3 smoke2 +
    # 3 smoke4) x 2 algorithms
    assert len(doc["results"]) == 12 * 3 + 12 + 3 + 2 + (3 + 3) * 2


# ------------------------------------------------------------- autotune

def _autotune_doc():
    """Minimal schema-v2 autotune document (one smoke cell)."""
    spec = ConvSpec(1, 14, 14, 4, 3, 3, 8, 1, 1)
    import dataclasses
    return {
        "autotune_schema_version": 2,
        "suite": "autotune",
        "base_suite": "smoke",
        "environment": {"backend": "cpu", "jax": "0"},
        "calibration": {"active": False, "source": None},
        "harness": {"iters": 3, "warmup": 1, "noise_margin": 0.05},
        "results": [{
            "scenario": "s3x3",
            "dtype": "float32",
            "run_spec": dataclasses.asdict(spec),
            "analytic_algorithm": "mec",
            "analytic_us": 230.0,
            "measured_algorithm": "mec",
            "measured_us": 230.0,
            "candidate_us": {"mec": 230.0, "direct": 410.0},
            "candidate_stats": {"mec": {"us_median": 230.0,
                                        "us_std": 4.0,
                                        "us_rel_spread": 0.017}},
            "skipped": {},
            "n_skipped": 0,
            "max_rel_spread": 0.017,
            "tuning": None,
            "pick_agrees": True,
        }],
    }


def test_autotune_check_gates_decision_fields_exactly():
    base = _autotune_doc()
    failures, _ = compare(copy.deepcopy(base), base)
    assert failures == []
    drift = copy.deepcopy(base)
    drift["results"][0]["analytic_algorithm"] = "direct"
    failures, _ = compare(drift, base)
    assert any("analytic_algorithm" in f for f in failures)
    missing = copy.deepcopy(base)
    missing["results"] = [dict(missing["results"][0], scenario="other")]
    failures, _ = compare(missing, base)
    assert any("missing" in f for f in failures)


def test_autotune_check_spread_and_measured_drift_never_fail():
    base = _autotune_doc()
    drift = copy.deepcopy(base)
    drift["results"][0].update(measured_algorithm="direct",
                               pick_agrees=False, max_rel_spread=0.4)
    drift["results"][0]["candidate_stats"]["mec"]["us_std"] = 90.0
    failures, notes = compare(drift, base)
    assert failures == []
    assert any("measured_algorithm" in n for n in notes)
    assert any("max_rel_spread" in n for n in notes)
    # timing stays under the tolerance policy, not exactness
    slow = copy.deepcopy(base)
    slow["results"][0]["measured_us"] = 230.0 * 2.5
    failures, _ = compare(slow, base, timing_rtol=1.0)
    assert any("measured_us regressed" in f for f in failures)
    failures, _ = compare(slow, base, schema_only_on_timing=True)
    assert failures == []


def test_autotune_check_newly_skipped_candidate_fails():
    base = _autotune_doc()
    lost = copy.deepcopy(base)
    lost["results"][0]["skipped"] = {"fft": "XlaRuntimeError: boom"}
    lost["results"][0]["n_skipped"] = 1
    failures, _ = compare(lost, base)
    assert any("newly skipped" in f for f in failures)
    # an already-skipped candidate staying skipped is not a regression
    failures, _ = compare(copy.deepcopy(lost), lost)
    assert failures == []


def test_autotune_check_calibration_flip_is_not_a_failure():
    base = _autotune_doc()
    calibrated = copy.deepcopy(base)
    calibrated["calibration"] = {"active": True, "source": "env:x"}
    calibrated["results"][0]["analytic_algorithm"] = "direct"
    failures, notes = compare(calibrated, base)
    assert failures == []
    assert any("calibration active differs" in n for n in notes)


def test_time_compiled_reports_spread():
    from repro.bench.harness import time_compiled
    t = time_compiled(lambda: None, iters=4, warmup=1)
    assert t["us_std"] >= 0.0
    assert t["us_rel_spread"] == pytest.approx(
        t["us_std"] / t["us_median"])


def test_committed_autotune_baseline_checks_against_itself():
    doc = json.loads((REPO / "BENCH_autotune.json").read_text())
    assert doc["autotune_schema_version"] == 2
    failures, _ = compare(copy.deepcopy(doc), doc,
                          schema_only_on_timing=True)
    assert failures == []
    for rec in doc["results"]:
        assert "candidate_stats" in rec and "skipped" in rec
        assert rec["n_skipped"] == len(rec["skipped"])


def test_smoke_w520_is_a_kernel_tuning_cell():
    # The w520 geometry exists to audit pick_w_blk's 512-column cap:
    # its o_w must exceed the planner default so the stage-2 grid has a
    # strictly larger (single-grid-step) block to find.
    from repro.kernels.ops import pick_w_blk
    sc = {s.name: s for s in resolve_suite("smoke")}["w520"]
    assert sc.tune_candidates == ("mec_fused",)
    assert set(sc.algorithms) == set(sc.tune_candidates)
    default = pick_w_blk(sc.run_spec.o_w, sc.run_spec.k_c, _warn_env=False)
    assert default < sc.run_spec.o_w


def test_committed_autotune_baseline_tunes_w_blk_off_default():
    # DESIGN.md §10 acceptance: measured mode demonstrably tunes the
    # knob — the committed report's w520 cell must carry a non-default
    # w_blk backed by before/after trial timings.
    doc = json.loads((REPO / "BENCH_autotune.json").read_text())
    rec = {r["scenario"]: r for r in doc["results"]}["w520"]
    tuning = rec["tuning"]
    assert tuning["knob"] == "w_blk"
    assert tuning["picked"] != tuning["default"]
    assert rec["plan"]["w_blk"] == int(tuning["picked"])
    trials = tuning["trials"]
    assert tuning["default"] in trials and tuning["picked"] in trials
    assert trials[tuning["picked"]]["us_median"] < \
        trials[tuning["default"]]["us_median"]
