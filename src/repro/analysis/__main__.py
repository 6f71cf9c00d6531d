"""CLI: ``python -m repro.analysis --suite
memaudit|lint|shardcheck|numcheck|all``.

Exit status is non-zero on any violation — this is what the CI
``static-analysis`` job runs on every push.  ``--update-lint-baseline``
regenerates the grandfathered-findings file (use only to *shrink* it
after fixing a finding, or to adopt a deliberate new suppression the
baseline should own).

The ``shardcheck`` suite forces a host platform with
:data:`SHARDCHECK_FORCED_DEVICES` devices (the env must be set before
jax initializes, so ``main`` does it up front) and writes the full
collective-contract evidence to ``BENCH_shardcheck.json``.

The ``numcheck`` suite (DESIGN.md §8.5) sweeps every conv backend x
{f32, bf16, f16}: static dtype-flow signature checks on fwd + grad plus
the measured f64 error-budget probe, written to ``BENCH_numcheck.json``
(CI gates the deterministic fields against the committed baseline).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys

SUITES = ("memaudit", "lint", "shardcheck", "numcheck", "all")

# Enough forced host devices for every committed dist-baseline mesh
# except the 256-way pod cells (those record an explicit skip — a CLI
# that forced 256 devices would spend CI minutes compiling what the
# slow-dryrun workflow already covers).
SHARDCHECK_FORCED_DEVICES = 8

DEFAULT_DIST = "benchmarks/baselines/dist.json"


def _run_memaudit(args) -> int:
    from repro.analysis.memaudit import write_audit
    out, failures = write_audit(
        plans_path=args.plans, out_path=args.out,
        calibration_store=True if args.record_calibration else None)
    print(f"memaudit: report written to {out}")
    if args.record_calibration:
        print("memaudit: gated ratios recorded to the calibration store "
              "(repro.plan.calibrate)")
    if failures:
        print(f"memaudit: {len(failures)} gate failure(s):")
        for f in failures:
            print(f"  {f}")
        return 1
    print("memaudit: all gated cells within tolerance")
    return 0


def _run_shardcheck(args) -> int:
    """Contract-check every partitioned cell of the committed baselines.

    Cells come from two sources: the dist baseline (every partitioned
    record, deduplicated by executed geometry) and any partitioned plans
    in the plans baseline (checked under a minimal 2-way-per-axis forced
    mesh — a plan records mesh *axes*, not sizes).  Writes the full
    evidence report and fails on any ``fail`` verdict; skips (e.g. the
    256-way pod cells) are recorded, never silently dropped.
    """
    from repro.analysis.memaudit import DEFAULT_PLANS, load_plans
    from repro.analysis.shardcheck import check_sharding
    from repro.bench.report import make_report, write_report
    from repro.bench.scenarios import ALGORITHM_VARIANTS
    from repro.core.convspec import ConvSpec
    root = pathlib.Path(__file__).resolve().parents[3]
    dist_path = pathlib.Path(args.dist or root / DEFAULT_DIST)
    results = []
    n_fail = n_skip = 0

    def one(scenario, variant, spec, partition, sizes, dtype, source,
            *, algorithm, solution="auto", precision=None):
        # `variant` is the bench cell key (e.g. "mecB"); `algorithm` is
        # the resolved executor algorithm it maps to (e.g. "mec").
        nonlocal n_fail, n_skip
        chk = check_sharding(spec, partition, sizes, dtype=dtype,
                             algorithm=algorithm, solution=solution,
                             precision=precision)
        rec = dict(chk.record)
        rec.update({
            "scenario": scenario,
            "algorithm": variant,
            "dtype": dtype,
            "spec": {f: getattr(spec, f) for f in
                     ("i_n", "i_h", "i_w", "i_c", "k_h", "k_w", "k_c",
                      "s_h", "s_w")},
            "source": source,
            "n_dev": int(math.prod(sizes)),
        })
        # solution/precision ride inside `directions`-level evidence
        # already; the report schema keys the canonical fields only.
        rec.pop("solution", None)
        results.append(rec)
        if chk.record["verdict"] == "fail":
            n_fail += 1
            print(f"shardcheck: FAIL {scenario}/{variant}:")
            for v in chk.record["violations"]:
                print(f"  {v}")
        elif chk.record["verdict"] == "skipped":
            n_skip += 1
            print(f"shardcheck: skip {scenario}/{variant}: "
                  f"{chk.record['skipped_reason']}")

    if dist_path.exists():
        dist = json.loads(dist_path.read_text())
        for r in dist.get("results", []):
            if "partition" not in r:
                continue
            spec = ConvSpec(**r["run_spec"])
            kw = ALGORITHM_VARIANTS.get(r["algorithm"],
                                        {"algorithm": r["algorithm"]})
            one(r["scenario"], r["algorithm"], spec, r["partition"],
                tuple(r.get("n_dev_axes") or [r["n_dev"]]), r["dtype"],
                "dist-baseline",
                algorithm=kw.get("algorithm", r["algorithm"]),
                solution=kw.get("solution", "auto"))
    else:
        print(f"shardcheck: no dist baseline at {dist_path} "
              f"(checking plans only)")
    plans = load_plans(args.plans or root / DEFAULT_PLANS)
    for name, plan in plans.items():
        if plan.partition is None:
            continue
        one(name, plan.algorithm, plan.spec, plan.partition,
            (2,) * len(plan.partition), plan.dtype, "plans-baseline",
            algorithm=plan.algorithm, solution=plan.solution,
            precision=plan.precision)
    out = pathlib.Path(args.shardcheck_out or root / "BENCH_shardcheck.json")
    if results:
        doc = make_report("shardcheck", results,
                          harness={"forced_devices":
                                   SHARDCHECK_FORCED_DEVICES,
                                   "dist_baseline": str(dist_path),
                                   "directions": ["fwd", "grad"]})
        write_report(doc, out)
        print(f"shardcheck: report written to {out}")
    verified = len(results) - n_fail - n_skip
    if n_fail:
        print(f"shardcheck: {n_fail} cell(s) broke the collective/"
              f"precision contract")
        return 1
    print(f"shardcheck: {verified} cell(s) verified, {n_skip} skipped, "
          f"0 contract violations")
    return 0


def _run_numcheck(args) -> int:
    """Numeric-contract check of every backend x contract dtype
    (DESIGN.md §8.5): static signature detectors on fwd + grad, then the
    measured error-budget probe vs the f64 reference.  Skips (winograd
    off-geometry, Pallas-rejected cells, unregistered backends) are
    recorded, never silently dropped.  Writes the full evidence to
    ``BENCH_numcheck.json``."""
    from repro.analysis.numcheck import (NUMCHECK_ALGORITHMS,
                                         NUMCHECK_DTYPES, check_numerics,
                                         probe_spec)
    from repro.bench.report import make_report, write_report
    from repro.bench.scenarios import ALGORITHM_VARIANTS
    root = pathlib.Path(__file__).resolve().parents[3]
    spec = probe_spec()
    results = []
    n_fail = n_skip = 0
    for variant in NUMCHECK_ALGORITHMS:
        kw = ALGORITHM_VARIANTS.get(variant, {"algorithm": variant})
        for dtype in NUMCHECK_DTYPES:
            chk = check_numerics(spec, kw.get("algorithm", variant), dtype,
                                 solution=kw.get("solution", "auto"),
                                 interpret=True)
            rec = dict(chk.record)
            rec.update({
                "scenario": f"numprobe_{dtype}",
                "algorithm": variant,
                "spec": {f: getattr(spec, f) for f in
                         ("i_n", "i_h", "i_w", "i_c", "k_h", "k_w", "k_c",
                          "s_h", "s_w")},
                "source": "probe-spec",
            })
            results.append(rec)
            if chk.record["verdict"] == "fail":
                n_fail += 1
                print(f"numcheck: FAIL {variant}/{dtype}:")
                for v in chk.record["violations"]:
                    print(f"  {v}")
            elif chk.record["verdict"] == "skipped":
                n_skip += 1
                print(f"numcheck: skip {variant}/{dtype}: "
                      f"{chk.record['skipped_reason']}")
    out = pathlib.Path(args.numcheck_out or root / "BENCH_numcheck.json")
    doc = make_report("numcheck", results,
                      harness={"directions": ["fwd", "grad"],
                               "probe_seed": 0,
                               "reference": "numpy-f64"})
    write_report(doc, out)
    print(f"numcheck: report written to {out}")
    verified = len(results) - n_fail - n_skip
    if n_fail:
        print(f"numcheck: {n_fail} cell(s) broke their numeric contract")
        return 1
    print(f"numcheck: {verified} cell(s) verified, {n_skip} skipped, "
          f"0 contract violations")
    return 0


def _run_lint(args) -> int:
    from repro.analysis.lint import (apply_baseline, lint_tree,
                                     load_baseline, repo_root,
                                     write_baseline)
    root = repo_root()
    findings = lint_tree(root)
    baseline_path = pathlib.Path(
        args.lint_baseline or root / "benchmarks/baselines/lint_baseline.json")
    if args.update_lint_baseline:
        write_baseline(findings, baseline_path)
        print(f"lint: baseline rewritten with {len(findings)} finding(s) "
              f"-> {baseline_path}")
        return 0
    baseline = load_baseline(baseline_path) if baseline_path.exists() else []
    split = apply_baseline(findings, baseline)
    for f in split["new"]:
        print(f"lint: NEW {f.render()}")
    if split["fixed"]:
        print(f"lint: {len(split['fixed'])} baseline entry(ies) no longer "
              f"fire — shrink the baseline with --update-lint-baseline:")
        for key in split["fixed"]:
            print(f"  fixed: {key}")
    if split["new"]:
        print(f"lint: {len(split['new'])} new finding(s) "
              f"({len(split['grandfathered'])} grandfathered)")
        return 1
    print(f"lint: clean ({len(split['grandfathered'])} grandfathered)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static analysis suites (DESIGN.md §8)")
    parser.add_argument("--suite", choices=SUITES, default="all")
    parser.add_argument("--plans", default=None,
                        help="plans baseline JSON (default: "
                             "benchmarks/baselines/plans.json)")
    parser.add_argument("--out", default=None,
                        help="memaudit report path "
                             "(default: BENCH_memaudit.json)")
    parser.add_argument("--record-calibration", action="store_true",
                        help="record gated measured/predicted ratios "
                             "into the fitted-costmodel store "
                             "(repro.plan.calibrate, DESIGN.md §10)")
    parser.add_argument("--lint-baseline", default=None,
                        help="lint baseline JSON (default: "
                             "benchmarks/baselines/lint_baseline.json)")
    parser.add_argument("--update-lint-baseline", action="store_true",
                        help="rewrite the lint baseline from the current "
                             "tree (shrink-only workflow)")
    parser.add_argument("--dist", default=None,
                        help="dist baseline JSON feeding the shardcheck "
                             "suite (default: benchmarks/baselines/"
                             "dist.json)")
    parser.add_argument("--shardcheck-out", default=None,
                        help="shardcheck report path "
                             "(default: BENCH_shardcheck.json)")
    parser.add_argument("--numcheck-out", default=None,
                        help="numcheck report path "
                             "(default: BENCH_numcheck.json)")
    args = parser.parse_args(argv)
    if args.suite in ("shardcheck", "all"):
        # Must happen before anything imports-and-initializes jax (the
        # other suites do), or the process is stuck with one device and
        # every multi-way cell records a skip instead of a verdict.
        # The raw read is sanctioned: XLA_FLAGS is jax bootstrap
        # surface, not repo configuration.
        flags = os.environ.get("XLA_FLAGS", "")  # lint-ignore: raw-environ-read-outside-compat
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                        f"{SHARDCHECK_FORCED_DEVICES}").strip()
    rc = 0
    if args.suite in ("lint", "all"):
        rc |= _run_lint(args)
    if args.suite in ("memaudit", "all"):
        rc |= _run_memaudit(args)
    if args.suite in ("numcheck", "all"):
        rc |= _run_numcheck(args)
    if args.suite in ("shardcheck", "all"):
        rc |= _run_shardcheck(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
