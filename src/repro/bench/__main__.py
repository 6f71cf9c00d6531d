"""CLI entry point:  PYTHONPATH=src python -m repro.bench --suite smoke \\
    --out BENCH_smoke.json [--format csv] [--crosscheck]

``--suite autotune`` is special: it runs the analytic-vs-measured pick
comparison (``harness.run_autotune``, DESIGN.md §7) over the scenarios
of ``--base-suite`` and writes its own document (BENCH_autotune.json)
rather than a standard suite report.

``--suite serve`` runs the conv-serving cells (``harness.run_serve``,
DESIGN.md §9): warm-plan vs cold-plan vs per-call ``algorithm="auto"``
over the registered shape-class services, emitted as a standard report
so ``repro.bench.check`` gates it against
``benchmarks/baselines/serve.json``."""
from __future__ import annotations

import argparse
import json
import sys

from repro.bench.harness import run_autotune, run_serve, run_suite
from repro.bench.report import render_csv, write_report
from repro.bench.scenarios import SUITES


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.bench", description=__doc__)
    ap.add_argument("--suite", required=True,
                    choices=sorted(SUITES) + ["autotune", "serve"])
    ap.add_argument("--base-suite", default="smoke", choices=sorted(SUITES),
                    help="scenarios the autotune comparison runs over")
    ap.add_argument("--out", default=None,
                    help="write BENCH_<suite>.json here (default: "
                         "BENCH_<suite>.json in the cwd for json format)")
    ap.add_argument("--format", choices=("json", "csv"), default="json",
                    help="csv prints the legacy table,name,us,derived lines")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--interpret", choices=("auto", "true", "false"),
                    default="auto",
                    help="Pallas interpret mode for mec_* kernels "
                         "(auto: interpret everywhere but real TPU)")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip cost_analysis of the compiled executables")
    ap.add_argument("--no-timing", action="store_true",
                    help="analytic + HLO fields only (fast, deterministic)")
    ap.add_argument("--crosscheck", action="store_true",
                    help="cross-validate costmodel predictions against "
                         "measurements (adds a 'crosscheck' section)")
    args = ap.parse_args(argv)
    from repro.core.compat import enable_compile_cache
    enable_compile_cache()

    interpret = {"auto": None, "true": True, "false": False}[args.interpret]
    if args.suite == "autotune":
        doc = run_autotune(args.base_suite, iters=args.iters,
                           warmup=args.warmup, interpret=interpret,
                           progress=lambda m: print(m, file=sys.stderr))
        out = args.out or "BENCH_autotune.json"
        with open(out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        wins = sum(1 for r in doc["results"]
                   if r["speedup"] and r["speedup"] >= 1.0)
        print(f"[bench] autotune over {args.base_suite}: "
              f"{len(doc['results'])} cells, measured pick <= analytic on "
              f"{wins} -> {out}")
        return 0
    if args.suite == "serve":
        doc = run_serve(progress=lambda m: print(m, file=sys.stderr))
        out = args.out or "BENCH_serve.json"
        write_report(doc, out)
        by_key = {(r["scenario"], r["serve_mode"]): r
                  for r in doc["results"]}
        cells = sorted({r["scenario"] for r in doc["results"]})
        warm_wins = sum(
            1 for c in cells
            if (by_key[(c, "warm")]["p50_us"] or 0)
            <= (by_key[(c, "auto")]["p50_us"] or 0))
        print(f"[bench] serve: {len(doc['results'])} records over "
              f"{len(cells)} class cells; warm p50 <= per-call auto p50 "
              f"on {warm_wins}/{len(cells)} -> {out}")
        return 0
    doc = run_suite(args.suite, iters=args.iters, warmup=args.warmup,
                    interpret=interpret, with_hlo=not args.no_hlo,
                    with_timing=not args.no_timing,
                    crosscheck=args.crosscheck,
                    progress=lambda msg: print(msg, file=sys.stderr))
    if args.format == "csv":
        for line in render_csv(doc):
            print(line)
        if args.out:
            write_report(doc, args.out)
        return 0
    out = args.out or f"BENCH_{args.suite}.json"
    write_report(doc, out)
    print(f"[bench] {args.suite}: {len(doc['results'])} cells -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
