"""Machine-readable benchmark subsystem (DESIGN.md §3).

The paper's entire claim is a measured trade-off — Eq. 3's compact
lowering vs. im2col's k_h*k_w blow-up, *and* a speedup from better
memory-subsystem behaviour — so benchmark results must be comparable
across runs, machines, and jax versions.  This package owns that:

* :mod:`repro.bench.scenarios` — the scenario registry: paper Table 2
  (``cv1``–``cv12``), the Table 3 ResNet-101 weighted set, the Fig 4(a)
  k/s sweep, batch/channel/dtype diversity suites, and the CI ``smoke``
  subset, all routed through ``repro.core.conv_api.conv2d``.
* :mod:`repro.bench.harness` — warmup/steady-state timing of
  pre-compiled calls, analytic memory overhead (``repro.core.memory``),
  HLO-derived flops/bytes (``repro.launch.hlo_analysis`` via
  ``Compiled.cost_analysis``), and costmodel cross-validation.
* :mod:`repro.bench.report` — the ``BENCH_<suite>.json`` schema,
  environment fingerprint, validation, and legacy-CSV rendering.
* :mod:`repro.bench.check` — baseline comparison with per-metric
  tolerances; non-zero exit on regression (the CI perf gate).

CLI::

  PYTHONPATH=src python -m repro.bench --suite smoke --out BENCH_smoke.json
  PYTHONPATH=src python -m repro.bench.check BENCH_smoke.json \\
      --baseline benchmarks/baselines/smoke.json --schema-only-on-timing
"""
from repro.bench.harness import run_autotune, run_serve, run_suite
from repro.bench.report import render_csv, validate_report, write_report
from repro.bench.scenarios import (ALGORITHM_VARIANTS, CV_LAYERS,
                                   RESNET101_WEIGHTS, SUITES, Scenario,
                                   resolve_suite)

__all__ = [
    "ALGORITHM_VARIANTS", "CV_LAYERS", "RESNET101_WEIGHTS", "SUITES",
    "Scenario", "render_csv", "resolve_suite", "run_autotune", "run_serve",
    "run_suite", "validate_report", "write_report",
]
