"""HLO memory auditor: XLA's bytes vs. the paper's Eqs. 2-4 (DESIGN.md §8).

For every plan in the committed decision baseline
(``benchmarks/baselines/plans.json``), AOT-lower the convolution through
the public ``conv2d(plan=...)`` executor against
``jax.ShapeDtypeStruct`` operands (no real arrays — cv4 alone would be
100+ MB), pull the compiled executable's peak temporary-buffer bytes via
``Compiled.memory_analysis()``, and gate
the measurement against the analytic model
(``repro.core.memory.algorithm_overhead`` x dtype size) within a
per-algorithm tolerance band.

Tolerance policy (bands measured on the jax 0.4.37 CPU backend across
all 15 baseline cells plus winograd/fft probes; see DESIGN.md §8):

* ``direct``   predicts zero overhead — gated on an absolute slack
  (XLA may keep a small reshape/copy temp).
* ``im2col``   XLA materializes exactly the Toeplitz patch matrix;
  measured/predicted was 1.000 on every cell, band [0.98, 1.15].
* ``mec``      XLA holds L plus an f32 accumulator / fusion temps;
  measured 1.03-1.51, band [0.95, 1.9].
* ``winograd`` / ``fft``  looser ([0.95, 2.0] / [0.95, 2.1]): XLA keeps
  transform temps alive across the element-wise product.
* the Pallas kernel (``mec_fused``) is **recorded but not gated**
  off-TPU: interpret-mode compiles materialize the lowering as XLA
  temps, so CPU numbers say nothing about the TPU VMEM story — that is
  the kernel's geometry rule's job (``repro.kernels.mec_conv``).

A band failure means either the analytic model or the implementation
drifted — exactly the regression Table 2's memory claims rest on.  Each
mec cell also carries a crosscheck: measured mec temp bytes must stay
*below* measured im2col temp bytes whenever Eq. 4 predicts a positive
saving — the paper's core claim, machine-checked end to end.

Output is a schema-validated ``BENCH_memaudit.json`` via the
``repro.bench.report`` machinery (suite ``memaudit``).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import memory
from repro.core.convspec import ConvSpec

# Per-algorithm measured-vs-predicted gates, keyed by the *base* model
# name (repro.core.memory._DISPATCH_BASE resolves mecA/mec_fused/...).
# ratio = measured_temp_bytes / predicted_overhead_bytes.
TOLERANCES: Dict[str, Dict[str, float]] = {
    "direct": {"abs_slack": 4096},
    "im2col": {"lo": 0.98, "hi": 1.15},
    "mec": {"lo": 0.95, "hi": 1.9},
    "winograd": {"lo": 0.95, "hi": 2.0},
    "fft": {"lo": 0.95, "hi": 2.1},
}

DEFAULT_PLANS = "benchmarks/baselines/plans.json"
DEFAULT_REPORT = "BENCH_memaudit.json"


def _base_algorithm(algorithm: str) -> str:
    return memory._DISPATCH_BASE.get(algorithm, algorithm)


def pallas_gated() -> bool:
    """Pallas cells are tolerance-gated only where the kernels actually
    run as kernels (TPU); interpret-mode temps are recorded only."""
    import jax
    return jax.default_backend() == "tpu"


def lower_plan(plan):
    """AOT-compile ``conv2d(plan=...)`` on ShapeDtypeStruct operands."""
    import jax
    from repro.core.conv_api import conv2d
    s = plan.spec
    inp = jax.ShapeDtypeStruct((s.i_n, s.i_h, s.i_w, s.i_c), plan.dtype)
    ker = jax.ShapeDtypeStruct((s.k_h, s.k_w, s.i_c, s.k_c), plan.dtype)
    fn = jax.jit(lambda i, k: conv2d(i, k, stride=(s.s_h, s.s_w),
                                     plan=plan))
    return fn.lower(inp, ker).compile()


def audit_plan(scenario: str, plan) -> Tuple[Dict, List[str]]:
    """One audit record (bench-report shape) + its gate failures."""
    import numpy as np
    s = plan.spec
    base = _base_algorithm(plan.algorithm)
    dtype_bytes = int(np.dtype(plan.dtype).itemsize)
    predicted_elems = memory.algorithm_overhead(s, plan.algorithm)
    predicted_bytes = predicted_elems * dtype_bytes

    compiled = lower_plan(plan)
    stats = compiled.memory_analysis()
    measured = None if stats is None else int(stats.temp_size_in_bytes)
    source = None if stats is None else "memory_analysis"

    is_pallas = plan.algorithm == "mec_fused"
    policy = "recorded" if (is_pallas and not pallas_gated()) else "gated"
    tol = TOLERANCES[base]
    ratio = None
    slack = None
    failures: List[str] = []
    if measured is None:
        verdict = "recorded"        # no memory stats on this backend
        policy = "recorded"
    elif policy == "recorded":
        verdict = "recorded"
        if predicted_bytes:
            ratio = measured / predicted_bytes
        slack = measured - predicted_bytes
    elif "abs_slack" in tol:
        slack = measured - predicted_bytes
        verdict = "pass" if slack <= tol["abs_slack"] else "fail"
    else:
        slack = measured - predicted_bytes
        if predicted_bytes <= 0:
            verdict = "fail"
            failures.append(
                f"{scenario}/{plan.algorithm}: model predicts no overhead "
                f"but algorithm is ratio-gated")
        else:
            ratio = measured / predicted_bytes
            verdict = "pass" if tol["lo"] <= ratio <= tol["hi"] else "fail"
    if verdict == "fail" and not failures:
        failures.append(
            f"{scenario}/{plan.algorithm}: measured temp {measured}B vs "
            f"predicted {predicted_bytes}B "
            f"(ratio={'n/a' if ratio is None else f'{ratio:.3f}'}, "
            f"slack={slack}B) outside {tol}")

    record = {
        "scenario": scenario,
        "algorithm": plan.algorithm,
        "dtype": plan.dtype,
        "spec": dataclasses.asdict(s),
        "predicted_overhead_elems": predicted_elems,
        "predicted_overhead_bytes": predicted_bytes,
        "measured_temp_bytes": measured,
        "measured_argument_bytes": None if stats is None
        else int(stats.argument_size_in_bytes),
        "measured_output_bytes": None if stats is None
        else int(stats.output_size_in_bytes),
        "ratio": ratio,
        "slack_bytes": slack,
        "tolerance": dict(tol),
        "policy": policy,
        "source": source,
        "verdict": verdict,
    }
    return record, failures


def _companion_plan(plan, algorithm: str):
    """Same cell, different algorithm — for the mec-vs-im2col crosscheck."""
    return dataclasses.replace(plan, algorithm=algorithm, solution="auto",
                               w_blk=None)


def load_plans(path) -> Dict[str, object]:
    from repro.plan.convplan import ConvPlan
    doc = json.loads(pathlib.Path(path).read_text())
    return {name: ConvPlan.from_dict(d)
            for name, d in sorted(doc["plans"].items())}


def record_calibration(records: Sequence[Dict], store=None) -> int:
    """Feed the memory-side fit (DESIGN.md §10): every tolerance-gated
    measured/predicted ratio becomes a memory sample in the calibration
    store.  ``recorded``-policy cells (Pallas off-TPU, absent memory
    stats) never train the fit — their temps are XLA interpret-mode
    artifacts, not the algorithm's memory story.  Returns the number of
    samples added; flushes (best-effort) when it created the store.
    """
    from repro.plan.calibrate import CalibrationStore
    own = store is None
    store = store or CalibrationStore()
    n = 0
    for rec in records:
        if rec.get("policy") != "gated" or rec.get("ratio") is None:
            continue
        store.add_memory(ConvSpec(**rec["spec"]), rec["dtype"],
                         _base_algorithm(rec["algorithm"]),
                         float(rec["ratio"]))
        n += 1
    if own and n:
        store.flush()
    return n


def run_audit(plans_path=None,
              plans: Optional[Dict[str, object]] = None,
              calibration_store=None) -> Tuple[Dict, List[str]]:
    """Audit every baseline plan (+ an im2col companion per mec cell).

    Returns ``(report_doc, failures)`` — the doc validates against the
    bench-report ``memaudit`` suite schema; failures is the flat list of
    gate violations (empty == audit passed).  Pass a
    ``repro.plan.calibrate.CalibrationStore`` (or ``True`` for the
    ambient one) to additionally record the gated ratios as memory
    samples for the fitted costmodel — opt-in, so a plain audit never
    mutates planner state.
    """
    from repro.bench.report import make_report
    if plans is None:
        root = pathlib.Path(__file__).resolve().parents[3]
        plans_path = pathlib.Path(plans_path or root / DEFAULT_PLANS)
        plans = load_plans(plans_path)
    results: List[Dict] = []
    crosscheck: List[Dict] = []
    failures: List[str] = []
    measured_by_cell: Dict[Tuple[str, str], Optional[int]] = {}
    for scenario, plan in plans.items():
        rec, fails = audit_plan(scenario, plan)
        results.append(rec)
        failures.extend(fails)
        measured_by_cell[(scenario, _base_algorithm(plan.algorithm))] = \
            rec["measured_temp_bytes"]
        if _base_algorithm(plan.algorithm) == "mec":
            comp, comp_fails = audit_plan(
                scenario, _companion_plan(plan, "im2col"))
            results.append(comp)
            failures.extend(comp_fails)
            saving = memory.mec_saving(plan.spec)
            mec_b = rec["measured_temp_bytes"]
            im2col_b = comp["measured_temp_bytes"]
            ok = (mec_b is None or im2col_b is None or saving <= 0
                  or mec_b < im2col_b)
            crosscheck.append({
                "scenario": scenario,
                "mec_temp_bytes": mec_b,
                "im2col_temp_bytes": im2col_b,
                "mec_saving_elems": saving,
                "ok": "yes" if ok else "no",
            })
            if not ok:
                failures.append(
                    f"{scenario}: Eq. 4 predicts a {saving}-element "
                    f"saving but measured mec temp {mec_b}B >= "
                    f"im2col temp {im2col_b}B")
    if calibration_store is not None and calibration_store is not False:
        record_calibration(
            results, None if calibration_store is True else calibration_store)
    doc = make_report(
        "memaudit", results,
        harness={
            "plans_path": str(plans_path) if plans_path else "<in-memory>",
            "tolerances": TOLERANCES,
            "pallas_gated": "yes" if pallas_gated() else "no",
        },
        crosscheck=crosscheck)
    return doc, failures


def write_audit(plans_path=None, out_path=None,
                calibration_store=None) -> Tuple[pathlib.Path, List[str]]:
    from repro.bench.report import write_report
    root = pathlib.Path(__file__).resolve().parents[3]
    doc, failures = run_audit(plans_path, calibration_store=calibration_store)
    out = pathlib.Path(out_path or root / DEFAULT_REPORT)
    write_report(doc, out)
    return out, failures
