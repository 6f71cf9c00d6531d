"""Benchmark harness: one measurement protocol for every scenario.

Protocol (DESIGN.md §3): each (scenario, algorithm) cell is lowered and
compiled ahead of time (``jax.jit(...).lower(...).compile()``); the
pre-compiled executable is called ``warmup`` times to reach steady
state, then ``iters`` times under ``time.perf_counter`` with
``block_until_ready``; ``us_per_call`` is the median.  Alongside the
measured timing every record carries *deterministic* analytic fields —
memory overhead (``repro.core.memory``, paper Eqs. 2–4, on the exact
paper spec) and flops (``repro.launch.costmodel``) — plus the
HLO-derived flops/bytes of the compiled executable
(``repro.launch.hlo_analysis.hlo_flops_bytes``).  The deterministic
fields are what ``repro.bench.check`` gates on; timing is tolerance- or
schema-only checked.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.bench.scenarios import (ALGORITHM_VARIANTS, Scenario,
                                   resolve_suite)
from repro.core.conv_api import conv2d
from repro.core.convspec import ConvSpec
from repro.core.memory import algorithm_overhead
from repro.launch.costmodel import (conv2d_algorithm_costs,
                                    conv_partition_costs,
                                    pick_conv2d_algorithm,
                                    pick_conv_partition)
from repro.launch.hlo_analysis import hlo_flops_bytes

# Variant name -> key into conv2d_algorithm_costs for the flops model
# (all MEC executions compute the same mult-adds as the reference).
_FLOPS_BASE = {"mecA": "mec", "mecB": "mec", "mec_fused": "mec"}


def make_arrays(s: ConvSpec, dtype: str = "float32", seed: int = 0):
    """Deterministic NHWC input + HWIO kernel (``i_c / groups`` input
    channels) for a spec."""
    rng = np.random.RandomState(seed)
    inp = rng.randn(s.i_n, s.i_h, s.i_w, s.i_c).astype(np.float32)
    ker = rng.randn(s.k_h, s.k_w, s.group_c, s.k_c).astype(np.float32)
    return jnp.asarray(inp, dtype), jnp.asarray(ker, dtype)


def time_compiled(call, iters: int = 3, warmup: int = 1) -> Dict:
    """Steady-state wall-clock stats (microseconds) of a nullary call.

    ``us_std`` / ``us_rel_spread`` (std over median) quantify the
    run-to-run jitter of the timed iterations — the data behind the
    planner's ``MEASURED_NOISE_MARGIN``: a measured flip is only
    trustworthy when the margin dominates the observed spread.
    """
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(call())
    us: List[float] = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        us.append((time.perf_counter() - t0) * 1e6)
    median = float(np.median(us))
    std = float(np.std(us))
    return {"iters": max(iters, 1), "warmup": max(warmup, 1),
            "us_median": median, "us_min": float(min(us)),
            "us_mean": float(np.mean(us)), "us_std": std,
            "us_rel_spread": (std / median if median > 0 else None)}


def _analytic_flops(spec: ConvSpec, algorithm: str) -> float:
    costs = conv2d_algorithm_costs(spec)
    base = _FLOPS_BASE.get(algorithm, algorithm)
    return float(costs[base]["flops"])


def _resolved_plan_dict(sc: Scenario) -> Dict:
    """The resolved ConvPlan (repro.plan, analytic policy) for the
    scenario's paper geometry — recorded per cell so a report shows the
    full decision, not just the algorithm name.  Lazy import: bench sits
    below plan in the layer order."""
    from repro.plan import plan_conv2d
    return plan_conv2d(sc.spec, dtype=sc.dtype, mode="analytic",
                       partition="none").to_dict()


def measure(sc: Scenario, algorithm: str, iters: int = 3, warmup: int = 1,
            interpret: Optional[bool] = None, with_hlo: bool = True,
            with_timing: bool = True,
            plan_dict: Optional[Dict] = None) -> Dict:
    """One result record for a (scenario, algorithm) cell.  plan_dict
    lets run_suite derive the (per-scenario, algorithm-independent)
    resolved plan once instead of per cell."""
    kwargs = dict(ALGORITHM_VARIANTS[algorithm])
    stride = (sc.run_spec.s_h, sc.run_spec.s_w)
    dtype_bytes = jnp.zeros((), sc.dtype).dtype.itemsize
    record = {
        "scenario": sc.name,
        "algorithm": algorithm,
        "dtype": sc.dtype,
        "weight": sc.weight,
        "spec": sc.spec.to_dict(),
        "run_spec": sc.run_spec.to_dict(),
        # Deterministic analytics on the exact paper spec (check gates on
        # these) ...
        "overhead_elems": int(algorithm_overhead(sc.spec, algorithm)),
        "overhead_bytes": int(algorithm_overhead(sc.spec, algorithm)
                              * dtype_bytes),
        "flops": _analytic_flops(sc.spec, algorithm),
        # ... and on the (possibly channel-capped) spec actually executed,
        # so HLO numbers have an apples-to-apples analytic partner.
        "run_flops": _analytic_flops(sc.run_spec, algorithm),
        "auto_algorithm": pick_conv2d_algorithm(sc.spec),
        "plan": plan_dict if plan_dict is not None
        else _resolved_plan_dict(sc),
        "out_shape": list(sc.run_spec.out_shape),
        "us_per_call": None,
        "timing": None,
        "hlo_flops": None,
        "hlo_bytes": None,
    }
    mesh = None
    mesh_axis = None
    if sc.partition is not None:
        # Distributed cell: per-device/halo analytics (DESIGN.md §6) are
        # always emitted; execution additionally needs enough devices.
        # Composite cells carry a component tuple + per-sub-axis device
        # tuple; records serialize them via partition_name / n_dev_axes.
        from repro.parallel.conv import (normalize_partition,
                                         partition_name, partition_viable)
        parts = normalize_partition(sc.partition)
        composite = len(parts) > 1
        sizes = tuple(sc.n_dev) if composite else (int(sc.n_dev),)
        n_total = math.prod(sizes)
        dist = conv_partition_costs(
            sc.spec, sizes if composite else sizes[0], dtype_bytes)
        entry = dist[parts if composite else parts[0]]
        record["partition"] = partition_name(parts)
        record["n_dev"] = int(n_total)
        record["n_dev_axes"] = [int(n) for n in sizes]
        record["halo_bytes_per_device"] = entry["halo_bytes_per_device"]
        record["per_device_overhead_elems"] = \
            entry["per_device_overhead_elems"]
        record["comm_bytes_per_device"] = (
            entry["comm_bytes_fwd_per_device"]
            + entry["comm_bytes_bwd_per_device"])
        candidates = {p: n_total for p in ("batch", "channel", "spatial")}
        if composite:
            from repro.parallel.conv import COMPOSITE_PARTITIONS
            candidates.update({c: sizes for c in COMPOSITE_PARTITIONS})
        auto = pick_conv_partition(sc.spec, candidates, dtype_bytes)
        record["auto_partition"] = \
            None if auto is None else partition_name(auto)
        if n_total > jax.device_count() or \
                not partition_viable(sc.run_spec, parts, sc.n_dev):
            with_hlo = with_timing = False
        else:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(shape=sizes)
            mesh_axis = mesh.axis_names if composite else None
    if not (with_hlo or with_timing):
        return record

    inp, ker = make_arrays(sc.run_spec, sc.dtype)
    if mesh is not None:
        from repro.parallel.conv import sharded_conv2d
        fn = jax.jit(lambda i, k: sharded_conv2d(
            i, k, stride=stride, partition=sc.partition, mesh=mesh,
            axis=mesh_axis, interpret=interpret, **kwargs))
    else:
        fn = jax.jit(lambda i, k: conv2d(i, k, stride=stride,
                                         interpret=interpret, **kwargs))
    compiled = fn.lower(inp, ker).compile()
    if with_hlo:
        hlo = hlo_flops_bytes(compiled)
        record["hlo_flops"] = hlo["flops"]
        record["hlo_bytes"] = hlo["bytes_accessed"]
    if mesh is not None and with_hlo:
        # Collective-contract verdict for the executed dist cell
        # (repro.analysis.shardcheck, DESIGN.md §8).  The gated field is
        # the version-robust reduction — verdict, per-direction status,
        # and the costmodel-side expected bytes — because the observed
        # HLO byte evidence may shift with the jax/XLA version matrix
        # while the contract still holds; the full evidence lives in
        # BENCH_shardcheck.json (python -m repro.analysis --suite
        # shardcheck).
        from repro.analysis.shardcheck import check_sharding
        chk = check_sharding(
            sc.run_spec, sc.partition, dtype=sc.dtype,
            algorithm=kwargs.get("algorithm", "auto"),
            solution=kwargs.get("solution", "auto"),
            interpret=interpret, mesh=mesh,
            axes=tuple(mesh.axis_names)).record
        record["shardcheck"] = {
            "verdict": chk["verdict"],
            "skipped_reason": chk["skipped_reason"],
            "directions": {
                d: ("unmodeled" if "unmodeled" in info else "verified")
                for d, info in chk["directions"].items()},
            "expected": {
                d: {"required": info["expected"],
                    "optional": info["optional"]}
                for d, info in chk["directions"].items()
                if "expected" in info},
            "violations": chk["violations"],
        }
    if with_hlo and mesh is None:
        # Numeric-contract verdict for the single-device cell
        # (repro.analysis.numcheck, DESIGN.md §8.5).  Static-only (no
        # probe — the harness must not pay an extra execution per cell)
        # and memoized across cells sharing a (spec, algorithm, dtype);
        # the reduced field is the version-robust verdict, the full
        # signature + probe evidence lives in BENCH_numcheck.json
        # (python -m repro.analysis --suite numcheck).
        from repro.analysis.numcheck import cell_numcheck
        record["numcheck"] = cell_numcheck(
            sc.run_spec, kwargs.get("algorithm", "auto"), sc.dtype,
            solution=kwargs.get("solution", "auto"), interpret=interpret)
    if with_timing:
        timing = time_compiled(lambda: compiled(inp, ker),
                               iters=iters, warmup=warmup)
        record["timing"] = timing
        record["us_per_call"] = timing["us_median"]
    return record


def crosscheck_scenario(records: Sequence[Dict]) -> Dict:
    """Costmodel-vs-measurement cross-validation for one scenario.

    * ``auto_matches_best`` — did ``pick_conv2d_algorithm`` choose the
      algorithm that actually timed fastest here?
    * ``auto_overhead_ok`` — is auto's pick also no worse on analytic
      memory overhead than the measured-fastest one (the paper's point:
      you should not have to pay memory for speed)?
    * ``flops_ratio_hlo`` — per-algorithm HLO flops / analytic flops on
      the executed spec; ~1 means the costmodel predicts what XLA built.
    """
    timed = [r for r in records if r["us_per_call"] is not None]
    out = {"scenario": records[0]["scenario"],
           "auto_algorithm": records[0]["auto_algorithm"],
           "measured_best": None, "auto_matches_best": None,
           "auto_overhead_ok": None, "flops_ratio_hlo": {}}
    for r in records:
        if r["hlo_flops"] and r["run_flops"]:
            out["flops_ratio_hlo"][r["algorithm"]] = \
                round(r["hlo_flops"] / r["run_flops"], 3)
    if not timed:
        return out
    best = min(timed, key=lambda r: r["us_per_call"])
    out["measured_best"] = best["algorithm"]
    auto = out["auto_algorithm"]
    # auto names a conv2d algorithm; bench variants mecA/mecB both map to it
    base_of = {n: kw["algorithm"] for n, kw in ALGORITHM_VARIANTS.items()}
    out["auto_matches_best"] = base_of[best["algorithm"]] == auto
    auto_recs = [r for r in records if base_of[r["algorithm"]] == auto]
    if auto_recs:
        out["auto_overhead_ok"] = \
            auto_recs[0]["overhead_elems"] <= best["overhead_elems"]
    return out


def run_suite(suite: str, iters: int = 3, warmup: int = 1,
              interpret: Optional[bool] = None, with_hlo: bool = True,
              with_timing: bool = True, crosscheck: bool = False,
              progress=None) -> Dict:
    """Run a registered suite and return the report document."""
    from repro.bench.report import make_report
    scenarios = resolve_suite(suite)
    results: List[Dict] = []
    checks: List[Dict] = []
    for sc in scenarios:
        recs = []
        plan_dict = _resolved_plan_dict(sc)   # algorithm-independent
        for alg in sc.algorithms:
            if progress:
                progress(f"[bench] {suite}/{sc.name}/{alg}")
            recs.append(measure(sc, alg, iters=iters, warmup=warmup,
                                interpret=interpret, with_hlo=with_hlo,
                                with_timing=with_timing,
                                plan_dict=plan_dict))
        results.extend(recs)
        if crosscheck:
            checks.append(crosscheck_scenario(recs))
    harness = {"iters": iters, "warmup": warmup,
               "interpret": interpret, "with_hlo": with_hlo,
               "with_timing": with_timing}
    return make_report(suite, results, harness,
                       crosscheck=checks if crosscheck else None)


SERVE_MODES = ("warm", "cold", "auto")


def _serve_requests(cell, kernel_dtype):
    """The cell's deterministic request stream, arrays prebuilt (array
    construction must not pollute the latency measurement)."""
    i_c = cell.kernel_shape[2]
    reqs = []
    for i in range(cell.n_requests):
        n, h, w = cell.requests[i % len(cell.requests)]
        rng = np.random.RandomState(1000 + i)
        x = jnp.asarray(rng.randn(n, h, w, i_c).astype(np.float32),
                        kernel_dtype)
        reqs.append(x)
    jax.block_until_ready(reqs)
    return reqs


def run_serve(progress=None) -> Dict:
    """The ``serve`` suite (DESIGN.md §9): every registered
    :class:`~repro.bench.scenarios.ServeScenario` served under the three
    policies in :data:`SERVE_MODES`, one record per (shape class, mode).

    Latencies are end-to-end request wall-clock *including* each mode's
    real setup profile — warm pays plan resolution + AOT compile before
    the stream starts, cold pays it inside the first request of each
    class (visible as ``first_request_us``/p99), and auto pays eager
    per-call dispatch on every request.  ``us_per_call`` is the p50, so
    the generic timing tolerance of ``repro.bench.check`` applies; the
    analytic fields are the paper's Eq. 3 MEC overhead on the padded
    class spec (backend-independent, gated exactly).
    """
    from repro.bench.report import make_report
    from repro.bench.scenarios import serve_cells
    from repro.plan import plan_conv2d
    from repro.serving.conv_service import ConvService
    results: List[Dict] = []
    for cell in serve_cells():
        rng = np.random.RandomState(7)
        k_h, k_w, i_c, k_c = cell.kernel_shape
        kernel = jnp.asarray(rng.randn(k_h, k_w, i_c, k_c)
                             .astype(np.float32), cell.dtype)
        reqs = _serve_requests(cell, cell.dtype)
        for mode in SERVE_MODES:
            if progress:
                progress(f"[bench] serve/{cell.name}/{mode}")
            svc = ConvService(kernel, stride=cell.stride,
                              padding=cell.padding, classes=cell.classes,
                              plan_mode="cached")
            warmed = svc.warm() if mode == "warm" else None
            per_class: Dict = {cls: [] for cls in svc.classes}
            t_all = time.perf_counter()
            for x in reqs:
                cls = svc.bucket(x.shape)
                t0 = time.perf_counter()
                if mode == "auto":
                    # The pre-planner serving baseline: every request
                    # re-enters conv2d's dispatch eagerly (same padding
                    # work, no frozen plan, no AOT executable).
                    out = conv2d(svc.pad_to_class(x, cls), kernel,
                                 stride=cell.stride, padding=cell.padding,
                                 algorithm="auto")
                    o_n, o_h, o_w, _ = svc.request_out_shape(x.shape)
                    out = out[:o_n, :o_h, :o_w, :]
                else:
                    out = svc.execute(x)
                jax.block_until_ready(out)
                per_class[cls].append((time.perf_counter() - t0) * 1e6)
            total_s = max(time.perf_counter() - t_all, 1e-9)
            throughput = len(reqs) / total_s
            for cls in svc.classes:
                spec = svc.class_spec(cls)
                lat = per_class[cls]
                record = {
                    "scenario": f"{cell.name}_c{cls.tag()}",
                    "algorithm": mode,
                    "dtype": cell.dtype,
                    "weight": 1,
                    "spec": spec.to_dict(),
                    "run_spec": spec.to_dict(),
                    # Eq. 3 on the padded class spec: the memory the
                    # serving layer's MEC lowering costs per class
                    # request — backend-independent, exact-gated.
                    "overhead_elems": int(algorithm_overhead(spec, "mec")),
                    "overhead_bytes": int(
                        algorithm_overhead(spec, "mec")
                        * jnp.dtype(cell.dtype).itemsize),
                    "flops": _analytic_flops(spec, "mec"),
                    "run_flops": _analytic_flops(spec, "mec"),
                    "auto_algorithm": pick_conv2d_algorithm(spec),
                    "plan": plan_conv2d(spec, dtype=cell.dtype,
                                        mode="analytic",
                                        partition="none").to_dict(),
                    "out_shape": list(spec.out_shape),
                    "us_per_call": (float(np.percentile(lat, 50))
                                    if lat else None),
                    "timing": ({"n": len(lat),
                                "us_p50": float(np.percentile(lat, 50)),
                                "us_p99": float(np.percentile(lat, 99)),
                                "us_mean": float(np.mean(lat)),
                                "us_min": float(min(lat)),
                                "us_max": float(max(lat))}
                               if lat else None),
                    "hlo_flops": None,
                    "hlo_bytes": None,
                    "serve_mode": mode,
                    "shape_class": cls.tag(),
                    "n_classes": len(svc.classes),
                    "n_requests": len(lat),
                    "p50_us": (float(np.percentile(lat, 50))
                               if lat else None),
                    "p99_us": (float(np.percentile(lat, 99))
                               if lat else None),
                    "first_request_us": float(lat[0]) if lat else None,
                    "throughput_rps": float(throughput),
                    "warmup_warnings": (warmed.warning_count
                                        if warmed else 0),
                    "plan_cache_io_errors": (warmed.plan_cache_io_errors
                                             if warmed else 0),
                }
                results.append(record)
    harness = {"modes": list(SERVE_MODES),
               "latency": "end-to-end request wall-clock incl. each "
                          "mode's setup profile"}
    return make_report("serve", results, harness)


def run_autotune(base_suite: str = "smoke", iters: int = 3, warmup: int = 1,
                 interpret: Optional[bool] = None, progress=None) -> Dict:
    """Analytic-vs-measured pick quality (the ``autotune`` scenario).

    For every scenario in ``base_suite``, derive the analytic plan on
    the *timed* geometry (``run_spec`` — both picks must be judged on
    the shapes actually measured), then run the full measured policy
    (``repro.plan.tune_measured`` — the same staged race + knob grid
    ``plan_conv2d(mode="measured")`` uses, so these numbers ARE the
    planner's numbers) and record both picks with their steady-state
    times.  ``speedup`` > 1 means measured autotuning beat the analytic
    costmodel on that cell.

    Schema v2 additions (DESIGN.md §10): per-candidate full timing
    stats including spread (``candidate_stats``) — the evidence behind
    the 5%% noise margin; candidates that could not be timed with their
    reasons (``skipped``/``n_skipped`` — nothing is dropped silently);
    the stage-2 knob grid (``tuning``) and final measured ``plan``; and
    the active calibration's provenance (every trial here feeds the
    calibration store, so autotune runs are the fitted costmodel's
    training data).
    """
    from repro.bench.report import environment_fingerprint
    from repro.plan import pick_measured, plan_conv2d, tune_measured
    from repro.plan.calibrate import calibration_info
    from repro.plan.convplan import MEASURED_NOISE_MARGIN
    results: List[Dict] = []
    for sc in resolve_suite(base_suite):
        if progress:
            progress(f"[bench] autotune/{sc.name}")
        analytic = plan_conv2d(sc.run_spec, dtype=sc.dtype, mode="analytic",
                               partition="none")
        plan, detail = tune_measured(sc.run_spec, sc.dtype, iters=iters,
                                     warmup=warmup, interpret=interpret,
                                     candidates=sc.tune_candidates)
        times = detail["candidate_us"]
        # The planner's own decision rule: noise-margin tie to analytic,
        # margin widened to each candidate's observed rel spread (§10).
        measured_alg = pick_measured(times, analytic.algorithm, spreads={
            a: s.get("us_rel_spread")
            for a, s in detail["candidate_stats"].items()})
        analytic_us = times.get(analytic.algorithm)
        measured_us = times[measured_alg]
        spreads = [s.get("us_rel_spread")
                   for s in detail["candidate_stats"].values()
                   if s.get("us_rel_spread") is not None]
        results.append({
            "scenario": sc.name,
            "dtype": sc.dtype,
            "run_spec": sc.run_spec.to_dict(),
            "analytic_algorithm": analytic.algorithm,
            "analytic_us": analytic_us,
            "measured_algorithm": measured_alg,
            "measured_us": measured_us,
            "candidate_us": {a: times[a] for a in sorted(times)},
            "candidate_stats": {a: detail["candidate_stats"][a]
                                for a in sorted(detail["candidate_stats"])},
            "skipped": dict(sorted(detail["skipped"].items())),
            "n_skipped": len(detail["skipped"]),
            "max_rel_spread": (round(max(spreads), 4) if spreads else None),
            "tuning": detail["tuning"],
            "plan": plan.to_dict(),
            "speedup": (None if not analytic_us
                        else round(analytic_us / measured_us, 3)),
            "pick_agrees": measured_alg == analytic.algorithm,
        })
    return {
        "autotune_schema_version": 2,
        "suite": "autotune",
        "base_suite": base_suite,
        "environment": environment_fingerprint(),
        "calibration": calibration_info(),
        "harness": {"iters": iters, "warmup": warmup,
                    "interpret": interpret,
                    "noise_margin": MEASURED_NOISE_MARGIN},
        "results": results,
    }
