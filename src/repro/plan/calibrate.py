"""Fitted costmodel calibration (DESIGN.md §10).

The paper's analytic model (Eqs. 2-4) ranks lowerings by memory
overhead, but the right pick is microarchitecture-dependent: the
committed ``BENCH_autotune.json`` shows ``direct`` beating the analytic
``mec`` pick 2.1x on the s5x5 smoke cell, and ``BENCH_memaudit.json``
shows XLA's measured mec temp bytes running 1.03-1.51x the Eq. 3
prediction while im2col lands at exactly 1.00x.  This module closes the
loop: it accumulates the planner's own measurements and turns them into
per-backend/per-device-kind correction coefficients the costmodel
consults.

Two kinds of evidence feed one :class:`Calibration`:

* **time samples** — every trial ``plan_conv2d(mode="measured")`` /
  ``repro.bench --suite autotune`` times (keyed
  ``spec|dtype|algorithm|solution|w_blk``), recorded by
  ``repro.plan.convplan.measure_candidates``: autotune runs ARE the
  training data;
* **memory samples** — measured/predicted temp-byte ratios from
  ``repro.analysis.memaudit`` (keyed ``spec|dtype|algorithm``).

Fitting produces three views (:meth:`Calibration.fit`):

* ``time_cells`` — per-cell measured us per algorithm; where a spec has
  direct evidence covering the analytic pick plus a rival, the pick is
  re-decided through ``pick_measured``'s noise margin (this is what
  flips s5x5 to ``direct``; cells without evidence keep the paper
  rule — a fit from three smoke cells must not rewrite Table 2);
* ``time_constants`` — per-algorithm least-squares constants of
  ``us ~ c0 + c_flops*flops + c_overhead*overhead_elems`` (the Eq. 2-4
  time model the paper leaves implicit), reported by
  ``python -m repro.plan calibrate --report``;
* ``mem_ratio`` — per-algorithm geometric-mean measured/Eq. 2-3 byte
  ratio (paper constant: 1.0), which scales the overhead comparison in
  ``pick_conv2d_algorithm`` and the per-device predictions of
  ``conv_partition_costs``.

Persistence mirrors ``repro.plan.cache.PlanCache`` exactly: one JSON
file per environment fingerprint beside the plan cache
(``calibration-<fingerprint>.json`` under ``plan_cache_dir()``), the
fingerprint change IS the invalidation rule, disk I/O is best-effort
(missing/corrupt/read-only degrades silently to the uncalibrated
analytic constants, counted in ``CalibrationStore.io_errors``), and
writes are atomic (tempfile + ``os.replace``).  ``$REPRO_CALIBRATION``
points the ambient lookup at an explicit file instead (CI uses the
committed ``benchmarks/baselines/calibration.json``); explicit files
are matched on backend + device kind rather than the full fingerprint,
so a committed CPU calibration survives a jax patch bump but never
leaks onto a TPU.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import pathlib
import tempfile
from typing import Dict, List, Optional, Tuple

from repro.core.convspec import ConvSpec
from repro.plan.convplan import spec_key

CALIBRATION_FILE_VERSION = 1
CALIBRATION_ENV = "REPRO_CALIBRATION"

# Keep the last N samples per (spec, dtype, algorithm, solution, w_blk)
# key: enough to median away scheduler noise, bounded so a long-running
# autotune loop cannot grow the file without limit.
MAX_SAMPLES_PER_KEY = 32

DEFAULT_BASELINE = "benchmarks/baselines/calibration.json"


def calibration_path() -> pathlib.Path:
    """The fingerprinted store file beside the plan cache."""
    from repro.plan.cache import environment_fingerprint, plan_cache_dir
    return plan_cache_dir() / f"calibration-{environment_fingerprint()}.json"


def time_sample_key(spec: ConvSpec, dtype: str, algorithm: str,
                    solution: str = "auto",
                    w_blk: Optional[int] = None) -> str:
    blk = "-" if w_blk is None else str(int(w_blk))
    return f"{spec_key(spec)}|{dtype}|{algorithm}|{solution}|{blk}"


def mem_sample_key(spec: ConvSpec, dtype: str, algorithm: str) -> str:
    return f"{spec_key(spec)}|{dtype}|{algorithm}"


def parse_spec_key(key: str) -> ConvSpec:
    """Inverse of ``repro.plan.spec_key`` (sample keys embed it)."""
    dims, kpart, spart = key.split("-")
    i_n, i_h, i_w, i_c = (int(v) for v in dims.split("x"))
    k_h, k_w, k_c = (int(v) for v in kpart[1:].split("x"))
    s_h, s_w = (int(v) for v in spart[1:].split("x"))
    return ConvSpec(i_n, i_h, i_w, i_c, k_h, k_w, k_c, s_h, s_w)


def _geomean(values: List[float]) -> float:
    return math.exp(sum(math.log(max(v, 1e-12)) for v in values)
                    / len(values))


def _features(spec: ConvSpec, algorithm: str) -> Tuple[float, float]:
    """(flops, overhead_elems) of the Eq. 2-4 time model for one trial.

    Overhead follows ``repro.core.memory.algorithm_overhead`` (variant
    names resolve through ``_DISPATCH_BASE``: the fused Pallas kernels
    predict the direct conv's zero HBM overhead); flops are the base
    algorithm's from ``conv2d_algorithm_costs`` (every MEC variant
    computes the same mult-adds).
    """
    from repro.core import memory
    from repro.launch.costmodel import conv2d_algorithm_costs
    overhead = float(memory.algorithm_overhead(spec, algorithm))
    costs = conv2d_algorithm_costs(spec)
    base = algorithm if algorithm in costs else \
        ("mec" if algorithm.startswith("mec") else algorithm)
    flops = float(costs[base]["flops"]) if base in costs \
        else float(memory.conv_flops(spec))
    return flops, overhead


def _current_env() -> Tuple[str, str]:
    import jax
    return jax.default_backend(), jax.devices()[0].device_kind


@dataclasses.dataclass
class Calibration:
    """Accumulated measurements + the fits derived from them, for one
    (backend, device kind).  Coefficients never cross backends: a
    calibration only applies to picks made for ``self.backend``."""

    backend: str
    device_kind: str
    fingerprint: str
    time_samples: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    mem_samples: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)

    @classmethod
    def for_current_env(cls) -> "Calibration":
        from repro.plan.cache import environment_fingerprint
        backend, kind = _current_env()
        return cls(backend=backend, device_kind=kind,
                   fingerprint=environment_fingerprint())

    def is_empty(self) -> bool:
        return not self.time_samples and not self.mem_samples

    # ------------------------------------------------------------ recording

    def add_time(self, spec: ConvSpec, dtype: str, algorithm: str,
                 us: float, solution: str = "auto",
                 w_blk: Optional[int] = None) -> None:
        key = time_sample_key(spec, dtype, algorithm, solution, w_blk)
        samples = self.time_samples.setdefault(key, [])
        samples.append(float(us))
        del samples[:-MAX_SAMPLES_PER_KEY]

    def add_memory(self, spec: ConvSpec, dtype: str, algorithm: str,
                   ratio: float) -> None:
        key = mem_sample_key(spec, dtype, algorithm)
        samples = self.mem_samples.setdefault(key, [])
        samples.append(float(ratio))
        del samples[:-MAX_SAMPLES_PER_KEY]

    def merge(self, other: "Calibration") -> None:
        for key, samples in other.time_samples.items():
            mine = self.time_samples.setdefault(key, [])
            mine.extend(samples)
            del mine[:-MAX_SAMPLES_PER_KEY]
        for key, samples in other.mem_samples.items():
            mine = self.mem_samples.setdefault(key, [])
            mine.extend(samples)
            del mine[:-MAX_SAMPLES_PER_KEY]

    # -------------------------------------------------------------- fitting

    def time_cells(self) -> Dict[str, Dict[str, float]]:
        """spec-key -> algorithm -> best (min over solution/w_blk/dtype
        variants) median us — the cell-level evidence picks consult."""
        cells: Dict[str, Dict[str, float]] = {}
        import numpy as np
        for key, samples in self.time_samples.items():
            if not samples:
                continue
            spec_part, _dtype, alg, _sol, _blk = key.split("|")
            med = float(np.median(samples))
            algs = cells.setdefault(spec_part, {})
            algs[alg] = min(algs.get(alg, med), med)
        return cells

    def cell_times(self, spec: ConvSpec) -> Dict[str, float]:
        return self.time_cells().get(spec_key(spec), {})

    def mem_ratios(self) -> Dict[str, Dict[str, float]]:
        """algorithm -> {ratio (geomean), n} measured/predicted bytes."""
        by_alg: Dict[str, List[float]] = {}
        for key, samples in self.mem_samples.items():
            alg = key.split("|")[2]
            by_alg.setdefault(alg, []).extend(samples)
        return {alg: {"ratio": _geomean(samples), "n": len(samples)}
                for alg, samples in sorted(by_alg.items()) if samples}

    def mem_ratio_for(self, algorithm: str) -> float:
        """Fitted byte ratio for one algorithm; 1.0 (the paper's
        implicit constant) when unfitted."""
        entry = self.mem_ratios().get(algorithm)
        return float(entry["ratio"]) if entry else 1.0

    def time_constants(self) -> Dict[str, Dict[str, float]]:
        """Per-algorithm least-squares constants of the Eq. 2-4 time
        model ``us ~ c0 + c_flops*flops + c_overhead*overhead_elems``.

        Reported (``calibrate --report``) and used for ``time_us_est``
        in ``conv2d_algorithm_costs``; picks never extrapolate through
        these — cell-level evidence gates every flip.
        """
        import numpy as np
        by_alg: Dict[str, List[Tuple[float, float, float]]] = {}
        for cell, algs in self.time_cells().items():
            spec = parse_spec_key(cell)
            for alg, us in algs.items():
                flops, overhead = _features(spec, alg)
                by_alg.setdefault(alg, []).append((flops, overhead, us))
        out: Dict[str, Dict[str, float]] = {}
        for alg, rows in sorted(by_alg.items()):
            a = np.array([[1.0, f, o] for f, o, _ in rows])
            b = np.array([us for _, _, us in rows])
            coef, *_ = np.linalg.lstsq(a, b, rcond=None)
            out[alg] = {"c0": float(coef[0]), "c_flops": float(coef[1]),
                        "c_overhead": float(coef[2]), "n": len(rows)}
        return out

    def time_estimate(self, spec: ConvSpec, algorithm: str,
                      constants: Optional[Dict] = None) -> Optional[float]:
        constants = self.time_constants() if constants is None else constants
        c = constants.get(algorithm)
        if c is None:
            return None
        flops, overhead = _features(spec, algorithm)
        return c["c0"] + c["c_flops"] * flops + c["c_overhead"] * overhead

    def decisions(self) -> Dict[str, Dict[str, str]]:
        """Per evidence cell: the paper-rule pick vs the calibrated pick
        — the decision fields ``calibrate --check`` gates exactly."""
        from repro.launch.costmodel import pick_conv2d_algorithm
        out: Dict[str, Dict[str, str]] = {}
        for cell in sorted(self.time_cells()):
            spec = parse_spec_key(cell)
            out[cell] = {
                "uncalibrated": pick_conv2d_algorithm(
                    spec, self.backend, calibration=None),
                "calibrated": pick_conv2d_algorithm(
                    spec, self.backend, calibration=self),
            }
        return out

    def fit(self) -> Dict:
        return {
            "time_cells": self.time_cells(),
            "time_constants": self.time_constants(),
            "mem_ratio": self.mem_ratios(),
            "decisions": self.decisions(),
        }

    # -------------------------------------------------------- serialization

    def to_dict(self, with_fit: bool = True) -> Dict:
        import jax
        doc = {
            "calibration_file_version": CALIBRATION_FILE_VERSION,
            "fingerprint": self.fingerprint,
            "backend": self.backend,
            "device_kind": self.device_kind,
            "jax": jax.__version__,
            "time_samples": {k: list(v) for k, v
                             in sorted(self.time_samples.items())},
            "mem_samples": {k: list(v) for k, v
                            in sorted(self.mem_samples.items())},
        }
        if with_fit:
            doc["fitted"] = self.fit()
        return doc

    @classmethod
    def from_dict(cls, doc: Dict) -> "Calibration":
        version = doc.get("calibration_file_version")
        if version != CALIBRATION_FILE_VERSION:
            raise ValueError(f"calibration_file_version {version!r} is not "
                             f"{CALIBRATION_FILE_VERSION}")
        return cls(
            backend=doc["backend"],
            device_kind=doc.get("device_kind", "unknown"),
            fingerprint=doc.get("fingerprint", ""),
            time_samples={str(k): [float(x) for x in v]
                          for k, v in doc.get("time_samples", {}).items()},
            mem_samples={str(k): [float(x) for x in v]
                         for k, v in doc.get("mem_samples", {}).items()},
        )


def resolve_calibration(calibration, backend: str) -> Optional[Calibration]:
    """``"ambient"`` | None | Calibration -> the Calibration a pick for
    ``backend`` may consult (None when absent or backend-mismatched:
    coefficients fitted on one backend never decide picks on another).
    """
    if calibration is None:
        return None
    if calibration == "ambient":
        calibration = current_calibration()
        if calibration is None:
            return None
    return calibration if calibration.backend == backend else None


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

class CalibrationStore:
    """Best-effort accumulation into the fingerprinted store file.

    ``add_time``/``add_memory`` buffer in memory; ``flush()`` merges the
    buffer into whatever is on disk (load -> merge -> atomic rewrite),
    so concurrent autotune runs append rather than clobber.  All disk
    failure modes degrade silently and bump ``io_errors`` — the same
    stance (and counter name) as ``PlanCache``.
    """

    def __init__(self, path: Optional[pathlib.Path] = None):
        self._explicit_path = pathlib.Path(path) if path is not None else None
        self.pending = Calibration.for_current_env()
        self.io_errors = 0

    def path(self) -> pathlib.Path:
        if self._explicit_path is not None:
            return self._explicit_path
        return calibration_path()

    def add_time(self, spec: ConvSpec, dtype: str, algorithm: str,
                 us: float, solution: str = "auto",
                 w_blk: Optional[int] = None) -> None:
        self.pending.add_time(spec, dtype, algorithm, us, solution, w_blk)

    def add_memory(self, spec: ConvSpec, dtype: str, algorithm: str,
                   ratio: float) -> None:
        self.pending.add_memory(spec, dtype, algorithm, ratio)

    def load(self) -> Calibration:
        """The on-disk calibration, or a fresh empty one.  A file whose
        fingerprint does not match the current environment is ignored —
        the PlanCache invalidation rule."""
        fresh = Calibration.for_current_env()
        path = self.path()
        try:
            text = path.read_text()
        except FileNotFoundError:
            return fresh
        except OSError:
            self.io_errors += 1
            return fresh
        try:
            calib = Calibration.from_dict(json.loads(text))
        except (ValueError, KeyError, TypeError):
            self.io_errors += 1       # corrupt file: degrade, but count it
            return fresh
        if calib.fingerprint != fresh.fingerprint:
            return fresh
        return calib

    def flush(self) -> None:
        if self.pending.is_empty():
            return
        disk = self.load()
        disk.merge(self.pending)
        self.pending = Calibration.for_current_env()
        path = self.path()
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                       prefix=path.name, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(disk.to_dict(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            self.io_errors += 1       # read-only environment: drop silently
        _load_cache.pop(str(path), None)


# Ambient lookup cache: path -> (stat signature, Calibration or None).
# Keyed by path (not a process singleton) so tests that repoint
# REPRO_PLAN_CACHE_DIR / REPRO_CALIBRATION see the change immediately.
_load_cache: Dict[str, Tuple[Optional[Tuple[int, int]],
                             Optional[Calibration]]] = {}


def _load_file(path: pathlib.Path, strict_fingerprint: bool
               ) -> Optional[Calibration]:
    try:
        sig_stat = path.stat()
        sig = (sig_stat.st_mtime_ns, sig_stat.st_size)
    except OSError:
        sig = None
    cached = _load_cache.get(str(path))
    if cached is not None and cached[0] == sig:
        return cached[1]
    calib: Optional[Calibration] = None
    if sig is not None:
        try:
            calib = Calibration.from_dict(json.loads(path.read_text()))
        except (OSError, ValueError, KeyError, TypeError):
            calib = None              # silent degradation to uncalibrated
    if calib is not None:
        if strict_fingerprint:
            from repro.plan.cache import environment_fingerprint
            if calib.fingerprint != environment_fingerprint():
                calib = None
        else:
            backend, kind = _current_env()
            if calib.backend != backend or calib.device_kind != kind:
                calib = None          # committed file from another device
    _load_cache[str(path)] = (sig, calib)
    return calib


def reset_calibration_cache() -> None:
    """Forget memoized file loads (tests)."""
    _load_cache.clear()


def current_calibration() -> Optional[Calibration]:
    """The ambient calibration the planner consults by default:
    ``$REPRO_CALIBRATION`` (explicit file, backend/device-kind matched)
    if set, else the fingerprinted store beside the plan cache.  None —
    the uncalibrated analytic constants — when absent, corrupt, empty,
    or environment-mismatched."""
    env = os.environ.get(CALIBRATION_ENV)
    if env:
        calib = _load_file(pathlib.Path(env), strict_fingerprint=False)
    else:
        calib = _load_file(calibration_path(), strict_fingerprint=True)
    if calib is None or calib.is_empty():
        return None
    return calib


def calibration_info() -> Dict:
    """Provenance block for bench reports: is a calibration active, and
    where did it come from?"""
    env = os.environ.get(CALIBRATION_ENV)
    calib = current_calibration()
    return {
        "active": calib is not None,
        "source": (f"env:{env}" if env else
                   (f"store:{calibration_path()}" if calib is not None
                    else None)),
        "backend": None if calib is None else calib.backend,
        "cells": 0 if calib is None else len(calib.time_cells()),
    }


# ---------------------------------------------------------------------------
# report ingestion (building the committed baseline)
# ---------------------------------------------------------------------------

def ingest_autotune(calib: Calibration, doc: Dict) -> int:
    """Fold a BENCH_autotune.json (schema v1 or v2) into ``calib`` as
    time samples.  Returns the number of samples added."""
    n = 0
    for rec in doc.get("results", []):
        spec = ConvSpec(**rec["run_spec"])
        dtype = rec.get("dtype", "float32")
        stats = rec.get("candidate_stats") or {}
        for alg, us in (rec.get("candidate_us") or {}).items():
            meta = stats.get(alg) or {}
            calib.add_time(spec, dtype, alg, float(us),
                           solution=meta.get("solution", "auto"),
                           w_blk=meta.get("w_blk"))
            n += 1
        tuning = rec.get("tuning") or {}
        for label, trial in (tuning.get("trials") or {}).items():
            if tuning.get("knob") == "solution":
                calib.add_time(spec, dtype, tuning["algorithm"],
                               float(trial["us_median"]), solution=label)
            elif tuning.get("knob") == "w_blk":
                calib.add_time(spec, dtype, tuning["algorithm"],
                               float(trial["us_median"]),
                               w_blk=int(label))
            n += 1
    return n


def ingest_memaudit(calib: Calibration, doc: Dict) -> int:
    """Fold a BENCH_memaudit.json into ``calib`` as memory samples.
    Only tolerance-gated cells count: Pallas interpret-mode temps are
    XLA artifacts, not the kernel's memory story."""
    from repro.core.memory import _DISPATCH_BASE
    n = 0
    for rec in doc.get("results", []):
        if rec.get("policy") != "gated" or rec.get("ratio") is None:
            continue
        spec = ConvSpec(**rec["spec"])
        base = _DISPATCH_BASE.get(rec["algorithm"], rec["algorithm"])
        calib.add_memory(spec, rec.get("dtype", "float32"), base,
                         float(rec["ratio"]))
        n += 1
    return n


# ---------------------------------------------------------------------------
# CLI: python -m repro.plan calibrate ...
# ---------------------------------------------------------------------------

def _repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[3]


def _rel_close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-9)


def check_calibration(doc: Dict, rtol: float = 0.05) -> List[str]:
    """Gate a calibration document: the stored ``fitted`` block must be
    reproducible from the stored samples — decision fields exactly,
    coefficients within ``rtol`` (numpy lstsq may wobble across
    versions).  Returns the failure list (empty == pass)."""
    failures: List[str] = []
    try:
        calib = Calibration.from_dict(doc)
    except (ValueError, KeyError, TypeError) as e:
        return [f"unreadable calibration document: {e}"]
    stored = doc.get("fitted")
    if not isinstance(stored, dict):
        return ["no 'fitted' block: regenerate with "
                "python -m repro.plan calibrate --fit"]
    refit = calib.fit()
    # Decisions: exact, both directions.
    for cell in sorted(set(stored.get("decisions", {}))
                       | set(refit["decisions"])):
        a = stored.get("decisions", {}).get(cell)
        b = refit["decisions"].get(cell)
        if a != b:
            failures.append(f"decision drift on {cell}: stored {a!r} "
                            f"vs refit {b!r}")
    # Coefficients: tolerance.
    for alg in sorted(set(stored.get("time_constants", {}))
                      | set(refit["time_constants"])):
        a = stored.get("time_constants", {}).get(alg)
        b = refit["time_constants"].get(alg)
        if (a is None) != (b is None):
            failures.append(f"time_constants coverage drift on {alg}")
            continue
        for coef in ("c0", "c_flops", "c_overhead"):
            if not _rel_close(a[coef], b[coef], rtol):
                failures.append(f"time_constants[{alg}][{coef}] "
                                f"{a[coef]:.6g} vs refit {b[coef]:.6g} "
                                f"(rtol {rtol})")
    for alg in sorted(set(stored.get("mem_ratio", {}))
                      | set(refit["mem_ratio"])):
        a = stored.get("mem_ratio", {}).get(alg)
        b = refit["mem_ratio"].get(alg)
        if (a is None) != (b is None):
            failures.append(f"mem_ratio coverage drift on {alg}")
            continue
        if not _rel_close(a["ratio"], b["ratio"], rtol):
            failures.append(f"mem_ratio[{alg}] {a['ratio']:.6g} vs refit "
                            f"{b['ratio']:.6g} (rtol {rtol})")
    for cell in sorted(set(stored.get("time_cells", {}))
                       | set(refit["time_cells"])):
        a = stored.get("time_cells", {}).get(cell, {})
        b = refit["time_cells"].get(cell, {})
        for alg in sorted(set(a) | set(b)):
            if alg not in a or alg not in b:
                failures.append(f"time_cells coverage drift on "
                                f"{cell}/{alg}")
            elif not _rel_close(a[alg], b[alg], rtol):
                failures.append(f"time_cells[{cell}][{alg}] {a[alg]:.6g} "
                                f"vs refit {b[alg]:.6g} (rtol {rtol})")
    return failures


def render_report(calib: Calibration) -> List[str]:
    """Fitted-vs-paper constants, one block per evidence cell."""
    lines = [f"[calibrate] backend={calib.backend} "
             f"device_kind={calib.device_kind} "
             f"fingerprint={calib.fingerprint}"]
    constants = calib.time_constants()
    decisions = calib.decisions()
    for cell, algs in sorted(calib.time_cells().items()):
        spec = parse_spec_key(cell)
        lines.append(f"cell {cell}:")
        lines.append(f"  {'algorithm':12s} {'Eq.2-4 elems':>12s} "
                     f"{'flops':>12s} {'measured us':>12s} "
                     f"{'fitted us':>10s}")
        for alg in sorted(algs):
            flops, overhead = _features(spec, alg)
            est = calib.time_estimate(spec, alg, constants)
            lines.append(
                f"  {alg:12s} {overhead:12.3e} {flops:12.3e} "
                f"{algs[alg]:12.1f} "
                f"{'-' if est is None else format(est, '10.1f')}")
        d = decisions.get(cell, {})
        flip = "" if d.get("uncalibrated") == d.get("calibrated") \
            else "   <-- flip"
        lines.append(f"  pick: paper={d.get('uncalibrated')} "
                     f"calibrated={d.get('calibrated')}{flip}")
    lines.append("memory ratios (measured / Eq. 2-3 prediction; "
                 "paper constant 1.0):")
    for alg, entry in calib.mem_ratios().items():
        lines.append(f"  {alg:12s} {entry['ratio']:.4f}  "
                     f"(n={entry['n']})")
    lines.append("time constants "
                 "(us ~ c0 + c_flops*flops + c_overhead*overhead):")
    for alg, c in constants.items():
        lines.append(f"  {alg:12s} c0={c['c0']:+.4g} "
                     f"c_flops={c['c_flops']:+.4g} "
                     f"c_overhead={c['c_overhead']:+.4g} (n={c['n']})")
    return lines


def calibrate_main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="repro.plan calibrate",
        description="Fitted-costmodel calibration: report, gate, or "
                    "(re)build the coefficient file (DESIGN.md §10)")
    ap.add_argument("--report", action="store_true",
                    help="print fitted-vs-paper constants per cell")
    ap.add_argument("--check", action="store_true",
                    help="gate a calibration file: stored fit must be "
                         "reproducible from its samples (decisions "
                         "exact, coefficients within --rtol)")
    ap.add_argument("--fit", action="store_true",
                    help="build a calibration from the ambient store "
                         "and/or report files; write it with --out")
    ap.add_argument("--baseline", default=None,
                    help=f"calibration JSON to report on / check "
                         f"(default: {DEFAULT_BASELINE})")
    ap.add_argument("--rtol", type=float, default=0.05,
                    help="coefficient tolerance for --check")
    ap.add_argument("--autotune", default=None,
                    help="BENCH_autotune.json to ingest for --fit")
    ap.add_argument("--memaudit", default=None,
                    help="BENCH_memaudit.json to ingest for --fit")
    ap.add_argument("--out", default=None,
                    help="where --fit writes the calibration JSON")
    args = ap.parse_args(argv)

    baseline = pathlib.Path(args.baseline) if args.baseline \
        else _repo_root() / DEFAULT_BASELINE

    if args.fit:
        calib = CalibrationStore().load()
        for path, ingest in ((args.autotune, ingest_autotune),
                             (args.memaudit, ingest_memaudit)):
            if path is None:
                continue
            try:
                doc = json.loads(pathlib.Path(path).read_text())
            except (OSError, ValueError) as e:
                print(f"[calibrate] cannot read {path}: {e}",
                      file=__import__("sys").stderr)
                return 2
            n = ingest(calib, doc)
            print(f"[calibrate] ingested {n} sample(s) from {path}")
        if calib.is_empty():
            print("[calibrate] nothing to fit: no samples in the store "
                  "or the given reports", file=__import__("sys").stderr)
            return 2
        out = pathlib.Path(args.out) if args.out else baseline
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(calib.to_dict(), indent=1,
                                  sort_keys=True) + "\n")
        flips = sum(1 for d in calib.decisions().values()
                    if d["uncalibrated"] != d["calibrated"])
        print(f"[calibrate] {len(calib.time_cells())} time cell(s), "
              f"{len(calib.mem_ratios())} memory-fitted algorithm(s), "
              f"{flips} calibrated flip(s) -> {out}")
        if args.report:
            for line in render_report(calib):
                print(line)
        return 0

    if args.check:
        try:
            doc = json.loads(baseline.read_text())
        except (OSError, ValueError) as e:
            print(f"[calibrate] cannot read {baseline}: {e}",
                  file=__import__("sys").stderr)
            return 2
        failures = check_calibration(doc, rtol=args.rtol)
        if failures:
            import sys
            for f in failures:
                print(f"[calibrate] FAIL: {f}", file=sys.stderr)
            print(f"[calibrate] {len(failures)} failure(s) in {baseline}",
                  file=sys.stderr)
            return 1
        n_cells = len(doc.get("fitted", {}).get("time_cells", {}))
        print(f"[calibrate] OK: {baseline} is self-consistent "
              f"({n_cells} cell(s), rtol {args.rtol})")
        if not args.report:
            return 0

    # --report (also the default action)
    calib = None
    if args.baseline:
        calib = _load_file(baseline, strict_fingerprint=False)
    if calib is None:
        calib = current_calibration()
    if calib is None and baseline.exists():
        calib = _load_file(baseline, strict_fingerprint=False)
    if calib is None or calib.is_empty():
        print("[calibrate] no calibration found (no ambient store, no "
              f"{baseline}); run the autotune suite or calibrate --fit",
              file=__import__("sys").stderr)
        return 2
    for line in render_report(calib):
        print(line)
    return 0
