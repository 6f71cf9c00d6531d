"""ConvPlan — the frozen planner/executor decision record (DESIGN.md §7).

MEC's win is choosing the right lowering per shape (paper §3-4, Table 2:
no single algorithm wins every cv1-cv12 cell).  A :class:`ConvPlan`
captures the *entire* decision for one convolution — geometry
(:class:`~repro.core.convspec.ConvSpec`), dtype, algorithm, MEC
solution, Pallas ``w_blk``, GEMM precision, and the distributed
partition (components + mesh axes) — so it can be inspected
(:meth:`ConvPlan.explain`), serialized (:meth:`ConvPlan.to_json`),
cached (``repro.plan.cache``), and executed exactly by the thin
``conv2d(..., plan=)`` executor.

:func:`plan_conv2d` produces plans under three policies:

``analytic``  the costmodel pick (``repro.launch.costmodel``), exactly
              what the pre-planner ``conv2d(algorithm="auto")`` derived
              per call — now derived once.
``measured``  AOT-compile every candidate algorithm and time it through
              the ``repro.bench.harness`` steady-state protocol; the
              wall-clock winner becomes the plan.  A second stage then
              tunes the winner's knobs — the MEC solution (§3.2
              Solutions 1-2: h- vs w-direction lowering) or the Pallas
              ``w_blk`` — over a small measured grid, and every trial
              is recorded into the calibration store
              (``repro.plan.calibrate``, DESIGN.md §10): autotune runs
              are the fitted costmodel's training data.
``cached``    process-level LRU backed by an on-disk JSON cache keyed
              by spec+dtype+backend (env-fingerprinted file); a miss
              falls back to ``analytic`` and populates both tiers.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Sequence, Tuple, Union

from repro.core.convspec import ConvSpec
from repro.core.mec import SOLUTIONS, pick_solution

PLAN_VERSION = 1

# Canonical names for jax.lax.Precision members (plan JSON stores the
# name, never the enum, so reports stay readable and version-stable).
PRECISION_NAMES = ("DEFAULT", "HIGH", "HIGHEST")

_SINGLE_DEVICE_ALGOS = ("direct", "im2col", "fft", "winograd", "mec",
                        "mec_fused")

PLAN_MODES = ("analytic", "measured", "cached")


def _precision_name(precision) -> Optional[str]:
    """None | 'highest' | lax.Precision.HIGHEST -> canonical name/None."""
    if precision is None:
        return None
    if isinstance(precision, str):
        name = precision.upper()
    elif isinstance(precision, tuple):
        raise ValueError(
            f"per-operand precision tuples are not plannable: {precision!r}")
    else:
        name = getattr(precision, "name", None)
        if name is None:
            raise ValueError(f"unknown precision {precision!r}")
    if name not in PRECISION_NAMES:
        raise ValueError(f"unknown precision {precision!r}; expected one "
                         f"of {PRECISION_NAMES} (or None)")
    return name


def _dtype_name(dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


def spec_key(spec: ConvSpec) -> str:
    """Readable, order-stable spec identity used in cache keys."""
    return (f"{spec.i_n}x{spec.i_h}x{spec.i_w}x{spec.i_c}"
            f"-k{spec.k_h}x{spec.k_w}x{spec.k_c}"
            f"-s{spec.s_h}x{spec.s_w}")


def plan_cache_key(spec: ConvSpec, dtype: str, backend: str) -> str:
    """The one cache-key format — ``ConvPlan.cache_key()`` and the
    cached policy's lookup both build it here, so they can never
    drift apart."""
    return f"{spec_key(spec)}|{dtype}|{backend}"


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """One fully-resolved convolution decision.  Frozen: a plan is a
    value — compare, hash, serialize, and replay it; never mutate it."""

    spec: ConvSpec
    dtype: str
    algorithm: str                         # resolved; never "auto"
    solution: str = "auto"                 # 'A'/'B' for mec, else 'auto'
    w_blk: Optional[int] = None            # Pallas output-column block
    precision: Optional[str] = None        # canonical Precision name
    partition: Optional[Tuple[str, ...]] = None
    partition_axes: Optional[Tuple[str, ...]] = None
    backend: str = "cpu"
    mode: str = "analytic"                 # policy that produced the plan

    def __post_init__(self):
        if self.algorithm not in _SINGLE_DEVICE_ALGOS:
            raise ValueError(f"plan algorithm {self.algorithm!r} is not a "
                             f"resolved algorithm {_SINGLE_DEVICE_ALGOS}")
        if self.solution not in SOLUTIONS:
            raise ValueError(f"unknown MEC solution {self.solution!r}")
        if self.precision is not None and \
                self.precision not in PRECISION_NAMES:
            raise ValueError(f"unknown precision {self.precision!r}")
        if (self.partition is None) != (self.partition_axes is None):
            raise ValueError("partition and partition_axes must be set "
                             "together")
        if self.partition is not None:
            from repro.parallel.conv import normalize_partition
            parts = normalize_partition(self.partition)
            object.__setattr__(self, "partition", parts)
            axes = tuple(self.partition_axes)
            if len(axes) != len(parts):
                raise ValueError(
                    f"partition {parts!r} needs {len(parts)} axis(es), "
                    f"got {axes!r}")
            object.__setattr__(self, "partition_axes", axes)

    # ------------------------------------------------------------- identity

    def cache_key(self) -> str:
        """spec + dtype + backend — what the plan cache indexes on."""
        return plan_cache_key(self.spec, self.dtype, self.backend)

    def precision_value(self):
        """The jax.lax.Precision the executor passes to the GEMMs."""
        if self.precision is None:
            return None
        import jax
        return getattr(jax.lax.Precision, self.precision)

    # ------------------------------------------------------------ execution

    def check_executable(self, spec: ConvSpec, dtype) -> None:
        """Raise unless this plan was made for exactly this call: the
        executor refuses to run a stale plan on drifted geometry — or
        on a different backend, where the recorded pick may be wildly
        wrong (e.g. a TPU Pallas plan interpreting on CPU)."""
        if spec != self.spec:
            raise ValueError(
                f"plan/call geometry mismatch: plan was made for "
                f"{self.spec}, call resolves to {spec}")
        got = _dtype_name(dtype)
        if got != self.dtype:
            raise ValueError(
                f"plan/call dtype mismatch: plan was made for "
                f"{self.dtype!r}, call carries {got!r}")
        import jax
        live = jax.default_backend()
        if live != self.backend:
            raise ValueError(
                f"plan/backend mismatch: plan was made for "
                f"{self.backend!r}, this process runs {live!r}; "
                f"re-plan with plan_conv2d(spec, backend={live!r})")

    # -------------------------------------------------------- serialization

    def to_dict(self) -> Dict:
        return {
            "plan_version": PLAN_VERSION,
            "spec": dataclasses.asdict(self.spec),
            "dtype": self.dtype,
            "algorithm": self.algorithm,
            "solution": self.solution,
            "w_blk": self.w_blk,
            "precision": self.precision,
            "partition": (None if self.partition is None
                          else list(self.partition)),
            "partition_axes": (None if self.partition_axes is None
                               else list(self.partition_axes)),
            "backend": self.backend,
            "mode": self.mode,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_dict(cls, doc: Dict) -> "ConvPlan":
        version = doc.get("plan_version")
        if version != PLAN_VERSION:
            raise ValueError(f"plan_version {version!r} is not "
                             f"{PLAN_VERSION}; regenerate the plan")
        return cls(
            spec=ConvSpec(**doc["spec"]),
            dtype=doc["dtype"],
            algorithm=doc["algorithm"],
            solution=doc.get("solution", "auto"),
            w_blk=doc.get("w_blk"),
            precision=doc.get("precision"),
            partition=(None if doc.get("partition") is None
                       else tuple(doc["partition"])),
            partition_axes=(None if doc.get("partition_axes") is None
                            else tuple(doc["partition_axes"])),
            backend=doc.get("backend", "cpu"),
            mode=doc.get("mode", "analytic"),
        )

    @classmethod
    def from_json(cls, text: str) -> "ConvPlan":
        return cls.from_dict(json.loads(text))

    # -------------------------------------------------------------- explain

    def explain(self) -> str:
        """Human-readable *why*: the paper's Eq. 2-4 memory overheads and
        flops for every eligible algorithm (winner marked), plus the
        predicted per-device communication bytes of the partition."""
        from repro.core import memory
        from repro.launch.costmodel import conv2d_algorithm_costs
        s = self.spec
        lines = [
            f"ConvPlan[{self.mode}] {spec_key(s)} dtype={self.dtype} "
            f"backend={self.backend}",
            f"  algorithm={self.algorithm} solution={self.solution} "
            f"w_blk={self.w_blk} precision={self.precision}",
            f"  out_shape={tuple(s.out_shape)}  "
            f"mec saving vs im2col (Eq. 4): {memory.mec_saving(s)} elems",
            "  candidate costs (Eq. 2-4 overhead elems / flops):",
        ]
        costs = conv2d_algorithm_costs(s)
        base = "direct" if self.algorithm == "mec_fused" else self.algorithm
        for alg in sorted(costs):
            mark = " <- plan" if alg == base else ""
            c = costs[alg]
            lines.append(f"    {alg:8s} overhead={c['overhead_elems']:.3e} "
                         f"flops={c['flops']:.3e}{mark}")
        if self.algorithm == "mec_fused":
            import numpy as np
            from repro.core.conv_api import (fused_input_grad_refusal,
                                             input_grad_spec)
            from repro.kernels.mec_conv import (fused_blocks,
                                                fused_weight_grad_refusal)
            from repro.kernels.ops import pick_w_blk
            lines.append("  (Pallas kernel: lowering stays in VMEM; "
                         "HBM overhead is the direct conv's)")
            w_blk = self.w_blk or pick_w_blk(s.o_w, s.k_c, _warn_env=False)
            fb = fused_blocks(s.i_n, s.i_h, s.i_w, s.i_c, s.k_h, s.k_w,
                              s.k_c, s.s_h, s.s_w, w_blk,
                              np.dtype(self.dtype).itemsize)
            lines.append(f"  mec_fused blocking: {fb.describe()}")
            why = fused_input_grad_refusal(s, self.dtype)
            lines.append(
                "  input gradient: the mec_fused kernel on the "
                f"transposed conv {spec_key(input_grad_spec(s))}"
                if why is None else
                f"  input gradient: XLA (_mec_input_grad), {why}")
            why = fused_weight_grad_refusal(s, self.dtype, w_blk)
            lines.append(
                "  weight gradient: the mec_fused_wgrad kernel on the "
                "forward's blocking" if why is None else
                f"  weight gradient: XLA (_mec_weight_grad), {why}")
        elif self.backend == "tpu":
            from repro.kernels.mec_conv import fused_refusal
            why = fused_refusal(s, self.dtype)
            if why:
                lines.append(f"  mec_fused not taken on tpu: {why}")
        if self.partition is None:
            lines.append("  partition: none (single device)")
        else:
            from repro.launch.costmodel import conv_partition_costs
            from repro.parallel.conv import partition_name
            import jax.numpy as jnp
            dtype_bytes = jnp.dtype(self.dtype).itemsize
            lines.append(f"  partition: {partition_name(self.partition)} "
                         f"over mesh axes {self.partition_axes}")
            try:
                n_dev = self._partition_sizes()
                entry = conv_partition_costs(
                    s, n_dev, dtype_bytes)[
                        self.partition if len(self.partition) > 1
                        else self.partition[0]]
                lines.append(
                    f"    predicted comm bytes/device: "
                    f"fwd={entry['comm_bytes_fwd_per_device']:.3e} "
                    f"bwd={entry['comm_bytes_bwd_per_device']:.3e} "
                    f"(halo {entry['halo_bytes_per_device']:.3e}); "
                    f"per-device L overhead "
                    f"{entry['per_device_overhead_elems']:.3e} elems")
            except (ValueError, KeyError):  # no live mesh to size axes
                lines.append("    (no live mesh: per-device comm bytes "
                             "need the axis sizes)")
        return "\n".join(lines)

    def _partition_sizes(self) -> Union[int, Tuple[int, ...]]:
        """Axis sizes of the plan's partition on the *installed* mesh."""
        from repro.parallel.axes import current_rules
        rules = current_rules()
        if rules is None:
            raise ValueError("no installed mesh")
        sizes = tuple(int(rules.mesh.shape[a]) for a in self.partition_axes)
        return sizes[0] if len(sizes) == 1 else sizes


# ---------------------------------------------------------------------------
# planning policies
# ---------------------------------------------------------------------------

def _resolve_partition(spec: ConvSpec, partition, partition_axis,
                       dtype_bytes: int):
    """(components, axes) or (None, None), mirroring the executor's
    rules-aware routing (DESIGN.md §6) — but resolved once, at plan
    time, via the same candidate enumeration the distributed layer
    uses."""
    from repro.parallel.axes import current_rules
    rules = current_rules()
    if partition == "none":
        return None, None
    if rules is None:
        if partition not in (None, "auto"):
            raise ValueError(f"partition {partition!r} needs an installed "
                             "mesh (parallel.axes.use_rules)")
        return None, None
    mesh = rules.mesh
    from repro.launch.costmodel import pick_conv_partition
    from repro.parallel.conv import (enumerate_partition_candidates,
                                     normalize_partition, partition_viable)
    candidates = enumerate_partition_candidates(mesh, rules, partition_axis)
    if partition is None or partition == "auto":
        picked = pick_conv_partition(
            spec, {p: n for p, (_, n) in candidates.items()}, dtype_bytes)
        if picked is None:
            return None, None
        return normalize_partition(picked), candidates[picked][0]
    parts = normalize_partition(partition)
    key = parts if len(parts) > 1 else parts[0]
    if key not in candidates:
        raise ValueError(f"partition {partition!r} resolves no mesh axis "
                         f"on {mesh.axis_names}; pass partition_axis=")
    axes, n_dev = candidates[key]
    if not partition_viable(spec, parts, n_dev):
        raise ValueError(f"partition {partition!r} cannot split "
                         f"{spec} over {n_dev} device(s)")
    return parts, axes


def _hit_satisfies(hit: ConvPlan, precision_name: Optional[str],
                   partition, partition_axis) -> bool:
    """Would serving this cached plan honour the caller's request?

    The cache key is spec|dtype|backend only, so precision, the
    partition intent (components AND explicit axes), and the current
    accumulator-budget derivation must be checked against the hit — a
    plan resolved without HIGHEST (or without a partition, or under a
    different REPRO_MEC_ACC_BYTES / device budget) must never silently
    answer a call that asked otherwise.
    """
    if hit.precision != precision_name:
        return False
    if hit.w_blk != _pallas_w_blk(hit.spec, hit.algorithm):
        return False              # env/device budget changed since tuning
    if partition_axis is not None and hit.partition_axes is not None:
        axes = (partition_axis,) if isinstance(partition_axis, str) \
            else tuple(partition_axis)
        if hit.partition_axes != axes:
            return False
    if partition == "none":
        return hit.partition is None
    if partition not in (None, "auto"):
        from repro.parallel.conv import normalize_partition
        return hit.partition == normalize_partition(partition)
    # Rules-aware request: the hit must make sense on the *currently*
    # installed mesh — a partitioned plan recorded under other rules,
    # or a partition-free plan now that a mesh is up, is recomputed
    # (if the recompute agrees, the caller below skips the re-store).
    from repro.parallel.axes import current_rules
    rules = current_rules()
    if rules is None:
        return hit.partition is None
    return hit.partition is not None and all(
        a in rules.mesh.axis_names for a in hit.partition_axes)


def _pallas_w_blk(spec: ConvSpec, algorithm: str) -> Optional[int]:
    if algorithm != "mec_fused":
        return None
    from repro.kernels.ops import pick_w_blk
    # The planner is the supported home for the accumulator budget; the
    # env override applies here without the deprecation warning.
    return pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)


def _kernel_refusal(plan: ConvPlan) -> Optional[str]:
    """Why the Pallas kernel cannot run ``plan``'s own block, or None
    (also None for a plan that runs no kernel)."""
    if plan.algorithm != "mec_fused":
        return None
    from repro.kernels.mec_conv import fused_refusal
    return fused_refusal(plan.spec, plan.dtype, plan.w_blk)


def _assert_kernel_runs(plan: ConvPlan) -> None:
    """Never return (or let the cached policy store) a plan whose block
    the kernel's rule refuses — raising here beats faulting at execute."""
    why = _kernel_refusal(plan)
    if why:
        raise ValueError(f"mec_fused cannot run the plan for "
                         f"{spec_key(plan.spec)}: {why}")


# A measured flip needs to clear this margin over the analytic pick —
# sub-5% deltas are timer jitter at bench iteration counts, and a pick
# that flips run-to-run on noise is worse than a stable analytic one.
MEASURED_NOISE_MARGIN = 0.05


def pick_measured(times: Dict[str, float], analytic: str,
                  margin: float = MEASURED_NOISE_MARGIN,
                  spreads: Optional[Dict[str, float]] = None) -> str:
    """The measured policy's decision rule (shared with the autotune
    bench suite): fastest candidate, except the analytic pick is kept
    whenever it is within the noise margin of the fastest — a flip must
    have timing evidence beyond run-to-run noise.

    ``spreads`` (algorithm -> ``us_rel_spread`` from the same timed
    iterations, DESIGN.md §10) widens the margin to the observed jitter
    of the two candidates being compared: the 5%% convention is the
    *floor*, and on a host whose medians wobble 30%% run-to-run a 30%%
    "win" is not evidence.  Without spread data the floor applies
    unchanged (pre-v2 reports, calibration cell medians)."""
    best = min(times, key=lambda a: times[a])
    if analytic not in times:
        return best
    eff = margin
    for alg in (analytic, best):
        sp = (spreads or {}).get(alg)
        if sp is not None:
            eff = max(eff, min(float(sp), 1.0))
    if times[analytic] <= times[best] * (1 + eff):
        return analytic
    return best


def eligible_candidates(spec: ConvSpec) -> Tuple[str, ...]:
    """conv2d algorithm names the measured policy may time on a spec:
    every single-device algorithm, winograd only on 3x3 stride 1."""
    return tuple(alg for alg in _SINGLE_DEVICE_ALGOS
                 if alg != "winograd"
                 or (spec.k_h, spec.k_w, spec.s_h, spec.s_w) == (3, 3, 1, 1))


@dataclasses.dataclass
class MeasuredCandidates:
    """Everything one measured sweep learned: per-candidate steady-state
    timings + full iteration stats, and — the part that used to vanish
    silently — every candidate that could not be timed, with the reason
    (same surfacing stance as ``PlanCache.io_errors``)."""

    times: Dict[str, float]            # alg -> us_median (timeable only)
    stats: Dict[str, Dict]             # alg -> full time_compiled stats
    skipped: Dict[str, str]            # alg -> why it was not timed


def _time_trial(trial: ConvPlan, inp, ker, iters: int, warmup: int,
                interpret: Optional[bool]) -> Dict:
    """AOT-compile one trial plan and run the harness timing protocol."""
    import jax
    from repro.bench.harness import time_compiled
    from repro.core.conv_api import conv2d
    spec = trial.spec
    fn = jax.jit(lambda i, k, _p=trial: conv2d(
        i, k, stride=(spec.s_h, spec.s_w), plan=_p, interpret=interpret))
    compiled = fn.lower(inp, ker).compile()
    return time_compiled(lambda: compiled(inp, ker),
                         iters=iters, warmup=warmup)


def _record_time_trials(spec: ConvSpec, dtype: str, trials) -> None:
    """Fold measured trials into the calibration store (DESIGN.md §10).

    Strictly best-effort: the store already degrades silently on disk
    trouble, and a calibration failure must never fail a measurement.
    """
    try:
        from repro.plan.calibrate import CalibrationStore
        store = CalibrationStore()
        for alg, solution, w_blk, us in trials:
            store.add_time(spec, dtype, alg, us,
                           solution=solution, w_blk=w_blk)
        store.flush()
    except Exception:
        pass


def measure_candidates_detailed(
        spec: ConvSpec, dtype: str = "float32",
        candidates: Optional[Sequence[str]] = None,
        iters: int = 3, warmup: int = 1,
        interpret: Optional[bool] = None,
        precision=None, record: bool = True) -> MeasuredCandidates:
    """Steady-state ``us_per_call`` per candidate algorithm, via the
    bench harness protocol (AOT compile -> warmup -> median of timed
    calls).  This IS the measured policy's inner loop; the autotune
    bench suite reuses it so its numbers are the planner's numbers.

    Each candidate is timed *through a ConvPlan executor call* — the
    measurement exercises exactly what the winning plan will later run
    (resolved solution, planner-derived w_blk, named precision), and
    the planner's w_blk derivation stays on the warning-free path.

    Candidates that cannot be timed — the kernel's geometry rule
    refuses the trial plan, or compilation/execution raises — are never
    dropped silently: each lands in ``.skipped`` with its reason (and a
    warning), so the autotune report can show exactly what the race was
    missing.  With ``record=True`` every successful trial is added to
    the calibration store.
    """
    import warnings

    import jax
    from repro.bench.harness import make_arrays
    candidates = tuple(candidates) if candidates else \
        eligible_candidates(spec)
    dtype = _dtype_name(dtype)
    precision_name = _precision_name(precision)
    inp, ker = make_arrays(spec, dtype)
    out = MeasuredCandidates(times={}, stats={}, skipped={})
    recorded = []
    for alg in candidates:
        trial = ConvPlan(
            spec=spec, dtype=dtype, algorithm=alg,
            solution=pick_solution(spec) if alg == "mec" else "auto",
            w_blk=_pallas_w_blk(spec, alg), precision=precision_name,
            backend=jax.default_backend())
        why = _kernel_refusal(trial)
        if why:
            # A block the kernel's rule refuses would fault or overrun
            # VMEM on a real TPU — never time it, never let it win.
            out.skipped[alg] = why
            warnings.warn(f"measured planning skips {alg}: {why}")
            continue
        try:
            timing = _time_trial(trial, inp, ker, iters, warmup, interpret)
        except Exception as e:
            # A candidate that fails to compile or run must not crash
            # the race — but it must be *counted*, not silently absent.
            reason = f"{type(e).__name__}: {e}"[:300]
            out.skipped[alg] = reason
            warnings.warn(f"measured planning skips {alg}: {reason}")
            continue
        out.times[alg] = timing["us_median"]
        out.stats[alg] = dict(timing, solution=trial.solution,
                              w_blk=trial.w_blk)
        recorded.append((alg, trial.solution, trial.w_blk,
                         timing["us_median"]))
    if record and recorded:
        _record_time_trials(spec, dtype, recorded)
    return out


def measure_candidates(spec: ConvSpec, dtype: str = "float32",
                       candidates: Optional[Sequence[str]] = None,
                       iters: int = 3, warmup: int = 1,
                       interpret: Optional[bool] = None,
                       precision=None,
                       record: bool = True) -> Dict[str, float]:
    """``measure_candidates_detailed`` reduced to {algorithm: us_median}
    (the historical return shape)."""
    return measure_candidates_detailed(
        spec, dtype, candidates, iters=iters, warmup=warmup,
        interpret=interpret, precision=precision, record=record).times


def _stage2_trials(spec: ConvSpec, dtype: str, algorithm: str,
                   precision_name: Optional[str], backend: str):
    """The winner's knob grid for measured stage 2 (DESIGN.md §10).

    mec: both §3.2 solutions (A = h-direction Solution 1, B =
    w-direction Solution 2) — ``pick_solution``'s T=100 rule is exactly
    the kind of paper constant the measurements should audit.
    ``mec_fused``: the planner's ``pick_w_blk`` default plus half and
    double (clamped to [8, o_w]), each one the kernel's rule admits.  Other
    algorithms have no plan-level knob.  Returns (knob_name, {label:
    trial plan}) or (None, {}).
    """
    if algorithm == "mec":
        plans = {sol: ConvPlan(spec=spec, dtype=dtype, algorithm="mec",
                               solution=sol, precision=precision_name,
                               backend=backend)
                 for sol in ("A", "B")}
        return "solution", plans
    if algorithm == "mec_fused":
        default = _pallas_w_blk(spec, algorithm)
        grid = {default, max(8, default // 2), min(spec.o_w, default * 2)}
        plans = {}
        for blk in sorted(b for b in grid if 1 <= b <= spec.o_w):
            trial = ConvPlan(spec=spec, dtype=dtype, algorithm=algorithm,
                             w_blk=blk, precision=precision_name,
                             backend=backend)
            if not _kernel_refusal(trial):
                plans[str(blk)] = trial
        return "w_blk", plans
    return None, {}


def tune_measured(spec: ConvSpec, dtype: str = "float32",
                  backend: Optional[str] = None, precision=None,
                  candidates: Optional[Sequence[str]] = None,
                  iters: int = 3, warmup: int = 1,
                  interpret: Optional[bool] = None,
                  record: bool = True,
                  calibration="ambient") -> Tuple[ConvPlan, Dict]:
    """The full measured policy: stage-1 algorithm race, then a stage-2
    grid over the winner's knob (MEC solution / Pallas ``w_blk``), both
    through ``pick_measured``'s noise margin so a non-default knob needs
    evidence beyond jitter.  Every trial lands in the calibration store
    when ``record=True``.

    Returns ``(plan, detail)`` where ``plan`` is the partition-free
    measured :class:`ConvPlan` and ``detail`` is the JSON-able evidence
    record the autotune bench suite embeds per cell:
    ``{analytic_algorithm, candidate_us, candidate_stats, skipped,
    tuning}`` (``tuning`` is None when the winner has no knob).
    """
    import jax
    backend = backend or jax.default_backend()
    dtype = _dtype_name(dtype)
    precision_name = _precision_name(precision)
    mc = measure_candidates_detailed(
        spec, dtype, candidates, iters=iters, warmup=warmup,
        interpret=interpret, precision=precision_name, record=record)
    from repro.launch.costmodel import pick_conv2d_algorithm
    analytic = pick_conv2d_algorithm(spec, backend,
                                     calibration=calibration, dtype=dtype)
    if not mc.times:
        raise ValueError(
            f"measured planning has no timeable candidate for "
            f"{spec_key(spec)}: skipped={mc.skipped}")
    algorithm = pick_measured(mc.times, analytic, spreads={
        a: s.get("us_rel_spread") for a, s in mc.stats.items()})
    solution = pick_solution(spec) if algorithm == "mec" else "auto"
    w_blk = _pallas_w_blk(spec, algorithm)

    tuning = None
    knob, plans = _stage2_trials(spec, dtype, algorithm,
                                 precision_name, backend)
    if knob is not None and plans:
        from repro.bench.harness import make_arrays
        inp, ker = make_arrays(spec, dtype)
        default_label = solution if knob == "solution" else str(w_blk)
        trial_times: Dict[str, float] = {}
        trial_stats: Dict[str, Dict] = {}
        recorded = []
        for label, trial in plans.items():
            try:
                timing = _time_trial(trial, inp, ker, iters, warmup,
                                     interpret)
            except Exception as e:
                mc.skipped[f"{algorithm}[{knob}={label}]"] = \
                    f"{type(e).__name__}: {e}"[:300]
                continue
            trial_times[label] = timing["us_median"]
            trial_stats[label] = dict(timing, solution=trial.solution,
                                      w_blk=trial.w_blk)
            recorded.append((algorithm, trial.solution, trial.w_blk,
                             timing["us_median"]))
        if record and recorded:
            _record_time_trials(spec, dtype, recorded)
        if trial_times:
            # The analytic default keeps its noise-margin advantage; if
            # it could not be timed the fastest trial wins outright.
            # Deliberately the plain 5% floor (no spread widening):
            # both trials run the same algorithm, so their jitter is
            # common-mode, and the default here is a paper heuristic
            # under audit (pick_solution's T=100, pick_w_blk) — a lower
            # bar than overriding the calibrated costmodel.
            picked = pick_measured(trial_times, default_label) \
                if default_label in trial_times \
                else min(trial_times, key=lambda k: trial_times[k])
            if knob == "solution":
                solution = picked
            else:
                w_blk = int(picked)
            tuning = {"knob": knob, "algorithm": algorithm,
                      "default": default_label, "picked": picked,
                      "trials": trial_stats}

    plan = ConvPlan(spec=spec, dtype=dtype, algorithm=algorithm,
                    solution=solution, w_blk=w_blk,
                    precision=precision_name, backend=backend,
                    mode="measured")
    _assert_kernel_runs(plan)
    detail = {"analytic_algorithm": analytic,
              "candidate_us": dict(mc.times),
              "candidate_stats": mc.stats,
              "skipped": mc.skipped,
              "tuning": tuning}
    return plan, detail


def plan_conv2d(spec: ConvSpec, *, dtype="float32", mode: str = "analytic",
                backend: Optional[str] = None, precision=None,
                partition=None, partition_axis=None,
                candidates: Optional[Sequence[str]] = None,
                iters: int = 3, warmup: int = 1,
                interpret: Optional[bool] = None,
                cache=None, calibration="ambient") -> ConvPlan:
    """Produce the :class:`ConvPlan` for one post-padding ``spec``.

    mode: ``"analytic"`` (costmodel pick — today's ``auto`` rule),
    ``"measured"`` (time every candidate through the bench harness,
    keep the winner, then tune its knob — see :func:`tune_measured`),
    or ``"cached"`` (process LRU -> on-disk JSON -> analytic on miss;
    see ``repro.plan.cache``).

    calibration: the fitted-costmodel handle the analytic pick consults
    (DESIGN.md §10) — ``"ambient"`` (default: $REPRO_CALIBRATION or the
    fingerprinted store beside the plan cache, silently absent when
    unfitted), ``None`` (force the paper's uncalibrated constants), or
    an explicit ``repro.plan.calibrate.Calibration``.  Cached plans
    record whatever the calibration said at *plan* time; like any
    costmodel change, a new calibration takes effect on cache misses
    and environment-fingerprint rollover, not retroactively.

    partition follows the executor's rules-aware convention: ``None``
    consults the installed ``parallel.axes`` rules (no mesh -> no
    partition), ``"auto"``/explicit modes resolve against the mesh at
    *plan* time — the plan records both the components and the mesh
    axes, so executing it never re-enumerates.
    """
    import jax
    if mode not in PLAN_MODES:
        raise ValueError(f"unknown plan mode {mode!r}; expected one of "
                         f"{PLAN_MODES}")
    spec.validate()
    dtype = _dtype_name(dtype)
    backend = backend or jax.default_backend()
    precision_name = _precision_name(precision)

    if mode == "cached":
        from repro.plan.cache import global_plan_cache
        cache = cache if cache is not None else global_plan_cache()
        key = plan_cache_key(spec, dtype, backend)
        hit = cache.get(key)
        if hit is not None and _hit_satisfies(hit, precision_name,
                                              partition, partition_axis):
            return hit
        # Miss — or a hit whose precision/partition decision does not
        # satisfy THIS request (the key is only spec|dtype|backend, so
        # a conflicting hit must never be served silently): recompute
        # and overwrite — most recent decision wins.
        plan = plan_conv2d(spec, dtype=dtype, mode="analytic",
                           backend=backend, precision=precision_name,
                           partition=partition,
                           partition_axis=partition_axis,
                           calibration=calibration)
        if plan != hit:               # an agreeing recompute skips the
            cache.put(key, plan)      # disk rewrite entirely
        return plan

    import jax.numpy as jnp
    parts, axes = _resolve_partition(spec, partition, partition_axis,
                                     jnp.dtype(dtype).itemsize)

    if mode == "measured":
        base, _detail = tune_measured(
            spec, dtype, backend=backend, precision=precision_name,
            candidates=candidates, iters=iters, warmup=warmup,
            interpret=interpret, calibration=calibration)
        # tune_measured already ran the kernel's rule; replaying it
        # through replace() only re-runs __post_init__ validation.
        plan = dataclasses.replace(base, partition=parts,
                                   partition_axes=axes)
        if plan.partition:
            # Same stance as the kernel's rule: never return a partitioned
            # plan whose compiled collectives break the costmodel
            # contract (skips silently when no mesh is installed).
            from repro.analysis.shardcheck import assert_plan_contract
            assert_plan_contract(plan)
        # Every returned plan also passes the static numeric contract
        # (DESIGN.md §8.5): accumulation widths, cast structure,
        # in-kernel Pallas accumulators.  Trace-only and memoized, so
        # planning stays cheap; the measured error-budget probe runs in
        # the numcheck suite, not here.
        from repro.analysis.numcheck import assert_plan_numerics
        assert_plan_numerics(plan)
        return plan

    from repro.launch.costmodel import pick_conv2d_algorithm
    algorithm = pick_conv2d_algorithm(spec, backend,
                                      calibration=calibration, dtype=dtype)
    solution = pick_solution(spec) if algorithm == "mec" else "auto"
    plan = ConvPlan(spec=spec, dtype=dtype, algorithm=algorithm,
                    solution=solution,
                    w_blk=_pallas_w_blk(spec, algorithm),
                    precision=precision_name,
                    partition=parts, partition_axes=axes,
                    backend=backend, mode=mode)
    _assert_kernel_runs(plan)
    if plan.partition:
        # Partitioned plans additionally pass the collective contract
        # (halo/psum bytes vs. the costmodel, no accidental resharding;
        # DESIGN.md §8).  Skips silently when no mesh is installed.
        from repro.analysis.shardcheck import assert_plan_contract
        assert_plan_contract(plan)
    # Every returned plan passes the static numeric contract (DESIGN.md
    # §8.5) for its resolved backend x dtype — accumulation widths, cast
    # structure, in-kernel Pallas accumulators.  Trace-only + memoized.
    from repro.analysis.numcheck import assert_plan_numerics
    assert_plan_numerics(plan)
    return plan


def resolve_cached_plan(spec: ConvSpec, dtype="float32",
                        backend: Optional[str] = None) -> ConvPlan:
    """What ``conv2d(algorithm="auto")`` calls: the cached-policy plan
    for (spec, dtype, backend), partition-free (the executor's partition
    routing already happened upstream)."""
    return plan_conv2d(spec, dtype=dtype, mode="cached", backend=backend,
                       partition="none")
