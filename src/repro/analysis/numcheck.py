"""Numerics contract checker: dtype-flow + accumulation audit
(DESIGN.md §8.5).

MEC's Table 2 claim is *result-preserving* memory/speed trades: im2col,
FFT, Winograd, the compact-L GEMMs and the Pallas kernels must all
compute the same convolution.  memaudit verifies the memory leg and
shardcheck the distributed leg; this module closes the numerics leg.
For one backend x dtype it extracts the computation's **numeric
signature** from the jaxpr — every ``dot_general`` /
``conv_general_dilated``'s operand dtypes, ``preferred_element_type``
and ``precision``, and every ``convert_element_type`` edge classified
as widen / narrow / complexify — recursing into Pallas kernels,
``custom_vjp`` branches and ``shard_map`` bodies, and checks it against
the backend's declared :class:`repro.core.numerics.NumericContract`:

* **disallowed-dtype** — a float/complex dtype outside the contract's
  allowed set ({input dtype, f32} + complex64 for FFT); catches both a
  stray mid-chain downcast (an ``astype(bf16)`` in an f32 program) and
  any f64/complex128 leak.
* **accumulation** — a contraction with sub-f32 operands whose output
  is also sub-f32 accumulated below the contract width (a dropped
  ``preferred_element_type``, the PR 4/PR 5 bug class).
* **pallas-accum** — the in-kernel variant, checked symbolically on the
  kernel jaxpr: Pallas dots must
  *carry* ``preferred_element_type=f32`` for sub-f32 inputs (MXU
  accumulation width is set per dot, not recovered by a later cast).
* **narrow-widen** — a value narrowed then widened again (silent
  precision loss); taint propagates through structural ops only
  (reshape/transpose/slice/...), so a forward output legitimately
  consumed by arithmetic in the backward pass never false-positives.
* **output-cast-count** — the forward program narrows back to the
  input dtype through *exactly* ``fwd_output_narrows`` cast edges (one
  everywhere today; two would be double rounding).
* **error-budget** — a measured probe: fwd + ``value_and_grad`` of a
  quadratic loss vs an f64 numpy reference on fixed seeds, gated by the
  per-algorithm tolerances the contract declares (never the test file).

The precision-flow pass that shipped inside shardcheck (PR 9) now lives
here — :func:`jaxpr_dot_precisions`, :func:`hlo_precision_tally`,
:func:`precision_flow_findings` — and shardcheck re-imports them, so
the partitioned contract keeps working unchanged.

Wired at the same three layers as shardcheck: ``plan_conv2d`` asserts
the static contract (:func:`assert_plan_numerics`, memoized) before
returning any plan; bench cells record a reduced ``numcheck`` field
(:func:`cell_numcheck`) gated by ``bench.check``; ``python -m
repro.analysis --suite numcheck`` sweeps every backend x {f32, bf16,
f16} x {fwd, grad} into the CI-gated ``BENCH_numcheck.json``.

Layering: never imports ``repro.plan`` (plans are duck-typed); jax is
imported lazily so contract data is usable before backend init.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.numerics import (CONTRACT_DTYPES, NumericContract,
                                 contract_for, float_bits)

DIRECTIONS = ("fwd", "grad")

#: executor backends the CLI suite sweeps (ALGORITHMS minus "auto").
NUMCHECK_ALGORITHMS = ("direct", "im2col", "fft", "winograd", "mec",
                       "mec_fused")
NUMCHECK_DTYPES = CONTRACT_DTYPES


def probe_spec():
    """The fixed geometry every contract budget is measured on: 3x3
    stride-1 (so winograd participates), small enough that the 18-cell
    f64 sweep stays in CI budget.  Matches shardcheck's probe spec."""
    from repro.core.convspec import ConvSpec
    return ConvSpec(2, 16, 16, 3, 3, 3, 4, 1, 1)

_DOT_PRIMS = ("dot_general", "conv_general_dilated")


@contextlib.contextmanager
def _quiet_trace():
    """The checker's internal traces go through the kwargs dispatch path
    (no ConvPlan), which may cross deprecation shims; those warnings are
    about the *caller's* API choice, not this audit — keep them out of
    planners and bench runs."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        yield

# Data-movement primitives that preserve a value's rounding history —
# the only edges narrow-widen taint flows through.  Arithmetic consumes
# the value (a terminal narrow followed by downstream compute is the
# normal sub-f32 output path, not double rounding).
_STRUCTURAL_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "rev", "concatenate", "pad",
    "gather", "copy",
})

_COMPLEX_BITS = {"complex64": 64, "complex128": 128}

_HLO_DOT_RE = re.compile(r"=\s*\S+\s+(?:dot|convolution)\(")
# `%x = bf16[2,14,14,4]{3,2,1,0} convert(f32[2,14,14,4]{3,2,1,0} %y)`
_HLO_CONVERT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[[^\]]*\](?:\{[^}]*\})?\s*convert\(([a-z0-9]+)\[")


class NumCheckError(AssertionError):
    """A backend's lowering broke its declared numeric contract."""


@dataclasses.dataclass(frozen=True)
class ContractViolation:
    rule: str          # disallowed-dtype | f64-leak | accumulation |
    #                    pallas-accum | narrow-widen | output-cast-count |
    #                    error-budget | precision-flow | (shardcheck's
    #                    collective rules reuse this class)
    direction: str     # 'fwd' | 'grad' | 'static'
    message: str

    def render(self) -> str:
        return f"[{self.rule}] {self.direction}: {self.message}"


# ---------------------------------------------------------------------------
# jaxpr walking (shared with shardcheck)
# ---------------------------------------------------------------------------

def _subjaxprs(value):
    """Jaxprs reachable from one eqn param (ClosedJaxpr, raw Jaxpr, or
    containers of either — pallas_call kernels, custom_vjp branches,
    shard_map bodies all hide theirs differently)."""
    if hasattr(value, "eqns"):                       # raw Jaxpr
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr                            # ClosedJaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _subjaxprs(v)


def _iter_jaxprs(closed):
    """Every (sub-)jaxpr reachable from ``closed``, each yielded once."""
    stack = [closed.jaxpr if hasattr(closed, "jaxpr") else closed]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                stack.extend(_subjaxprs(v))


def _walk_eqns(closed):
    """``(eqn, in_pallas)`` for every eqn reachable through nested
    sub-jaxprs; ``in_pallas`` is True inside a ``pallas_call`` kernel
    body (where the in-kernel accumulator audit applies)."""
    stack = [(closed.jaxpr if hasattr(closed, "jaxpr") else closed, False)]
    seen = set()
    while stack:
        j, in_pallas = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn, in_pallas
            child = in_pallas or eqn.primitive.name == "pallas_call"
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    stack.append((sub, child))


def jaxpr_dot_precisions(closed) -> List[Tuple[str, object]]:
    """``(primitive_name, precision_param)`` for every dot/convolution
    eqn reachable through nested sub-jaxprs."""
    out: List[Tuple[str, object]] = []
    for j in _iter_jaxprs(closed):
        for eqn in j.eqns:
            if eqn.primitive.name in _DOT_PRIMS:
                out.append((eqn.primitive.name,
                            eqn.params.get("precision")))
    return out


def _precision_matches(param, declared: str) -> bool:
    import jax
    want = getattr(jax.lax.Precision, declared)
    if param is None:
        return False
    vals = param if isinstance(param, tuple) else (param,)
    return all(p == want for p in vals)


def hlo_precision_tally(hlo_text: str,
                        declared: Optional[str]) -> Dict[str, int]:
    """dot/convolution ops in the (optimized) HLO, and how many lack
    the declared ``operand_precision`` marker.  With no declared
    precision nothing is required (XLA's default annotation is fine)."""
    dots = 0
    unannotated = 0
    marker = None if declared is None else \
        "operand_precision={" + declared.lower()
    for line in hlo_text.splitlines():
        if not _HLO_DOT_RE.search(line):
            continue
        dots += 1
        if marker is not None and marker not in line:
            unannotated += 1
    return {"dots": dots, "unannotated": unannotated}


def precision_flow_findings(closed_jaxprs: Sequence,
                            hlo_texts: Sequence[str],
                            declared: Optional[str]
                            ) -> Tuple[Dict, List[ContractViolation]]:
    """The precision-flow pass over one cell's lowerings.

    ``declared`` is the plan's canonical precision name ('HIGHEST' /
    'HIGH' / 'DEFAULT') or None (nothing declared — trivially clean).
    The jaxpr walk is the primary evidence (it sees inside Pallas
    kernels and custom-VJP branches, which HLO fusions can hide); the
    HLO scan is the backstop that the annotation *survived* lowering.
    """
    tally = {"declared": declared, "dot_ops": 0, "unannotated_dot_ops": 0,
             "hlo_dots": 0, "hlo_unannotated": 0}
    violations: List[ContractViolation] = []
    for closed in closed_jaxprs:
        for name, param in jaxpr_dot_precisions(closed):
            tally["dot_ops"] += 1
            if declared not in (None, "DEFAULT") and \
                    not _precision_matches(param, declared):
                tally["unannotated_dot_ops"] += 1
    for text in hlo_texts:
        t = hlo_precision_tally(
            text, None if declared in (None, "DEFAULT") else declared)
        tally["hlo_dots"] += t["dots"]
        tally["hlo_unannotated"] += t["unannotated"]
    if tally["unannotated_dot_ops"]:
        violations.append(ContractViolation(
            "precision-flow", "static",
            f"{tally['unannotated_dot_ops']}/{tally['dot_ops']} "
            f"dot/convolution op(s) in the jaxpr lack the declared "
            f"precision={declared} — a kwargs path dropped precision= "
            f"before the GEMM (the PR 4/5 silent-downcast bug class)"))
    if tally["hlo_unannotated"]:
        violations.append(ContractViolation(
            "precision-flow", "static",
            f"{tally['hlo_unannotated']}/{tally['hlo_dots']} "
            f"dot/convolution op(s) in the optimized HLO lack "
            f"operand_precision={{{str(declared).lower()},...}} — the "
            f"declared precision did not survive lowering"))
    return tally, violations


# ---------------------------------------------------------------------------
# numeric signature
# ---------------------------------------------------------------------------

def _is_complex(name: str) -> bool:
    return str(name) in _COMPLEX_BITS


def _is_inexact(name: str) -> bool:
    return float_bits(name) is not None or _is_complex(name)


def cast_kind(src: str, dst: str) -> str:
    """Classify one convert edge: narrow / widen / reformat (same-width
    float, e.g. bf16<->f16) / complexify / realify / complex-narrow /
    complex-widen / other (integer/bool)."""
    src, dst = str(src), str(dst)
    sb, db = float_bits(src), float_bits(dst)
    if sb is not None and db is not None:
        if db < sb:
            return "narrow"
        if db > sb:
            return "widen"
        return "same" if src == dst else "reformat"
    sc, dc = _is_complex(src), _is_complex(dst)
    if dc and not sc:
        return "complexify"
    if sc and not dc:
        return "realify"
    if sc and dc:
        s, d = _COMPLEX_BITS[src], _COMPLEX_BITS[dst]
        return "complex-narrow" if d < s else \
            "complex-widen" if d > s else "same"
    return "other"


def _dtype_name(value) -> Optional[str]:
    if value is None:
        return None
    import numpy as np
    try:
        return str(np.dtype(value))
    except TypeError:
        return str(value)


def extract_signature(closed) -> Dict:
    """The numeric signature of one traced program: every contraction
    (operand dtypes, accumulation dtype, precision, Pallas context) and
    every cast edge, classified."""
    dots: List[Dict] = []
    casts: List[Dict] = []
    for eqn, in_pallas in _walk_eqns(closed):
        name = eqn.primitive.name
        if name in _DOT_PRIMS:
            operands = [str(v.aval.dtype) for v in eqn.invars
                        if hasattr(v.aval, "dtype")]
            dots.append({
                "op": name,
                "operands": operands,
                "out": str(eqn.outvars[0].aval.dtype),
                "preferred_element_type":
                    _dtype_name(eqn.params.get("preferred_element_type")),
                "precision": eqn.params.get("precision"),
                "pallas": in_pallas,
            })
        elif name == "convert_element_type":
            src = str(eqn.invars[0].aval.dtype)
            dst = str(eqn.outvars[0].aval.dtype)
            casts.append({"op": name, "src": src, "dst": dst,
                          "kind": cast_kind(src, dst), "pallas": in_pallas})
    return {"dots": dots, "casts": casts}


def _render_dot(d: Dict) -> str:
    return (f"{d['op']}({' x '.join(d['operands'])} -> {d['out']}"
            + (", in-kernel" if d["pallas"] else "") + ")")


def signature_findings(sig: Dict, contract: NumericContract,
                       direction: str,
                       input_dtype: str) -> List[ContractViolation]:
    """Static detectors over one direction's numeric signature."""
    out: List[ContractViolation] = []
    allowed = set(contract.allowed_dtypes(input_dtype))
    accum_bits = float_bits(contract.accum_dtype) or 32
    flagged = set()

    def check_dtype(name: str, where: str):
        if name in allowed or not _is_inexact(name):
            return
        key = (where, name)
        if key in flagged:
            return
        flagged.add(key)
        if name in ("float64", "complex128") and not contract.allow_f64:
            out.append(ContractViolation(
                "f64-leak", direction,
                f"{where} touches {name} — the contract bans f64 "
                f"everywhere (an unintended promotion, not extra "
                f"accuracy the backend claims)"))
        else:
            out.append(ContractViolation(
                "disallowed-dtype", direction,
                f"{where} touches {name}; a {input_dtype} "
                f"{contract.algorithm} program may only use "
                f"{sorted(allowed)} — a stray mid-chain cast "
                f"silently re-rounds the value"))

    for d in sig["dots"]:
        where = _render_dot(d)
        for o in d["operands"] + [d["out"]]:
            check_dtype(o, where)
        sub = [o for o in d["operands"]
               if (float_bits(o) or 99) < accum_bits]
        out_bits = float_bits(d["out"])
        if sub and out_bits is not None and out_bits < accum_bits:
            out.append(ContractViolation(
                "accumulation", direction,
                f"{where} accumulates below {contract.accum_dtype}: "
                f"sub-{contract.accum_dtype} operands must carry "
                f"preferred_element_type={contract.accum_dtype} "
                f"(got {d['preferred_element_type']})"))
        if d["pallas"] and sub:
            p = d["preferred_element_type"]
            if p is None or (float_bits(p) or 0) < accum_bits:
                out.append(ContractViolation(
                    "pallas-accum", direction,
                    f"in-kernel {d['op']}"
                    f"({' x '.join(d['operands'])}) must carry "
                    f"preferred_element_type={contract.accum_dtype} for "
                    f"sub-f32 inputs — MXU accumulation width is set "
                    f"per dot, a later cast cannot recover it "
                    f"(got {p})"))
    for c in sig["casts"]:
        where = f"{c['op']}({c['src']} -> {c['dst']})"
        check_dtype(c["src"], where)
        check_dtype(c["dst"], where)
    in_bits = float_bits(input_dtype)
    if direction == "fwd" and in_bits is not None and in_bits < accum_bits:
        narrows = [c for c in sig["casts"]
                   if c["kind"] == "narrow" and c["dst"] == input_dtype]
        if len(narrows) != contract.fwd_output_narrows:
            srcs = ", ".join(f"{c['src']}->{c['dst']}" for c in narrows) \
                or "none"
            out.append(ContractViolation(
                "output-cast-count", direction,
                f"forward program narrows to {input_dtype} "
                f"{len(narrows)} time(s) ({srcs}); the contract says "
                f"exactly {contract.fwd_output_narrows} — fewer means "
                f"the accumulator never narrowed (dropped "
                f"preferred_element_type upstream), more means double "
                f"rounding through an intermediate {input_dtype}"))
    return out


def narrow_widen_findings(closed, direction: str) -> List[ContractViolation]:
    """A value narrowed then widened again = silent precision loss.

    Taint is per-jaxpr (never crosses sub-jaxpr boundaries) and flows
    only through :data:`_STRUCTURAL_PRIMS`; arithmetic consumes it, so
    the legitimate pattern — a sub-f32 forward output fed to backward
    compute that widens its *own* operands — never fires."""
    out: List[ContractViolation] = []
    for j in _iter_jaxprs(closed):
        taint: Dict[int, Tuple[str, str]] = {}
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type":
                src_v = eqn.invars[0]
                if not hasattr(src_v, "count"):
                    # Literal operand: constants carry no history.
                    continue
                src = str(src_v.aval.dtype)
                dst = str(eqn.outvars[0].aval.dtype)
                kind = cast_kind(src, dst)
                hist = taint.get(id(src_v))
                if hist is not None and kind == "widen":
                    orig, narrowed = hist
                    out.append(ContractViolation(
                        "narrow-widen", direction,
                        f"a value narrowed {orig}->{narrowed} is widened "
                        f"back to {dst} by convert_element_type without "
                        f"intervening compute — the narrow rounded away "
                        f"precision the widen cannot restore (the "
                        f"PR 4/PR 5 silent-loss class)"))
                if kind == "narrow":
                    taint[id(eqn.outvars[0])] = (src, dst)
                elif kind in ("same", "reformat") and hist is not None:
                    taint[id(eqn.outvars[0])] = hist
            elif name in _STRUCTURAL_PRIMS:
                hist = None
                for v in eqn.invars:
                    if hasattr(v, "count") and id(v) in taint:
                        hist = taint[id(v)]
                        break
                if hist is not None:
                    for ov in eqn.outvars:
                        taint[id(ov)] = hist
    return out


def hlo_convert_counts(hlo_text: str) -> Dict[Tuple[str, str], int]:
    """(src_dtype, dst_dtype) -> count over every ``convert`` op in the
    optimized HLO text (fusion bodies included) — the lowered-cast
    evidence behind the output-cast-count regression tests."""
    counts: Dict[Tuple[str, str], int] = {}
    for line in hlo_text.splitlines():
        m = _HLO_CONVERT_RE.search(line)
        if m:
            key = (m.group(2), m.group(1))   # (operand, result)
            counts[key] = counts.get(key, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# f64 reference + error probe
# ---------------------------------------------------------------------------

def _group_slices(x64, k64):
    """``(input channels, output channels)`` slices of each group of an
    HWIO kernel whose ``I`` is the input's channels over the groups."""
    groups = x64.shape[3] // k64.shape[2]
    c_g, o_g = k64.shape[2], k64.shape[3] // groups
    return [(slice(g * c_g, (g + 1) * c_g), slice(g * o_g, (g + 1) * o_g))
            for g in range(groups)]


def f64_conv2d(x64, k64, s_h: int, s_w: int):
    """The f64 numpy oracle (jax's x64 flag stays untouched): direct
    valid convolution, NHWC x HWIO -> NHWC, grouped where the kernel's
    ``I`` is a share of the input's channels."""
    import numpy as np
    if k64.shape[2] != x64.shape[3]:
        return np.concatenate([f64_conv2d(x64[..., ci], k64[..., co],
                                          s_h, s_w)
                               for ci, co in _group_slices(x64, k64)], -1)
    i_h, i_w = x64.shape[1], x64.shape[2]
    k_h, k_w = k64.shape[0], k64.shape[1]
    o_h = (i_h - k_h) // s_h + 1
    o_w = (i_w - k_w) // s_w + 1
    out = np.zeros((x64.shape[0], o_h, o_w, k64.shape[3]), np.float64)
    for r in range(k_h):
        for c in range(k_w):
            xs = x64[:, r:r + s_h * (o_h - 1) + 1:s_h,
                     c:c + s_w * (o_w - 1) + 1:s_w, :]
            out += np.einsum("nhwc,co->nhwo", xs, k64[r, c])
    return out


def f64_conv2d_grads(x64, k64, g64, s_h: int, s_w: int):
    """``(dL/dx, dL/dk)`` for cotangent ``g64``, same oracle."""
    import numpy as np
    if k64.shape[2] != x64.shape[3]:
        parts = [f64_conv2d_grads(x64[..., ci], k64[..., co], g64[..., co],
                                  s_h, s_w)
                 for ci, co in _group_slices(x64, k64)]
        return (np.concatenate([dx for dx, _ in parts], -1),
                np.concatenate([dk for _, dk in parts], -1))
    k_h, k_w = k64.shape[0], k64.shape[1]
    o_h, o_w = g64.shape[1], g64.shape[2]
    dx = np.zeros_like(x64)
    dk = np.zeros_like(k64)
    for r in range(k_h):
        for c in range(k_w):
            sl_h = slice(r, r + s_h * (o_h - 1) + 1, s_h)
            sl_w = slice(c, c + s_w * (o_w - 1) + 1, s_w)
            xs = x64[:, sl_h, sl_w, :]
            dk[r, c] = np.einsum("nhwc,nhwo->co", xs, g64)
            dx[:, sl_h, sl_w, :] += np.einsum("nhwo,co->nhwc", g64, k64[r, c])
    return dx, dk


def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got).astype(np.float64)
    denom = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(got - ref)) / denom)


def error_probe(spec, algorithm: str, dtype: str = "float32", *,
                solution: str = "auto", precision: Optional[str] = None,
                interpret: Optional[bool] = None, seed: int = 0) -> Dict:
    """Measured fwd + grad error vs the f64 oracle on fixed seeds.

    The reference consumes the *dtype-quantized* inputs widened to f64,
    so the measured error is the backend's compute error, not input
    rounding.  The grad probe is ``value_and_grad`` of ``sum(out^2)``
    — its cotangent is quantized at the input dtype, the honest
    training-time error the budgets must cover."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.conv_api import conv2d
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(spec.i_n, spec.i_h, spec.i_w, spec.i_c)
                    .astype(np.float32), dtype)
    k = jnp.asarray(rng.randn(spec.k_h, spec.k_w, spec.group_c, spec.k_c)
                    .astype(np.float32), dtype)
    x64 = np.asarray(x).astype(np.float64)
    k64 = np.asarray(k).astype(np.float64)
    prec = None if precision is None else \
        getattr(jax.lax.Precision, precision)
    stride = (spec.s_h, spec.s_w)

    def fwd(xv, kv):
        return conv2d(xv, kv, stride=stride, algorithm=algorithm,
                      solution=solution, interpret=interpret,
                      precision=prec, partition="none")

    def loss(xv, kv):
        o = fwd(xv, kv)
        return jnp.sum(o * o)

    with _quiet_trace():
        out = jax.jit(fwd)(x, k)
        din, dk = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, k)
    out64 = f64_conv2d(x64, k64, spec.s_h, spec.s_w)
    dx64, dk64 = f64_conv2d_grads(x64, k64, 2.0 * out64, spec.s_h, spec.s_w)
    return {"seed": seed,
            "fwd_err": _rel_err(out, out64),
            "din_err": _rel_err(din, dx64),
            "dk_err": _rel_err(dk, dk64)}


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NumCheck:
    """Verdict of one (algorithm, dtype) numeric-contract check.

    ``record`` is the JSON-able evidence bench/CLI reports embed;
    ``skipped`` carries the reason when the cell cannot be checked here
    (no contract for the backend or dtype, geometry the backend
    refuses) — a skip is not a pass and not a failure."""

    algorithm: str
    dtype: str
    violations: List[ContractViolation]
    record: Dict
    skipped: Optional[str] = None

    @property
    def ok(self) -> bool:
        return not self.violations

    def render(self) -> str:
        head = (f"numcheck {self.algorithm}/{self.dtype}: "
                f"{self.record.get('verdict')}")
        lines = [head]
        if self.skipped:
            lines.append(f"  skipped: {self.skipped}")
        lines += [f"  {v.render()}" for v in self.violations]
        return "\n".join(lines)


def check_numerics(spec, algorithm: str, dtype: str = "float32", *,
                   solution: str = "auto",
                   precision: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   directions: Sequence[str] = DIRECTIONS,
                   probe: bool = True, seed: int = 0) -> NumCheck:
    """Full numeric-contract check of one backend x dtype cell.

    Traces ``conv2d`` on ``spec`` (fwd and ``value_and_grad`` of the
    quadratic probe loss — tracing only, no compile), runs the static
    detectors over each direction's numeric signature, the per-jaxpr
    narrow-widen taint pass, the precision-flow pass when a precision
    is declared, and — with ``probe=True`` — the measured error-budget
    probe (this one jit-compiles and executes, so the plan hook turns
    it off)."""
    contract = contract_for(algorithm)
    record: Dict = {
        "algorithm": algorithm,
        "dtype": dtype,
        "contract": None if contract is None else contract.to_dict(),
        "directions": {},
        "precision_flow": None,
        "probe": None,
        "verdict": "pass",
        "skipped_reason": None,
        "violations": [],
    }

    def skipped(reason: str) -> NumCheck:
        record["verdict"] = "skipped"
        record["skipped_reason"] = reason
        return NumCheck(algorithm, dtype, [], record, skipped=reason)

    if contract is None:
        return skipped(f"no numeric contract declared for {algorithm!r} "
                       f"(repro.core.numerics.CONTRACTS — every backend "
                       f"must declare one before entering the plan "
                       f"candidate set)")
    if dtype not in CONTRACT_DTYPES:
        return skipped(f"no contract dtype {dtype!r} (contract dtypes: "
                       f"{CONTRACT_DTYPES})")
    from repro.core.convspec import grouped_refusal
    why = grouped_refusal(spec, algorithm)
    if why:
        return skipped(why)
    if algorithm == "winograd" and \
            (spec.k_h, spec.k_w, spec.s_h, spec.s_w) != (3, 3, 1, 1):
        return skipped("winograd F(2x2,3x3) requires a 3x3 kernel and "
                       "stride 1")
    if algorithm == "mec_fused":
        from repro.kernels.mec_conv import fused_refusal
        from repro.kernels.ops import pick_w_blk
        why = fused_refusal(spec, dtype,
                            pick_w_blk(spec.o_w, spec.k_c, _warn_env=False))
        if why:
            return skipped(f"mec_fused refuses the geometry: {why}")

    import jax
    import jax.numpy as jnp
    from repro.core.conv_api import conv2d
    prec = None if precision is None else \
        getattr(jax.lax.Precision, precision)
    stride = (spec.s_h, spec.s_w)

    def fwd(xv, kv):
        return conv2d(xv, kv, stride=stride, algorithm=algorithm,
                      solution=solution, interpret=interpret,
                      precision=prec, partition="none")

    def loss(xv, kv):
        o = fwd(xv, kv)
        return jnp.sum(o * o)

    fns = {"fwd": fwd, "grad": jax.value_and_grad(loss, argnums=(0, 1))}
    x_s = jax.ShapeDtypeStruct((spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                               dtype)
    k_s = jax.ShapeDtypeStruct((spec.k_h, spec.k_w, spec.group_c,
                                spec.k_c), dtype)
    violations: List[ContractViolation] = []
    jaxprs = []
    for direction in directions:
        with _quiet_trace():
            closed = jax.make_jaxpr(fns[direction])(x_s, k_s)
        jaxprs.append(closed)
        sig = extract_signature(closed)
        violations += signature_findings(sig, contract, direction, dtype)
        violations += narrow_widen_findings(closed, direction)
        record["directions"][direction] = {
            "dots": len(sig["dots"]),
            "pallas_dots": sum(1 for d in sig["dots"] if d["pallas"]),
            "casts": len(sig["casts"]),
            "narrows_to_input": sum(
                1 for c in sig["casts"]
                if c["kind"] == "narrow" and c["dst"] == dtype),
        }
    if precision not in (None, "DEFAULT"):
        tally, pviol = precision_flow_findings(jaxprs, [], precision)
        violations += pviol
        record["precision_flow"] = tally
    if probe:
        errs = error_probe(spec, algorithm, dtype, solution=solution,
                           precision=precision, interpret=interpret,
                           seed=seed)
        tol_fwd = contract.tolerance(dtype, "fwd")
        tol_grad = contract.tolerance(dtype, "grad")
        record["probe"] = dict(errs, budget_fwd=tol_fwd,
                               budget_grad=tol_grad)
        for label, err, tol in (("fwd", errs["fwd_err"], tol_fwd),
                                ("grad(d_input)", errs["din_err"], tol_grad),
                                ("grad(d_kernel)", errs["dk_err"],
                                 tol_grad)):
            if tol is not None and err > tol:
                direction = "fwd" if label == "fwd" else "grad"
                violations.append(ContractViolation(
                    "error-budget", direction,
                    f"{label} error {err:.3e} vs the f64 reference "
                    f"exceeds the contract budget {tol:.0e} for "
                    f"{algorithm}/{dtype} (seed {errs['seed']})"))
    record["violations"] = [v.render() for v in violations]
    record["verdict"] = "pass" if not violations else "fail"
    return NumCheck(algorithm, dtype, violations, record)


# ---------------------------------------------------------------------------
# bench + plan wiring (duck-typed; repro.plan imports us, never the
# reverse)
# ---------------------------------------------------------------------------

_CELL_CACHE: Dict[Tuple, Dict] = {}
_CELL_CACHE_MAX = 256


def cell_numcheck(spec, algorithm: str, dtype: str, *,
                  solution: str = "auto",
                  interpret: Optional[bool] = None) -> Dict:
    """Reduced, memoized static verdict for one bench cell (no probe —
    the bench harness must not pay an extra execution per cell).  The
    reduced field is version-robust: verdict + rendered violations; the
    full evidence lives in BENCH_numcheck.json."""
    key = (spec, algorithm, solution, dtype)
    hit = _CELL_CACHE.get(key)
    if hit is not None:
        return dict(hit)
    chk = check_numerics(spec, algorithm, dtype, solution=solution,
                         interpret=interpret, probe=False)
    reduced = {"verdict": chk.record["verdict"],
               "skipped_reason": chk.record["skipped_reason"],
               "violations": chk.record["violations"]}
    if len(_CELL_CACHE) >= _CELL_CACHE_MAX:
        _CELL_CACHE.clear()
    _CELL_CACHE[key] = reduced
    return dict(reduced)


# plan_conv2d calls the hook once per contract identity; layers
# resolving the same plan per construction must not re-pay two traces
# each time.
_HOOK_CACHE: Dict[Tuple, Tuple[bool, str]] = {}
_HOOK_CACHE_MAX = 256


def assert_plan_numerics(plan) -> None:
    """The ``plan_conv2d`` hook: raise :class:`NumCheckError` when the
    resolved backend x dtype breaks its static numeric contract.
    Static-only (tracing, no compile, no probe) so planning stays
    cheap; skipped checks (unregistered backend or dtype) pass silently
    — the CLI suite is where skips are visible.  Memoized by contract
    identity (spec, dtype, algorithm, solution, precision)."""
    algorithm = getattr(plan, "algorithm", None)
    if algorithm in (None, "auto"):
        return
    dtype = str(getattr(plan, "dtype", "float32"))
    solution = getattr(plan, "solution", "auto")
    precision = getattr(plan, "precision", None)
    key = (plan.spec, dtype, algorithm, solution, precision)
    hit = _HOOK_CACHE.get(key)
    if hit is not None:
        ok, rendered = hit
        if not ok:
            raise NumCheckError(rendered)
        return
    result = check_numerics(plan.spec, algorithm, dtype, solution=solution,
                            precision=precision, probe=False)
    if len(_HOOK_CACHE) >= _HOOK_CACHE_MAX:
        _HOOK_CACHE.clear()
    _HOOK_CACHE[key] = (result.ok, result.render())
    if not result.ok:
        raise NumCheckError(result.render())
