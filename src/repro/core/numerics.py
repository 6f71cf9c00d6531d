"""Per-backend numeric contracts (DESIGN.md §8.5).

MEC's Table 2 claim is that swapping the lowering trades memory for
speed *without* changing the convolution's result.  Each backend in
:data:`repro.core.conv_api.ALGORITHMS` therefore declares a
:class:`NumericContract`: the accumulation width its GEMMs must keep,
the cast structure its forward program is allowed to emit, and a
*measured* error budget against an f64 reference — the numbers
``repro.analysis.numcheck`` verifies statically (jaxpr dataflow) and
dynamically (the fixed-seed probe).

The shared baseline every current backend satisfies:

* all dot/conv contractions with sub-f32 operands accumulate at f32
  (``preferred_element_type=jnp.float32`` on every GEMM — in-kernel
  Pallas dots included);
* the forward program narrows back to the input dtype through exactly
  one cast edge (``fwd_output_narrows``) — MEC's per-row narrow inside
  the scan body is that one edge, written per output row;
* f64/complex128 never appear (``allow_f64=False`` everywhere: this is
  an f32-accumulate reproduction, a stray f64 means an unintended
  promotion);
* only ``fft`` may touch complex, and only at ``complex64`` — exactly
  2x the f32 compute width (``complex_pair``).

Error budgets are scale-normalized max errors (``max|y-ref| /
max|ref|``) measured on the fixed-seed probe spec (`numcheck`'s
``probe_spec()``) and recorded here with ~4x headroom over the observed
error — the contract, not the test file, owns the tolerance (a new
backend must declare its own before it can enter the plan candidate
set; ROADMAP "algorithm zoo").  ``grad`` budgets cover both cotangents
(input and kernel) of a quadratic probe loss, whose cotangent is
quantized at the input dtype — the honest training-time error.

Layering: pure data + stdlib; importable from anywhere (core, plan,
analysis, tests) without touching jax.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple

#: dtypes every backend must hold a contract (and budget) for.
CONTRACT_DTYPES = ("float32", "bfloat16", "float16")

_FLOAT_BITS = {"float16": 16, "bfloat16": 16, "float32": 32, "float64": 64}


@dataclasses.dataclass(frozen=True)
class NumericContract:
    """The declared dtype-flow rules for one conv backend.

    ``error_budget`` maps dtype -> {"fwd": tol, "grad": tol}; a dtype
    missing from the map means the backend makes no accuracy claim
    there and the probe records (but cannot gate) its error.
    """

    algorithm: str
    #: minimum accumulation dtype for contractions with sub-f32 operands
    accum_dtype: str = "float32"
    #: complex64 admitted beside f32 compute (FFT round-trip only)
    complex_pair: bool = False
    #: narrowing casts back to the input dtype in the *forward* program
    #: when the input is sub-f32 (f32 inputs must narrow zero times)
    fwd_output_narrows: int = 1
    #: f64/complex128 are never part of the contract
    allow_f64: bool = False
    #: scale-normalized max-error budget vs the f64 reference
    error_budget: Mapping[str, Mapping[str, float]] = \
        dataclasses.field(default_factory=dict)

    def allowed_dtypes(self, input_dtype: str) -> Tuple[str, ...]:
        """Float/complex dtypes a program on ``input_dtype`` may touch."""
        allowed = {input_dtype, self.accum_dtype}
        if self.complex_pair:
            allowed.add("complex64")
        return tuple(sorted(allowed))

    def tolerance(self, dtype: str, direction: str) -> Optional[float]:
        budget = self.error_budget.get(dtype)
        return None if budget is None else budget.get(direction)

    def to_dict(self) -> Dict:
        return {
            "algorithm": self.algorithm,
            "accum_dtype": self.accum_dtype,
            "complex_pair": self.complex_pair,
            "fwd_output_narrows": self.fwd_output_narrows,
            "allow_f64": self.allow_f64,
            "error_budget": {d: dict(b)
                             for d, b in sorted(self.error_budget.items())},
        }


def float_bits(dtype: str) -> Optional[int]:
    """Float width in bits; None for non-float dtypes (by name, so the
    contract layer never needs jax/numpy)."""
    return _FLOAT_BITS.get(str(dtype))


# Budgets measured on numcheck's probe_spec() at seed 0, recorded with
# ~4x headroom over the worst observed backend (BENCH_numcheck.json
# carries the raw measurements).  f32: every backend sits at a few ulps
# of the f64 reference (worst fwd 1.8e-7, worst grad 2.9e-7); fft and
# winograd get a slightly wider band for the complex round-trip /
# transform conditioning.  bf16 (8-bit mantissa) dominates the sub-f32
# budgets (worst fwd 2.9e-3, worst grad 6.3e-3 — im2col's d_input,
# whose cotangent is quantized bf16 before the f32-accumulated VJP
# GEMMs consume it); f16's 11-bit mantissa lands ~8x tighter.
_F32 = {"fwd": 1e-6, "grad": 2e-6}
_F32_FFT = {"fwd": 2e-6, "grad": 4e-6}
_BF16 = {"fwd": 1.2e-2, "grad": 2.5e-2}
_F16 = {"fwd": 1.2e-3, "grad": 2e-3}

_MEC_BUDGET = {"float32": _F32, "bfloat16": _BF16, "float16": _F16}

CONTRACTS: Dict[str, NumericContract] = {
    "direct": NumericContract(
        "direct",
        error_budget={"float32": _F32, "bfloat16": _BF16, "float16": _F16}),
    "im2col": NumericContract(
        "im2col",
        error_budget={"float32": _F32, "bfloat16": _BF16, "float16": _F16}),
    "fft": NumericContract(
        "fft", complex_pair=True,
        error_budget={"float32": _F32_FFT, "bfloat16": _BF16,
                      "float16": _F16}),
    "winograd": NumericContract(
        "winograd",
        error_budget={"float32": _F32_FFT, "bfloat16": _BF16,
                      "float16": _F16}),
    "mec": NumericContract("mec", error_budget=_MEC_BUDGET),
    "mec_fused": NumericContract("mec_fused", error_budget=_MEC_BUDGET),
}


def contract_for(algorithm: str) -> Optional[NumericContract]:
    """The declared contract, or None for unregistered backends (the
    checker records those as skips — a backend without a contract is a
    ROADMAP violation, not a crash)."""
    return CONTRACTS.get(algorithm)
