"""Public jit'd entry points for the MEC Pallas kernels.

``interpret`` defaults to True when the backend has no TPU, so the CPU
tests run the kernels in the Pallas interpreter.  On a TPU backend the
kernels always compile through Mosaic: asking for interpret mode there is
an error, never a silent slowdown.  Block sizes are chosen for v5e VMEM
(~16 MiB/core).
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels.mec_conv import (mec_conv_fused_pallas,
                                    mec_weight_grad_pallas)
from repro.kernels.mec_conv1d import mec_conv1d_pallas

# Accumulator budget override for non-v5e targets (bytes; decimal or hex).
ACC_BYTES_ENV = "REPRO_MEC_ACC_BYTES"

# Per-core VMEM by device kind (substring match against
# jax.Device.device_kind).  v2-v5 generations all carry ~16 MiB/core;
# Trillium doubles it.  An unknown TPU kind is an error.
_VMEM_BYTES_BY_KIND = (
    ("v6", 32 << 20),
    ("v5", 16 << 20),
    ("v4", 16 << 20),
    ("v3", 16 << 20),
    ("v2", 16 << 20),
)
# Off-TPU (Pallas interpreter) runs size blocks as for a v5e chip, so the
# CPU tests exercise the block sizes the chip path would pick.
INTERPRET_VMEM = 16 << 20
# The f32 accumulator gets 1/8 of VMEM; the rest holds the input strip,
# kernel block, and Mosaic's double buffering.
_ACC_FRACTION = 8


def resolve_interpret(interpret) -> bool:
    """The interpret flag a kernel call runs with.

    None follows the backend: interpret everywhere but TPU.  On a TPU
    backend ``interpret=True`` raises."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend would run the Pallas kernel "
            "in the interpreter; drop the flag to compile it for the chip")
    return interpret


def vmem_bytes() -> int:
    """Per-core VMEM of the local device kind.  Off TPU (interpreter
    runs) this is :data:`INTERPRET_VMEM`; an unknown TPU kind raises.
    The kernel's geometry rule (``repro.kernels.mec_conv.fused_refusal``)
    sizes a step's working set against this; :func:`accumulator_budget`
    carves the accumulator's fraction out of it."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return INTERPRET_VMEM
    kind = dev.device_kind.lower()
    for tag, vmem in _VMEM_BYTES_BY_KIND:
        if tag in kind:
            return vmem
    raise ValueError(f"no VMEM size known for TPU kind {dev.device_kind!r}; "
                     "add it to repro.kernels.ops._VMEM_BYTES_BY_KIND")


def accumulator_budget(*, _warn_env: bool = True) -> int:
    """VMEM bytes the f32 output accumulator may fill.

    Resolution order: the REPRO_MEC_ACC_BYTES env override, else
    VMEM/8 of the local device (:func:`vmem_bytes`; ~2 MiB on v5e and
    in interpreter runs).

    The env override is deprecated outside the planner: tuned block
    sizes belong in a :class:`repro.plan.ConvPlan` (``plan.w_blk``,
    produced by ``repro.plan.plan_conv2d`` and threaded to the kernels
    by the ``conv2d`` executor).  Reads of the env var on the kwargs
    fallback path emit a DeprecationWarning; behaviour is unchanged.
    """
    env = os.environ.get(ACC_BYTES_ENV)  # lint-ignore: deprecated-acc-bytes-env, raw-environ-read-outside-compat (this IS the deprecation shim for the env var)
    if env:
        if _warn_env:
            warnings.warn(
                f"{ACC_BYTES_ENV} is deprecated outside the plan path: "
                "put tuned accumulator budgets in a ConvPlan instead "
                "(repro.plan.plan_conv2d resolves ConvPlan.w_blk once; "
                "conv2d(plan=...) threads it to the kernels)",
                DeprecationWarning, stacklevel=2)
        budget = int(env, 0)
        if budget <= 0:
            raise ValueError(f"{ACC_BYTES_ENV} must be positive, got {env!r}")
        return budget
    return vmem_bytes() // _ACC_FRACTION


def pick_w_blk(o_w: int, k_c: int, target_bytes: int | None = None, *,
               _warn_env: bool = True) -> int:
    """Output-column block: fill the accumulator budget (device-queried /
    env-tunable via :func:`accumulator_budget`, ~2 MiB on v5e) with the
    f32 accumulator, rounded down to a multiple of 8 (sublane) and capped
    at o_w.

    The 8-column sublane floor applies only to the *implicit* device
    budget; an explicit ``target_bytes`` is a hard cap — the block never
    exceeds it (down to the 1-column minimum, the smallest accumulator
    that exists).  ``_warn_env=False`` is the planner's entry
    (``repro.plan``): the env override still applies there without the
    deprecation warning, since a plan *is* the supported place for the
    tuned value to land.
    """
    explicit = target_bytes is not None
    if not explicit:
        target_bytes = accumulator_budget(_warn_env=_warn_env)
    blk = min(512, target_bytes // max(1, 4 * k_c))
    if not explicit:
        blk = max(8, blk)
    if blk >= 8:
        blk = (blk // 8) * 8
    return max(1, min(blk, o_w))


def mec_conv2d_tpu(inp: jnp.ndarray, kernel: jnp.ndarray, stride=1,
                   interpret=None, precision=None,
                   w_blk: int | None = None) -> jnp.ndarray:
    """MEC convolution on the fused Pallas kernel (no L in HBM).

    precision reaches the in-kernel GEMMs (matters for bf16 operands on
    the MXU; accumulation is f32 regardless).  w_blk is normally supplied
    by the resolved :class:`repro.plan.ConvPlan`; when None (bare kwargs
    path) it falls back to :func:`pick_w_blk` — device-queried VMEM with
    the deprecated REPRO_MEC_ACC_BYTES env override.
    """
    interpret = resolve_interpret(interpret)
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    k_w, k_c = kernel.shape[1], kernel.shape[3]
    o_w = (inp.shape[2] - k_w) // s_w + 1
    if w_blk is None:
        w_blk = pick_w_blk(o_w, k_c)
    elif not 1 <= w_blk <= max(o_w, 1):
        raise ValueError(f"w_blk must be in [1, o_w={o_w}], got {w_blk}")
    return mec_conv_fused_pallas(inp, kernel, (s_h, s_w), w_blk=w_blk,
                                 interpret=interpret, precision=precision)


def mec_weight_grad_tpu(inp: jnp.ndarray, g: jnp.ndarray, k_h: int,
                        k_w: int, stride=1, interpret=None, precision=None,
                        w_blk: int | None = None) -> jnp.ndarray:
    """dL/dK (f32, HWIO) of the :func:`mec_conv2d_tpu` conv of ``inp``
    that ran at ``w_blk`` (None: :func:`pick_w_blk`, as the forward
    picks it), from the cotangent ``g`` of its output, on the forward's
    blocking (``repro.kernels.mec_conv.mec_weight_grad_pallas``)."""
    interpret = resolve_interpret(interpret)
    if w_blk is None:
        w_blk = pick_w_blk(g.shape[2], g.shape[3], _warn_env=False)
    return mec_weight_grad_pallas(inp, g, k_h, k_w, stride, w_blk=w_blk,
                                  interpret=interpret, precision=precision)


def mec_conv1d_tpu(x: jnp.ndarray, kernel: jnp.ndarray,
                   interpret=None) -> jnp.ndarray:
    """Fused causal depthwise conv1d (Mamba2 / xLSTM blocks)."""
    interpret = resolve_interpret(interpret)
    return mec_conv1d_pallas(x, kernel, interpret=interpret)
