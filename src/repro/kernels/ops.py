"""Public jit'd entry points for the MEC Pallas kernels.

``interpret`` defaults to True when the backend has no TPU, so the CPU
tests run the kernels in the Pallas interpreter.  On a TPU backend the
kernels always compile through Mosaic: asking for interpret mode there is
an error, never a silent slowdown.  Only ``mode="fused"`` has a Mosaic
lowering (:data:`MOSAIC_MODES`); the other modes raise
``NotImplementedError`` before lowering.  Block sizes are chosen for v5e
VMEM (~16 MiB/core).
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels.mec_conv import (mec_conv_fused2_pallas,
                                    mec_conv_fused_pallas, mec_gemm_pallas,
                                    mec_lower_pallas)
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


# Kernel modes with a Mosaic (TPU) lowering.  ``fused2`` and ``lowered``
# index loaded values with ``lax.dynamic_slice`` and use strided or
# (8,128)-misaligned blocks, none of which Mosaic lowers; they run only
# in the Pallas interpreter.
MOSAIC_MODES = ("fused",)


def resolve_interpret(interpret, mode: str = "fused") -> bool:
    """The interpret flag a kernel call runs with.

    None follows the backend: interpret everywhere but TPU.  On a TPU
    backend ``interpret=True`` raises.  A mode without a Mosaic lowering
    raises ``NotImplementedError`` whenever the call would lower for the
    chip, before any tracing."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend would run the Pallas kernel "
            "in the interpreter; drop the flag to compile it for the chip")
    if not interpret and mode not in MOSAIC_MODES:
        raise NotImplementedError(
            f"mec_{mode} has no Mosaic lowering (it slices loaded values "
            "with lax.dynamic_slice and uses strided or misaligned "
            f"blocks); on TPU use one of {['mec_' + m for m in MOSAIC_MODES]}"
            " or an XLA algorithm")
    return interpret


def vmem_bytes() -> int:
    """Per-core VMEM of the local device kind.  Off TPU (interpreter
    runs) this is :data:`INTERPRET_VMEM`; an unknown TPU kind raises.
    The static checker (``repro.analysis.pallas_check``) sizes
    whole-kernel working sets against this; :func:`accumulator_budget`
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
                   mode: str = "fused", interpret=None,
                   precision=None, w_blk: int | None = None) -> jnp.ndarray:
    """MEC convolution with Pallas kernels.

    mode='lowered' is the paper-faithful path (L materialized in HBM,
    Eq. 3 memory observable); mode='fused' is the beyond-paper fused path.
    precision reaches the in-kernel GEMMs (matters for bf16 operands on
    the MXU; accumulation is f32 regardless).  w_blk is normally supplied
    by the resolved :class:`repro.plan.ConvPlan`; when None (bare kwargs
    path) it falls back to :func:`pick_w_blk` — device-queried VMEM with
    the deprecated REPRO_MEC_ACC_BYTES env override.
    """
    if mode not in ("fused", "fused2", "lowered"):
        raise ValueError(f"unknown mode {mode!r}")
    interpret = resolve_interpret(interpret, mode)
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    i_n, i_h, i_w, i_c = inp.shape
    k_h, k_w, _, k_c = kernel.shape
    o_w = (i_w - k_w) // s_w + 1
    if w_blk is None:
        w_blk = pick_w_blk(o_w, k_c)
    elif not 1 <= w_blk <= max(o_w, 1):
        raise ValueError(f"w_blk must be in [1, o_w={o_w}], got {w_blk}")
    if mode == "fused":
        return mec_conv_fused_pallas(inp, kernel, (s_h, s_w), w_blk=w_blk,
                                     interpret=interpret,
                                     precision=precision)
    if mode == "fused2":   # h-blocked + halo: ~1x input fetch (EXPERIMENTS)
        return mec_conv_fused2_pallas(inp, kernel, (s_h, s_w), w_blk=w_blk,
                                      interpret=interpret,
                                      precision=precision)
    low = mec_lower_pallas(inp, k_w, s_w, interpret=interpret)   # lowered
    kernel_mat = kernel.reshape(k_h, k_w * i_c, k_c)
    out = mec_gemm_pallas(low, kernel_mat, k_h, s_h, w_blk=w_blk,
                          interpret=interpret, precision=precision)
    with jax.named_scope("conv2d_out"):
        return out.astype(inp.dtype)


def mec_conv1d_tpu(x: jnp.ndarray, kernel: jnp.ndarray,
                   interpret=None) -> jnp.ndarray:
    """Fused causal depthwise conv1d (Mamba2 / xLSTM blocks)."""
    interpret = resolve_interpret(interpret)
    return mec_conv1d_pallas(x, kernel, interpret=interpret)
