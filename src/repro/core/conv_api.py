"""Unified, trainable 2-D convolution front-end (DESIGN.md §1, §7).

Every conv call site in this repo — models, examples, benchmarks — goes
through ``conv2d``.  It owns padding (SAME/VALID/explicit), validates
geometry through :class:`~repro.core.convspec.ConvSpec`, and dispatches
to one of the algorithm back-ends the paper compares in §4:

=============  ============================================================
``direct``     ``lax.conv_general_dilated`` (XLA direct; numerical oracle)
``im2col``     full Toeplitz lowering + one GEMM (paper Eq. 2 baseline)
``fft``        frequency-domain (paper §2.2 FFT baseline)
``winograd``   F(2x2, 3x3); requires a 3x3 kernel and stride 1
``mec``        paper Algorithm 2, pure JAX (Solutions A/B)
``mec_fused``  Pallas: lowering fused into the GEMM, no L in HBM
``auto``       cached :class:`repro.plan.ConvPlan` (analytic on miss)
=============  ============================================================

Since the planner redesign (DESIGN.md §7) ``conv2d`` is a thin
*executor*: the full decision — algorithm, MEC solution, Pallas
``w_blk``, precision, partition — lives in a frozen
:class:`repro.plan.ConvPlan`.  ``conv2d(..., plan=)`` executes exactly
that plan (plan fields win over kwargs); bare kwargs with
``algorithm="auto"`` resolve through the process/disk plan cache
(``repro.plan.resolve_cached_plan``), which computes the analytic plan
on a miss — the same pick the pre-planner dispatch made.

All MEC paths are wrapped in a single ``jax.custom_vjp`` so the compact
lowering is trainable end-to-end:

* input gradient = a *transposed MEC conv*: the cotangent, stride-dilated
  and fully padded, is itself MEC-convolved with the spatially-flipped,
  channel-transposed kernel.  A stride-1 conv that ran forward on
  ``mec_fused`` runs it on the same ``mec_fused`` kernel, in the
  cotangent's dtype, where the transposed geometry passes the forward's
  own rule (:func:`fused_input_grad_refusal`); every other conv, and
  a transposed geometry the rule refuses, runs the pure-JAX reference
  in f32 (``_mec_input_grad``);
* weight gradient = the forward's windows against the cotangent, summed
  over the pixels.  A conv that ran forward on ``mec_fused`` runs it on
  ``mec_fused_wgrad``, which walks the forward's own blocking and builds
  each tap's window in VMEM, so L never exists in HBM, where its working
  set fits and a strided conv folds to enough column taps
  (``kernels.mec_conv.fused_weight_grad_refusal``); every other
  conv, and a geometry the rule refuses, materialises ``mec_lower``'s
  compact L and runs one f32 einsum per kernel row over shifted views of
  it (``_mec_weight_grad``), never an im2col-sized buffer.
"""
from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.convspec import (ConvSpec, normalize_stride, pad_same,
                                 spec_of)
from repro.core.direct import direct_conv2d
from repro.core.fft_conv import fft_conv2d
from repro.core.im2col import im2col_conv2d
from repro.core.mec import mec_conv2d as _mec_reference, mec_lower
from repro.core.winograd import winograd_conv2d

if TYPE_CHECKING:  # repro.plan imports core; the cycle is runtime-lazy
    from repro.plan import ConvPlan

MEC_ALGORITHMS = ("mec", "mec_fused")
ALGORITHMS = ("auto", "direct", "im2col", "fft", "winograd") + MEC_ALGORITHMS

Padding = Union[str, int, Tuple]

# The names the program gives its device work, as they appear in each
# op's name stack (HLO ``op_name``) and so in a profiler trace: the
# ``jax.named_scope``s of the whole conv, its padding pass, the kernel
# wrapper's stride fold and output crop/cast, the two halves of the MEC
# backward and the optimizer step (``repro.optim.adamw.update``), then the
# ``pallas_call`` names of the kernels (``repro.kernels``), then the
# ResNet's (``repro.models``): every batch norm, every 1x1 conv call and
# the head (pool, classifier and loss).
TRACE_SCOPES = ("conv2d", "conv2d_pad", "mec_fold", "conv2d_out",
                "mec_input_grad", "mec_weight_grad", "adamw_update",
                "mec_fused", "mec_fused_wgrad", "mec_conv1d", "batch_norm",
                "pointwise", "head")


def apply_padding(inp: jnp.ndarray, k_h: int, k_w: int, s_h: int, s_w: int,
                  padding: Padding) -> jnp.ndarray:
    """SAME / VALID / explicit padding, applied once so every algorithm
    sees an identical pre-padded input (paper §2.1).  Negative explicit
    pads are rejected here — ``jnp.pad`` would otherwise raise deep in
    the trace with an opaque message."""
    if isinstance(padding, str):
        mode = padding.upper()
        if mode == "VALID":
            return inp
        if mode == "SAME":
            with jax.named_scope("conv2d_pad"):
                return pad_same(inp, k_h, k_w, s_h, s_w)
        raise ValueError(f"unknown padding {padding!r}")
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    p_h, p_w = padding
    if isinstance(p_h, int):
        p_h = (p_h, p_h)
    if isinstance(p_w, int):
        p_w = (p_w, p_w)
    p_h, p_w = tuple(p_h), tuple(p_w)
    if min(p_h + p_w) < 0:
        raise ValueError(
            f"padding must be non-negative, got {(p_h, p_w)}; negative "
            "pads (cropping) are not a convolution padding")
    with jax.named_scope("conv2d_pad"):
        return jnp.pad(inp, ((0, 0), p_h, p_w, (0, 0)))


# ---------------------------------------------------------------------------
# MEC custom VJP (shared by the reference and the Pallas kernel)
# ---------------------------------------------------------------------------

def _mec_forward(inp, kernel, s_h, s_w, variant, solution, interpret,
                 precision, w_blk):
    if variant == "mec":
        return _mec_reference(inp, kernel, (s_h, s_w), solution=solution,
                              precision=precision)
    from repro.kernels.ops import mec_conv2d_tpu
    return mec_conv2d_tpu(inp, kernel, (s_h, s_w), interpret=interpret,
                          precision=precision, w_blk=w_blk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _mec_conv(inp, kernel, s_h, s_w, variant, solution, interpret,
              precision, w_blk):
    return _mec_forward(inp, kernel, s_h, s_w, variant, solution, interpret,
                        precision, w_blk)


def _mec_fwd(inp, kernel, s_h, s_w, variant, solution, interpret, precision,
             w_blk):
    out = _mec_forward(inp, kernel, s_h, s_w, variant, solution, interpret,
                       precision, w_blk)
    return out, (inp, kernel)


def _mec_input_grad(g: jnp.ndarray, kernel: jnp.ndarray, s_h: int, s_w: int,
                    i_h: int, i_w: int, precision=None) -> jnp.ndarray:
    """dL/dI as a transposed MEC conv: stride-dilate the cotangent, pad it
    fully, and MEC-convolve with the spatially-flipped kernel whose
    channel axes are swapped (HWIO -> HWOI)."""
    k_h, k_w = kernel.shape[:2]
    g32 = g.astype(jnp.float32)
    i_n, o_h, o_w, k_c = g.shape
    if s_h > 1 or s_w > 1:
        gd = jnp.zeros((i_n, (o_h - 1) * s_h + 1, (o_w - 1) * s_w + 1, k_c),
                       jnp.float32)
        gd = gd.at[:, ::s_h, ::s_w, :].set(g32)
    else:
        gd = g32
    gp = jnp.pad(gd, ((0, 0), (k_h - 1, k_h - 1), (k_w - 1, k_w - 1), (0, 0)))
    k_t = jnp.transpose(kernel[::-1, ::-1], (0, 1, 3, 2)).astype(jnp.float32)
    # (n, (o_h-1)s_h + k_h, ..., i_c)
    di = _mec_reference(gp, k_t, (1, 1), precision=precision)
    # Input rows/cols beyond the last kernel window receive zero gradient.
    return jnp.pad(di, ((0, 0), (0, i_h - di.shape[1]),
                        (0, i_w - di.shape[2]), (0, 0)))


def _mec_weight_grad(inp: jnp.ndarray, g: jnp.ndarray, s_h: int, s_w: int,
                     k_h: int, k_w: int, precision=None) -> jnp.ndarray:
    """dL/dK from the compact L (Eq. 3): for each kernel row r, the
    stride-s_h shifted view of L against the cotangent — the same
    k_h-decomposition the Pallas kernels use, run in reverse."""
    low = mec_lower(inp, k_w, s_w)        # (n, o_w, i_h, k_w, i_c)
    o_h = g.shape[1]
    g32 = g.astype(jnp.float32)
    low32 = low.astype(jnp.float32)
    rows = []
    for r in range(k_h):
        lr = lax.slice_in_dim(low32, r, r + s_h * (o_h - 1) + 1,
                              stride=s_h, axis=2)  # (n, o_w, o_h, k_w, i_c)
        rows.append(jnp.einsum("nwhjc,nhwo->jco", lr, g32,
                               precision=precision,
                               preferred_element_type=jnp.float32))
    return jnp.stack(rows, axis=0)        # (k_h, k_w, i_c, k_c)


def input_grad_spec(spec: ConvSpec) -> ConvSpec:
    """The stride-1 conv whose output is dL/dI of the stride-1 ``spec``:
    the cotangent padded by (k_h - 1, k_w - 1) against the flipped kernel
    with its channel axes swapped.  Its output is ``spec``'s input."""
    return ConvSpec(spec.i_n, spec.o_h + 2 * (spec.k_h - 1),
                    spec.o_w + 2 * (spec.k_w - 1), spec.k_c, spec.k_h,
                    spec.k_w, spec.i_c)


def fused_input_grad_refusal(spec: ConvSpec, dtype) -> Optional[str]:
    """Why the input gradient of a ``mec_fused`` conv of ``spec`` runs on
    the XLA path (``_mec_input_grad``) and not on the ``mec_fused``
    kernel, or None where the kernel takes it: a stride-1 conv whose
    transposed geometry (:func:`input_grad_spec`) passes the forward's
    own rule, :func:`repro.kernels.mec_conv.fused_refusal`."""
    if (spec.s_h, spec.s_w) != (1, 1):
        return (f"stride {(spec.s_h, spec.s_w)}: the kernel takes the "
                "input gradients of stride-1 convs only")
    from repro.kernels.mec_conv import fused_refusal
    why = fused_refusal(input_grad_spec(spec), dtype)
    return None if why is None else f"transposed geometry refused: {why}"


def _mec_fused_input_grad(g: jnp.ndarray, kernel: jnp.ndarray, interpret,
                          precision=None) -> jnp.ndarray:
    """dL/dI of a stride-1 conv on the ``mec_fused`` kernel: the cotangent
    padded by (k_h - 1, k_w - 1) in its own dtype, convolved with the
    flipped kernel (HWIO -> HWOI) cast to that dtype.  Every tap sums in
    the kernel's f32 accumulator, rounded once to the cotangent's dtype;
    the output is the gradient of the whole (padded) input."""
    from repro.kernels.ops import mec_conv2d_tpu, pick_w_blk
    k_h, k_w, i_c, _ = kernel.shape
    gp = jnp.pad(g, ((0, 0), (k_h - 1, k_h - 1), (k_w - 1, k_w - 1),
                     (0, 0)))
    k_t = jnp.transpose(kernel[::-1, ::-1], (0, 1, 3, 2)).astype(g.dtype)
    w_blk = pick_w_blk(gp.shape[2] - k_w + 1, i_c, _warn_env=False)
    return mec_conv2d_tpu(gp, k_t, (1, 1), interpret=interpret,
                          precision=precision, w_blk=w_blk)


def _mec_bwd(s_h, s_w, variant, _solution, interpret, precision, w_blk,
             res, g):
    # The nondiff args arrive positionally.  solution shapes the forward
    # lowering only; the variant and the forward's w_blk pick where each
    # gradient runs, the same mathematics either way.
    inp, kernel = res
    spec = spec_of(inp, kernel, (s_h, s_w))
    fused = variant == "mec_fused"
    with jax.named_scope("mec_input_grad"):
        if fused and fused_input_grad_refusal(spec, g.dtype) is None:
            d_inp = _mec_fused_input_grad(g, kernel, interpret, precision)
        else:
            d_inp = _mec_input_grad(g, kernel, s_h, s_w, inp.shape[1],
                                    inp.shape[2], precision)
    with jax.named_scope("mec_weight_grad"):
        from repro.kernels.mec_conv import fused_weight_grad_refusal
        if fused and fused_weight_grad_refusal(spec, g.dtype, w_blk) is None:
            from repro.kernels.ops import mec_weight_grad_tpu
            d_ker = mec_weight_grad_tpu(inp, g, kernel.shape[0],
                                        kernel.shape[1], (s_h, s_w),
                                        interpret, precision, w_blk)
        else:
            d_ker = _mec_weight_grad(inp, g, s_h, s_w, kernel.shape[0],
                                     kernel.shape[1], precision)
    return d_inp.astype(inp.dtype), d_ker.astype(kernel.dtype)


_mec_conv.defvjp(_mec_fwd, _mec_bwd)


# ---------------------------------------------------------------------------
# public dispatch
# ---------------------------------------------------------------------------

def _dispatch(x: jnp.ndarray, kernel: jnp.ndarray, spec: ConvSpec,
              s_h: int, s_w: int, algorithm: str, solution: str,
              interpret: Optional[bool], precision,
              w_blk: Optional[int]) -> jnp.ndarray:
    """Single-device execution of a *resolved* algorithm on the
    pre-padded input — the executor core shared by the kwargs path and
    ``conv2d(plan=)``."""
    if algorithm == "direct":
        return direct_conv2d(x, kernel, (s_h, s_w), precision=precision)
    if algorithm == "im2col":
        return im2col_conv2d(x, kernel, (s_h, s_w), precision=precision)
    if algorithm == "fft":
        return fft_conv2d(x, kernel, (s_h, s_w), precision=precision)
    if algorithm == "winograd":
        if (spec.k_h, spec.k_w, s_h, s_w) != (3, 3, 1, 1):
            raise ValueError(
                "winograd F(2x2,3x3) requires a 3x3 kernel and stride 1; "
                f"got kernel {(spec.k_h, spec.k_w)} stride {(s_h, s_w)}")
        return winograd_conv2d(x, kernel, precision=precision)
    return _mec_conv(x, kernel, s_h, s_w, algorithm, solution, interpret,
                     precision, w_blk)


def conv2d(inp: jnp.ndarray, kernel: jnp.ndarray, *, stride=1,
           padding: Padding = "VALID", algorithm: str = "auto",
           solution: str = "auto", interpret: Optional[bool] = None,
           precision=None,
           partition: Union[str, Tuple[str, ...], None] = None,
           partition_axis: Union[str, Tuple[str, ...], None] = None,
           plan: Optional["ConvPlan"] = None) -> jnp.ndarray:
    """2-D convolution, NHWC x HWIO -> NHWC.

    inp: (i_n, i_h, i_w, i_c); kernel: (k_h, k_w, i_c, k_c).
    stride: int or (s_h, s_w).  padding: 'SAME' | 'VALID' | int |
    ((lo, hi), (lo, hi)).  algorithm: one of :data:`ALGORITHMS`.
    solution: MEC Solution 'A' | 'B' | 'auto' (reference path only).
    interpret: force Pallas interpret mode (None = auto: interpret
    everywhere but real TPU).  All MEC algorithms are differentiable via
    the shared custom VJP.

    plan: a resolved :class:`repro.plan.ConvPlan` (DESIGN.md §7).  When
    given, the plan's decision fields — algorithm, solution, precision,
    Pallas ``w_blk``, partition + mesh axes — *win over the kwargs*;
    only the geometry kwargs (stride, padding) remain the caller's and
    must reproduce ``plan.spec`` exactly (mismatch raises).  Without a
    plan, ``algorithm="auto"`` resolves through the plan cache
    (``repro.plan.resolve_cached_plan``: process LRU -> on-disk JSON ->
    analytic costmodel), so repeated shapes reuse one decision.

    partition routes through the distributed layer
    (``repro.parallel.conv.sharded_conv2d``, DESIGN.md §6):
    'batch' | 'channel' | 'spatial' | a composite 2-tuple from
    ``parallel.conv.COMPOSITE_PARTITIONS`` (e.g. ``("batch", "spatial")``
    on a ``data x model`` mesh) | 'auto' split over the installed
    ``parallel.axes`` mesh (no mesh -> single-device no-op); 'none'
    forces single-device; None (default) is rules-aware — sharded 'auto'
    exactly when ``parallel.axes.use_rules`` rules are installed (1-D
    and composite candidates both enumerated by the cost model), so the
    same model code runs on a laptop and a pod.  partition_axis names the
    mesh axis explicitly (a tuple, paired positionally, for composites).
    """
    with jax.named_scope("conv2d"):
        if plan is not None:
            return _execute_plan(inp, kernel, plan, stride=stride,
                                 padding=padding, interpret=interpret)

        if partition != "none":
            # Lazy import: parallel sits above core; call-time routing keeps
            # core import-clean (mirrors the plan/costmodel imports below).
            from repro.parallel.axes import current_rules
            if partition is not None or current_rules() is not None:
                from repro.parallel.conv import sharded_conv2d
                return sharded_conv2d(
                    inp, kernel, stride=stride, padding=padding,
                    algorithm=algorithm, solution=solution,
                    partition=partition or "auto", axis=partition_axis,
                    interpret=interpret, precision=precision)

        s_h, s_w = normalize_stride(stride)
        k_h, k_w = kernel.shape[0], kernel.shape[1]
        x = apply_padding(inp, k_h, k_w, s_h, s_w, padding)
        spec = spec_of(x, kernel, (s_h, s_w))

        algorithm = algorithm.lower()
        if algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {algorithm!r}; expected "
                             f"one of {ALGORITHMS}")
        w_blk = None
        if algorithm == "auto":
            # Bare kwargs resolve through the plan cache (DESIGN.md §7):
            # process LRU -> on-disk JSON -> the analytic costmodel pick the
            # pre-planner dispatch made.  Lazy import: plan sits above core.
            from repro.plan import resolve_cached_plan
            cached = resolve_cached_plan(spec, dtype=x.dtype)
            algorithm = cached.algorithm
            w_blk = cached.w_blk
        return _dispatch(x, kernel, spec, s_h, s_w, algorithm, solution,
                         interpret, precision, w_blk)


def _execute_plan(inp: jnp.ndarray, kernel: jnp.ndarray, plan: "ConvPlan",
                  *, stride, padding: Padding,
                  interpret: Optional[bool]) -> jnp.ndarray:
    """Execute exactly the decision a :class:`repro.plan.ConvPlan`
    captured.  The caller's geometry (stride/padding/shapes) must
    reproduce ``plan.spec``; every decision field comes from the plan."""
    s_h, s_w = normalize_stride(stride)
    k_h, k_w = kernel.shape[0], kernel.shape[1]
    x = apply_padding(inp, k_h, k_w, s_h, s_w, padding)
    spec = spec_of(x, kernel, (s_h, s_w))
    plan.check_executable(spec, x.dtype)
    if plan.partition is not None:
        # The plan already holds the partition decision (components +
        # mesh axes); the distributed layer executes it without
        # re-enumerating candidates.  w_blk is not forwarded: the
        # per-device body sees a *local* geometry the global block was
        # not picked for, so it re-derives its own (DESIGN.md §7).
        from repro.parallel.conv import sharded_conv2d
        return sharded_conv2d(
            x, kernel, stride=(s_h, s_w), padding="VALID",
            algorithm=plan.algorithm, solution=plan.solution,
            partition=plan.partition, axis=plan.partition_axes,
            interpret=interpret, precision=plan.precision_value())
    return _dispatch(x, kernel, spec, s_h, s_w, plan.algorithm,
                     plan.solution, interpret, plan.precision_value(),
                     plan.w_blk)


def conv2d_spec(inp: jnp.ndarray, kernel: jnp.ndarray, *, stride=1,
                padding: Padding = "VALID") -> ConvSpec:
    """The post-padding ConvSpec ``conv2d`` would dispatch on (for cost
    and memory accounting — and planning — without running the conv)."""
    s_h, s_w = normalize_stride(stride)
    x = jax.eval_shape(
        lambda a: apply_padding(a, kernel.shape[0], kernel.shape[1],
                                s_h, s_w, padding), inp)
    i_n, i_h, i_w, i_c = x.shape
    return ConvSpec(i_n, i_h, i_w, i_c, kernel.shape[0], kernel.shape[1],
                    kernel.shape[3], s_h, s_w)
