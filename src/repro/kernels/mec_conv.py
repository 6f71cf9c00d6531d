"""The Pallas TPU kernel for MEC convolution (Cho & Brand, ICML 2017).

TPU adaptation (see DESIGN.md §2): the paper's BLAS ``ld``-aliased
overlapping sub-matrix views become windows of the input placed by
element, and its k_h decomposition::

    O[n, h, :, :] = sum_{r=0}^{k_h-1}  L[n, :, h*s_h + r, :] @ K[r]

runs inside one kernel, ``mec_fused``: the lowering happens in VMEM
inside the GEMM pipeline and L never exists in HBM.  A grid step takes a
block of output rows (whole planes of several images where planes are
small) and runs each kernel tap as one GEMM over the whole block; HBM
traffic is I (1 + halo/rows a step) + K + O.  The paper's lowering with
L in HBM is the pure-JAX reference, ``repro.core.mec``.

The blocking is :func:`fused_blocks`, and :func:`fused_refusal` is the
one rule for the geometries the kernel does not take.  The weight
gradient of such a conv runs on ``mec_fused_wgrad``, which walks the
same blocks and folds and builds the same windows in VMEM, summing each
tap's window against the cotangent; :func:`fused_weight_grad_refusal`
says where its working set does not fit.  Every tap accumulates in f32
regardless of input dtype.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class FusedBlocks:
    """How ``mec_fused`` tiles one convolution, derived from its shapes.

    The fields describe the problem the kernel sees after the wrapper's
    folds: an input of unit width is squeezed (its height runs as the
    width), the width stride is folded into channels (``k_q`` taps over
    ``c2`` channels) and the height stride into a phase axis, so the
    input is ``(i_n, i_h2, s_h, i_w2, c2)``.  A grid step takes ``nb``
    images, ``hb`` output rows, ``w_blk`` output columns and ``kc``
    output channels; it walks the block in chunks of ``ib`` images by
    ``hc`` rows and runs every kernel tap of a chunk as one dot of
    ``dot_rows`` rows."""

    squeeze: bool
    i_n: int
    o_h: int
    o_w: int
    k_h: int
    s_h: int
    k_q: int
    c2: int
    k_c: int
    w_blk: int         # output columns a step computes
    nb: int            # images a step
    hb: int            # output rows a step
    kc: int            # output channels a step
    ib: int            # images a chunk: one dot's share of the step
    hc: int            # output rows a chunk
    itemsize: int
    useful_macs: int   # i_n * o_h * o_w * k_h * k_w * i_c * k_c

    @property
    def i_h2(self) -> int:
        """Folded input rows: the last output row's window ends there."""
        return self.o_h + (self.k_h - 1) // self.s_h

    @property
    def i_w2(self) -> int:
        return self.o_w + self.k_q - 1

    @property
    def cols_in(self) -> int:
        """Folded input columns a step reads: its ``w_blk`` plus the
        halo, rounded up to the sublane tile as Mosaic's blocks are."""
        return _round_up(self.w_blk + self.k_q - 1,
                         _sublane_tile(self.itemsize))

    @property
    def rows_in(self) -> int:
        """Folded input rows a step reads: its ``hb`` plus the halo."""
        return self.hb + (self.k_h - 1) // self.s_h

    @property
    def grid(self) -> Tuple[int, int, int, int]:
        """(output-channel blocks, image blocks, row blocks, column
        blocks); the weights change only with the outermost."""
        return (self.k_c // self.kc, -(-self.i_n // self.nb),
                -(-self.o_h // self.hb), -(-self.o_w // self.w_blk))

    @property
    def out_extent(self) -> Tuple[int, int, int]:
        """(images, rows, columns) the grid's output blocks cover."""
        _, n_b, n_h, n_w = self.grid
        return n_b * self.nb, n_h * self.hb, n_w * self.w_blk

    @property
    def window_extent(self) -> Tuple[int, int, int]:
        """(images, folded rows, folded columns) the grid's input windows
        reach: past the folded input wherever the last blocks run past
        the output."""
        n, rows, _ = self.out_extent
        return (n, rows + (self.k_h - 1) // self.s_h,
                (self.grid[3] - 1) * self.w_blk + self.cols_in)

    @property
    def steps(self) -> int:
        return math.prod(self.grid)

    @property
    def chunks(self) -> int:
        """Chunks a step walks, one after another."""
        return (self.nb // self.ib) * (self.hb // self.hc)

    @property
    def dot_rows(self) -> int:
        """M of each tap's dot: images x rows x columns of a chunk."""
        return self.ib * self.hc * self.w_blk

    @property
    def padded_flop_share(self) -> float:
        """Share of the kernel's multiply-adds that no output needs:
        padded columns, zero taps of the width fold, and the rows and
        images of last blocks that run past the array."""
        done = (self.steps * self.nb * self.hb * self.w_blk * self.k_h
                * self.k_q * self.c2 * self.kc)
        return 1.0 - self.useful_macs / done

    def block_bytes(self) -> dict:
        """VMEM bytes of each block and temporary of one step, in the
        (sublane, 128-lane) tiles Mosaic lays them out in."""
        tile = _sublane_tile(self.itemsize)
        db = self.itemsize
        c2 = _round_up(self.c2, _LANES)
        kc = _round_up(self.kc, _LANES)
        wb = _round_up(self.w_blk, tile)
        return {
            "input": self.nb * self.rows_in * self.s_h * self.cols_in
            * c2 * db,
            "kernel": self.k_h * self.k_q * _round_up(self.c2, tile) * kc
            * db,
            "output": self.nb * self.hb * wb * kc * db,
            "acc": self.ib * self.hc * wb * kc * 4,
            "window": self.ib * self.hc * wb * c2 * db,
            "dot": self.ib * self.hc * wb * kc * 4,
        }

    @property
    def vmem_bytes(self) -> int:
        """Per-step working set: the three block streams double-buffered
        plus a chunk's accumulator and a tap's window and product."""
        b = self.block_bytes()
        return (2 * (b["input"] + b["kernel"] + b["output"]) + b["acc"]
                + b["window"] + b["dot"])

    @property
    def weight_grad_vmem_bytes(self) -> int:
        """Per-step working set of the weight-gradient kernel on these
        blocks: the input and cotangent (the output's block) streams
        double-buffered, the f32 dW block (resident across the reduction
        axes; Mosaic gives a block whose index never changes one buffer,
        else two), and a chunk's cotangent, its transpose, a tap's window
        and their f32 product."""
        b = self.block_bytes()
        tile = _sublane_tile(self.itemsize)
        c2 = _round_up(self.c2, _LANES)
        kc = _round_up(self.kc, _LANES)
        dw = self.k_h * self.k_q * _round_up(self.kc, 8) * c2 * 4
        cot = self.ib * self.hc * _round_up(self.w_blk, tile) * kc
        lhs = _round_up(self.kc, tile) * _round_up(self.dot_rows, _LANES)
        window = _round_up(self.dot_rows, tile) * c2
        return (2 * (b["input"] + b["output"])
                + (2 if self.grid[0] > 1 else 1) * dw
                + (cot + lhs + window) * self.itemsize
                + _round_up(self.kc, 8) * c2 * 4)

    def describe(self) -> str:
        return (f"{self.nb} image(s) x {self.hb} row(s) x {self.w_blk} "
                f"col(s) x {self.kc} channel(s) a step, grid {self.grid} "
                f"= {self.steps} steps, {self.chunks} chunk(s) of "
                f"{self.ib} x {self.hc} a step, dot M={self.dot_rows}, padded "
                f"FLOPs {100 * self.padded_flop_share:.1f}%"
                + (", unit width squeezed" if self.squeeze else ""))


_LANES = 128
# Output pixels of a chunk, the M of its dots.  Mosaic unrolls a dot into
# one instruction per vector register, and the executable, which the
# device holds in HBM, carries the unrolled body: chunks keep it small
# while each dot still streams several 128-row passes through the MXU.
_DOT_ROWS = 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublane_tile(itemsize: int) -> int:
    """Rows of one (sublane, 128) tile: 8 for f32, 16 for bf16."""
    return max(8, 32 // itemsize)


def fused_blocks(i_n: int, i_h: int, i_w: int, i_c: int, k_h: int,
                 k_w: int, k_c: int, s_h: int = 1, s_w: int = 1,
                 w_blk: int | None = None, itemsize: int = 4, *,
                 acc_budget: int | None = None,
                 vmem_budget: int | None = None) -> FusedBlocks:
    """The blocking ``mec_conv_fused_pallas`` runs a geometry with.

    ``w_blk`` is the planner's output-column block; a row that fits one
    block is computed whole, its width rounded up to the sublane tile.
    Where the input has unit width, or ``w_blk`` is None, the
    accumulator budget sizes the column block.  Output channels are
    blocked only where the double-buffered kernel would fill more than a
    quarter of VMEM.  Rows, then images, fill the f32 accumulator up to
    :func:`repro.kernels.ops.accumulator_budget`; images, rows, then the
    picker's own columns shrink while the step's working set overruns
    :func:`repro.kernels.ops.vmem_bytes`, and each block is then evened
    out over its grid axis.  A step walks its block in chunks of whole
    images, or of one image's rows, of up to ``_DOT_ROWS`` pixels."""
    if acc_budget is None or vmem_budget is None:
        from repro.kernels import ops
        if acc_budget is None:
            acc_budget = ops.accumulator_budget(_warn_env=False)
        if vmem_budget is None:
            vmem_budget = ops.vmem_bytes()
    o_h = (i_h - k_h) // s_h + 1
    o_w = (i_w - k_w) // s_w + 1
    useful = i_n * o_h * o_w * k_h * k_w * i_c * k_c
    squeeze = i_w == 1 and o_h > 1
    if squeeze:                 # conv1d as (time, 1): time becomes width
        i_h, i_w, k_h, k_w, s_h, s_w = i_w, i_h, k_w, k_h, s_w, s_h
        o_h, o_w, w_blk = o_w, o_h, None
    tile = _sublane_tile(itemsize)
    k_q = -(-k_w // s_w)
    c2 = s_w * i_c

    def kernel_bytes(kc):
        return (2 * k_h * k_q * _round_up(c2, tile) * _round_up(kc, _LANES)
                * itemsize)

    kc = k_c
    if kernel_bytes(k_c) > vmem_budget // 4:
        splits = [d for d in range(_LANES, k_c, _LANES) if k_c % d == 0]
        fits = [d for d in splits if kernel_bytes(d) <= vmem_budget // 4]
        kc = max(fits) if fits else min(splits, default=k_c)
    acc_rows = max(1, acc_budget // (4 * _round_up(kc, _LANES)))
    own_cols = w_blk is None
    if own_cols:
        w_blk = o_w if o_w <= acc_rows else max(tile, acc_rows // tile * tile)

    def blocks(nb, hb, wb):
        if wb >= o_w:
            wb = _round_up(o_w, tile)
        # a chunk: whole images of the block up to _DOT_ROWS rows, else
        # a run of one image's rows
        ib = max(d for d in range(1, nb + 1)
                 if nb % d == 0 and (d == 1 or d * hb * wb <= _DOT_ROWS))
        hc = hb if ib > 1 else max(
            d for d in range(1, hb + 1)
            if hb % d == 0 and (d == 1 or d * wb <= _DOT_ROWS))
        return FusedBlocks(
            squeeze=squeeze, i_n=i_n, o_h=o_h, o_w=o_w, k_h=k_h, s_h=s_h,
            k_q=k_q, c2=c2, k_c=k_c, w_blk=wb, nb=nb, hb=hb, kc=kc,
            ib=ib, hc=hc, itemsize=itemsize, useful_macs=useful)

    wb = blocks(1, 1, w_blk).w_blk
    if o_h * wb <= acc_rows:
        nb, hb = max(1, min(i_n, acc_rows // (o_h * wb))), o_h
    else:
        nb, hb = 1, max(1, min(o_h, acc_rows // wb))
    while blocks(nb, hb, w_blk).vmem_bytes > vmem_budget:
        if nb > 1:
            nb = -(-nb // 2)
        elif hb > 1:
            hb = -(-hb // 2)
        elif own_cols and w_blk > tile:
            w_blk = _round_up(-(-min(w_blk, o_w) // 2), tile)
        else:
            break

    def even(n, blk):
        return -(-n // -(-n // blk))

    if own_cols and w_blk < o_w:
        w_blk = _round_up(even(o_w, w_blk), tile)
    return blocks(even(i_n, nb), even(o_h, hb), w_blk)


# Fewer input channels than this leave the kernel's per-tap GEMMs nearly
# empty (a contraction over i_c x s_w of the MXU's 128 rows).  On a TPU
# v5e at batch 32 in bfloat16, XLA's conv ran every 3-channel conv timed
# (the ResNet stem, 7x7/2 at 224 px; MEC Table 2's cv1, cv3 and cv7)
# 3.1-5.2x faster forward and 4.5-6.3x faster with its gradients.  The
# rule stops at the width timed; wider inputs keep the kernel.
FUSED_MIN_CHANNELS = 4


# A strided conv whose width fold leaves fewer column taps than this
# (ResNet's 3x3/2 convs: 2 taps over 2 x i_c channels, a quarter of them
# zero) ran its weight gradient slower on ``mec_fused_wgrad`` than on
# XLA's ``_mec_weight_grad``.  On a TPU v5e at batch 32 in bfloat16 (host
# clock, dispatch included), 3x3/2 at 56, 28 and 14 px read 0.255, 0.235
# and 0.280 ms on the kernel against 0.207, 0.197 and 0.211 on XLA, while
# cv4 (7x7/2, 4 taps) read 4.73 ms against 11.35.  The rule stops at the
# geometries timed; stride-1 convs keep the kernel.
WGRAD_MIN_STRIDED_TAPS = 3


def fused_refusal(spec, dtype, w_blk: int | None = None) -> str | None:
    """Why ``mec_fused`` does not take the conv ``spec`` in ``dtype``, or
    None where it does.

    A grouped conv (``spec.groups > 1``) is refused first, with or
    without a block: the kernel's GEMMs contract over all of ``i_c``.
    Without ``w_blk`` this is the TPU pick's rule, at the planner's block
    (:func:`repro.kernels.ops.pick_w_blk`): a 1x1 kernel, whose lowering
    is a no-op, and an input of fewer than ``FUSED_MIN_CHANNELS``
    channels run faster on XLA's conv.  With a ``w_blk``, the question is
    only whether the kernel runs that block; a measured plan may time
    the kernel where the pick would not take it.  The limits are read
    from the :class:`FusedBlocks` the kernel runs with: the block inside
    ``[1, o_w]``, block starts on whole sublane tiles where a row takes
    several blocks, the step's working set inside
    :func:`~repro.kernels.ops.vmem_bytes` and a chunk's f32 sums inside
    :func:`~repro.kernels.ops.accumulator_budget`."""
    from repro.kernels import ops
    if spec.groups > 1:
        return (f"groups={spec.groups}: each tap's GEMM contracts over all "
                "of i_c, so the kernel runs dense convs only")
    if w_blk is None:
        if spec.k_h == 1 and spec.k_w == 1:
            return "1x1 kernel: the lowering is a no-op, XLA's conv wins"
        if spec.i_c < FUSED_MIN_CHANNELS:
            return (f"{spec.i_c} input channel(s), under "
                    f"{FUSED_MIN_CHANNELS}: each tap's GEMM contracts over "
                    "i_c x s_w lanes, and XLA's conv runs such convs "
                    "several times faster")
        w_blk = ops.pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)
    if not 1 <= w_blk <= max(spec.o_w, 1):
        return f"w_blk={w_blk} outside [1, o_w={spec.o_w}]"
    itemsize = np.dtype(dtype).itemsize
    vmem = ops.vmem_bytes()
    acc = ops.accumulator_budget(_warn_env=False)
    fb = fused_blocks(spec.i_n, spec.i_h, spec.i_w, spec.i_c, spec.k_h,
                      spec.k_w, spec.k_c, spec.s_h, spec.s_w, w_blk,
                      itemsize, acc_budget=acc, vmem_budget=vmem)
    tile = _sublane_tile(itemsize)
    n_w = fb.grid[3]
    if n_w > 1 and fb.w_blk % tile:
        return (f"w_blk={fb.w_blk} splits o_w={fb.o_w} into {n_w} blocks "
                f"whose starts are not sublane ({tile}) aligned")
    if fb.vmem_bytes > vmem:
        return (f"a step's working set, {fb.vmem_bytes} B, exceeds VMEM "
                f"{vmem} B")
    if fb.dot_rows * fb.kc * 4 > acc:
        return (f"a chunk's f32 sums, {fb.dot_rows} x {fb.kc}, exceed the "
                f"accumulator budget {acc} B")
    return None


def fused_weight_grad_refusal(spec, dtype,
                              w_blk: int | None = None) -> str | None:
    """Why the weight gradient of a conv that ran forward on ``mec_fused``
    at column block ``w_blk`` (None: the planner's
    :func:`~repro.kernels.ops.pick_w_blk`) does not run on
    ``mec_fused_wgrad``, or None where it does.  The kernel walks the
    forward's own :class:`FusedBlocks`, so the forward must run them; its
    step then also holds the f32 dW block of the step's output channels,
    and the working set (:attr:`FusedBlocks.weight_grad_vmem_bytes`) must
    fit :func:`~repro.kernels.ops.vmem_bytes`.  A strided conv folded to
    fewer than ``WGRAD_MIN_STRIDED_TAPS`` column taps keeps XLA."""
    from repro.kernels import ops
    if w_blk is None:
        w_blk = ops.pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)
    why = fused_refusal(spec, dtype, w_blk)
    if why is not None:
        return f"the forward's blocking is refused: {why}"
    vmem = ops.vmem_bytes()
    fb = fused_blocks(spec.i_n, spec.i_h, spec.i_w, spec.i_c, spec.k_h,
                      spec.k_w, spec.k_c, spec.s_h, spec.s_w, w_blk,
                      np.dtype(dtype).itemsize, vmem_budget=vmem)
    if max(spec.s_h, spec.s_w) > 1 and fb.k_q < WGRAD_MIN_STRIDED_TAPS:
        return (f"stride {spec.s_h}x{spec.s_w} folds {spec.k_w} kernel "
                f"columns into {fb.k_q} taps: XLA's weight gradient wins "
                f"under {WGRAD_MIN_STRIDED_TAPS}")
    if fb.weight_grad_vmem_bytes > vmem:
        return (f"a step's working set with the f32 dW block "
                f"{fb.k_h}x{fb.k_q}x{fb.c2}x{fb.kc}, "
                f"{fb.weight_grad_vmem_bytes} B, exceeds VMEM {vmem} B")
    return None


def _fused_kernel(x_ref, k_ref, o_ref, acc_ref, *, fb: FusedBlocks,
                  precision):
    # x_ref: (nb, rows_in, s_h, cols_in, c2) — the step's images, folded
    #        input rows and columns, each with its halo
    # k_ref: (k_h, k_q, c2, kc); o_ref: (nb, hb, w_blk, kc)
    # acc_ref: (ib * hc * w_blk, kc) f32 — one chunk's sums
    ib, hc, wb = fb.ib, fb.hc, fb.w_blk
    per_image = fb.hb // hc

    def chunk(j, carry):
        i0, h0 = (j // per_image) * ib, (j % per_image) * hc
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def kernel_row(r, c):
            # Output row h reads input row h*s_h + r = folded row h + t
            # of phase p: the tap's rows are a unit-stride window.
            t, p = r // fb.s_h, r % fb.s_h
            part = None
            for q in range(fb.k_q):
                lhs = x_ref[pl.ds(i0, ib), pl.ds(h0 + t, hc), p,
                            q:q + wb, :]
                prod = jnp.dot(lhs.reshape(ib * hc * wb, fb.c2),
                               k_ref[r, q], precision=precision,
                               preferred_element_type=jnp.float32)
                part = prod if part is None else part + prod
            acc_ref[...] += part
            return c

        lax.fori_loop(0, fb.k_h, kernel_row, 0)
        o_ref[pl.ds(i0, ib), pl.ds(h0, hc)] = acc_ref[...].reshape(
            ib, hc, wb, o_ref.shape[-1]).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, fb.chunks, chunk, 0)


def _weight_grad_kernel(x_ref, g_ref, o_ref, *, fb: FusedBlocks,
                        precision):
    # x_ref: the forward's input block; g_ref: (nb, hb, w_blk, kc), the
    #        cotangent of the forward's output block
    # o_ref: (k_h, k_q, kc, c2) f32 — dW of the step's output channels,
    #        transposed, resident across the image, row and column axes
    ib, hc, wb, kc = fb.ib, fb.hc, fb.w_blk, fb.kc
    per_image = fb.hb // hc

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0)
             & (pl.program_id(3) == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def chunk(j, carry):
        i0, h0 = (j // per_image) * ib, (j % per_image) * hc
        # The chunk's cotangent, transposed once for all its taps.
        g_t = g_ref[pl.ds(i0, ib), pl.ds(h0, hc)].reshape(fb.dot_rows, kc).T

        def kernel_row(r, c):
            t, p = r // fb.s_h, r % fb.s_h
            for q in range(fb.k_q):
                win = x_ref[pl.ds(i0, ib), pl.ds(h0 + t, hc), p,
                            q:q + wb, :]
                o_ref[r, q] += jnp.dot(
                    g_t, win.reshape(fb.dot_rows, fb.c2),
                    precision=precision, preferred_element_type=jnp.float32)
            return c

        lax.fori_loop(0, fb.k_h, kernel_row, 0)
        return carry

    lax.fori_loop(0, fb.chunks, chunk, 0)


def _fold_input(inp: jnp.ndarray, fb: FusedBlocks,
                extent: Tuple[int, int, int]) -> jnp.ndarray:
    """The input as the kernels read it, ``(images, rows, s_h, cols,
    c2)`` for ``extent`` = (images, folded rows, folded columns): a unit
    width squeezed, the width stride folded into channels and the height
    stride into a phase axis.  Rows and columns past the extent feed no
    window and are cut; missing ones, and missing images, are zeros."""
    i_n, i_h, _, i_c = inp.shape
    if fb.squeeze:
        inp = inp.reshape(i_n, 1, i_h, i_c)
    n, rows, cols = extent
    need_h, need_w = rows * fb.s_h, cols * (fb.c2 // i_c)
    inp = inp[:, :need_h, :need_w, :]
    if n > i_n or need_h > inp.shape[1] or need_w > inp.shape[2]:
        inp = jnp.pad(inp, ((0, n - i_n), (0, need_h - inp.shape[1]),
                            (0, need_w - inp.shape[2]), (0, 0)))
    # The width fold first, materialized on its own: XLA then lays it out
    # as the plain (rows, cols, channels) fold does, and the split into
    # phases is free.  Folded in one reshape, it went through a
    # lane-padded copy of the whole input.
    x4 = lax.optimization_barrier(inp.reshape(n, need_h, cols, fb.c2))
    return x4.reshape(n, rows, fb.s_h, cols, fb.c2)


def _input_spec(fb: FusedBlocks, shape) -> pl.BlockSpec:
    """The step's input window: windows overlap by the halo, so they are
    placed by element; where the folded input ``shape`` ends before the
    grid's windows do, the last ones run past it."""
    n, rows, cols = fb.window_extent

    def el(size, over=0):
        return pl.Element(size, (0, over))

    return pl.BlockSpec(
        (el(fb.nb, n - shape[0]), el(fb.rows_in, rows - shape[1]),
         el(fb.s_h), el(fb.cols_in, cols - shape[3]), el(fb.c2)),
        lambda c, b, h, w: (b * fb.nb, h * fb.hb, 0, w * fb.w_blk, 0))


def _out_spec(fb: FusedBlocks) -> pl.BlockSpec:
    """The step's output block, and in the weight gradient the block of
    the cotangent it reads."""
    return pl.BlockSpec((fb.nb, fb.hb, fb.w_blk, fb.kc),
                        lambda c, b, h, w: (b, h, w, c))


@functools.partial(jax.jit,
                   static_argnames=("stride", "w_blk", "interpret",
                                    "precision"))
def mec_conv_fused_pallas(inp: jnp.ndarray, kernel: jnp.ndarray, stride=1,
                          w_blk: int = 128,
                          interpret: bool = True,
                          precision=None) -> jnp.ndarray:
    """Fused MEC convolution: implicit lowering inside the GEMM pipeline.

    inp: (n, i_h, i_w, i_c) pre-padded; kernel: (k_h, k_w, i_c, k_c).
    Returns (n, o_h, o_w, k_c) in inp.dtype: every tap's product sums in
    an f32 VMEM accumulator, rounded once to the output dtype.

    The strides are folded out here.  Width (space-to-depth): input
    column ``m*s_w + p`` becomes column ``m``, channel block ``p``, and
    the kernel's k_w taps regroup into ``k_q = ceil(k_w/s_w)`` unit-
    stride taps over ``s_w*i_c`` channels (taps past k_w are zero).
    Height: row ``m*s_h + p`` becomes row ``m`` of phase ``p``.  Tap
    ``(r, q)`` of output row ``h``, column ``w`` then reads folded row
    ``h + r // s_h`` of phase ``r % s_h``, column ``w + q``: in VMEM a
    unit-stride window of the step's block, so each tap is one GEMM over
    a chunk of the step's images, rows and columns (MEC's k_h
    decomposition ``O[h] = sum_r L[h*s_h + r] @ K[r]``, with L built in
    VMEM).  An input of unit width runs with its height as the width.
    The blocking is :func:`fused_blocks`; the input windows overlap by
    the row halo, and the last ones run past the array into outputs that
    are cropped or never written.
    """
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    i_n, i_h, i_w, i_c = inp.shape
    k_h, k_w, _, k_c = kernel.shape
    o_h = (i_h - k_h) // s_h + 1
    o_w = (i_w - k_w) // s_w + 1
    fb = fused_blocks(i_n, i_h, i_w, i_c, k_h, k_w, k_c, s_h, s_w,
                      min(w_blk, o_w), inp.dtype.itemsize)
    with jax.named_scope("mec_fold"):
        # Rows and columns past the last window feed no output; missing
        # ones (fewer than a stride) only meet zero taps.
        x5 = _fold_input(inp, fb, (i_n, fb.i_h2, fb.i_w2))
        if fb.squeeze:
            kernel = kernel.reshape(1, k_h, i_c, k_c)
            k_h, k_w, s_w = 1, k_h, s_h
        k2 = jnp.pad(kernel, ((0, 0), (0, fb.k_q * s_w - k_w), (0, 0),
                              (0, 0)))
        k2 = k2.reshape(k_h, fb.k_q, fb.c2, k_c)
    # The last input windows run past the array, into rows, images and
    # columns whose outputs are cropped or never written.
    out = pl.pallas_call(
        functools.partial(_fused_kernel, fb=fb, precision=precision),
        name="mec_fused",
        grid=fb.grid,
        in_specs=[
            _input_spec(fb, x5.shape),
            pl.BlockSpec((k_h, fb.k_q, fb.c2, fb.kc),
                         lambda c, b, h, w: (0, 0, 0, c)),
        ],
        out_specs=_out_spec(fb),
        out_shape=jax.ShapeDtypeStruct((i_n, fb.o_h, fb.out_extent[2], k_c),
                                       inp.dtype),
        scratch_shapes=[pltpu.VMEM((fb.dot_rows, fb.kc), jnp.float32)],
        interpret=interpret,
    )(x5, k2)
    with jax.named_scope("conv2d_out"):
        out = out[:, :, :fb.o_w, :]
        if fb.squeeze:
            out = out.reshape(i_n, fb.o_w, 1, k_c)
        return out


@functools.partial(jax.jit,
                   static_argnames=("k_h", "k_w", "stride", "w_blk",
                                    "interpret", "precision"))
def mec_weight_grad_pallas(inp: jnp.ndarray, g: jnp.ndarray, k_h: int,
                           k_w: int, stride=1, w_blk: int = 128,
                           interpret: bool = True,
                           precision=None) -> jnp.ndarray:
    """dL/dK of the conv :func:`mec_conv_fused_pallas` runs at ``w_blk``,
    from its pre-padded input ``inp`` (n, i_h, i_w, i_c) and the
    cotangent ``g`` (n, o_h, o_w, k_c) of its output, in the operands'
    dtype.  Returns (k_h, k_w, i_c, k_c) in f32.

    The kernel, ``mec_fused_wgrad``, walks the forward's blocks and
    folds (:func:`fused_blocks`): each step reads the forward's input
    window and the cotangent of the forward's output block, and each
    tap ``(r, q)`` of a chunk is one GEMM of the chunk's cotangent,
    transposed, against the tap's window, summed in an f32 block of the
    step's output channels that stays in VMEM across the image, row and
    column axes.  L never exists in HBM.  The input is
    zero-padded to the windows' extent and the cotangent to the output
    blocks', so pixels past the conv's output add nothing.
    """
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    i_n, i_h, i_w, i_c = inp.shape
    k_c = g.shape[3]
    o_w = (i_w - k_w) // s_w + 1
    fb = fused_blocks(i_n, i_h, i_w, i_c, k_h, k_w, k_c, s_h, s_w,
                      min(w_blk, o_w), inp.dtype.itemsize)
    n, rows, cols = fb.out_extent
    with jax.named_scope("mec_fold"):
        x5 = _fold_input(inp, fb, fb.window_extent)
        g = g.reshape(i_n, fb.o_h, fb.o_w, k_c)     # a squeezed unit width
        g = jnp.pad(g, ((0, n - i_n), (0, rows - fb.o_h),
                        (0, cols - fb.o_w), (0, 0)))
    dw = pl.pallas_call(
        functools.partial(_weight_grad_kernel, fb=fb, precision=precision),
        name="mec_fused_wgrad",
        grid=fb.grid,
        in_specs=[_input_spec(fb, x5.shape), _out_spec(fb)],
        out_specs=pl.BlockSpec((fb.k_h, fb.k_q, fb.kc, fb.c2),
                               lambda c, b, h, w: (0, 0, c, 0)),
        out_shape=jax.ShapeDtypeStruct((fb.k_h, fb.k_q, k_c, fb.c2),
                                       jnp.float32),
        interpret=interpret,
    )(x5, g)
    with jax.named_scope("mec_fold"):
        # (k_h, k_q, k_c, s_w' i_c) back to HWIO; the width fold's taps
        # past k_w belong to no weight and are cut.
        s_w = fb.c2 // i_c
        dw = dw.transpose(0, 1, 3, 2).reshape(fb.k_h, fb.k_q * s_w, i_c, k_c)
        return dw[:, :(k_h if fb.squeeze else k_w)].reshape(k_h, k_w, i_c,
                                                            k_c)
