"""Pallas TPU kernels for MEC convolution (Cho & Brand, ICML 2017).

TPU adaptation (see DESIGN.md §2): the paper's BLAS ``ld``-aliased
overlapping sub-matrix views become BlockSpec *index maps*.  The key
observation making the shifted-window GEMM expressible with non-overlapping
BlockSpec blocks is the k_h-decomposition::

    O[n, h, :, :] = sum_{r=0}^{k_h-1}  L[n, :, h*s_h + r, :] @ K[r]

In ``mec_gemm``, with block size 1 on the i_h axis of L, the index
``h*s_h + r`` is a plain block index — the grid dimension ``r`` walks the
kernel rows and the output block accumulates in VMEM.  ``mec_fused``
places overlapping row windows by element instead and walks the taps
inside a step.  Three kernels:

* ``mec_lower``    — Algorithm 2 lines 4-6 (build compact L in HBM).
* ``mec_gemm``     — the o_h shifted GEMMs over a materialized L
                     (paper-faithful mode: Eq. 3 memory is observable).
* ``mec_conv_fused`` — beyond-paper: lowering happens in VMEM inside the
                     GEMM pipeline, L never exists in HBM.  A grid step
                     takes a block of output rows (whole planes of
                     several images where planes are small) and runs
                     each kernel tap as one GEMM over the whole block;
                     HBM traffic is I (1 + halo/rows a step) + K + O, vs.
                     the lowered path's additional |L| write +
                     (k_h/s_h)|L| read.

All kernels accumulate in f32 regardless of input dtype.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Lowering kernel: I (n, i_h, i_w, i_c) -> L (n, o_w, i_h, k_w*i_c)
# ---------------------------------------------------------------------------

def _lower_kernel(i_ref, l_ref, *, k_w: int, s_w: int, o_w: int):
    # i_ref: (1, h_blk, i_w, i_c); l_ref: (1, o_w, h_blk, k_w*i_c)
    x = i_ref[0]  # (h_blk, i_w, i_c)
    h_blk, _, i_c = x.shape
    # Column-strip windows: strip[j] = x[:, j : j + s_w*o_w : s_w, :]
    cols = [
        lax.slice(x, (0, j, 0), (h_blk, j + s_w * (o_w - 1) + 1, i_c),
                  (1, s_w, 1))
        for j in range(k_w)
    ]
    strip = jnp.stack(cols, axis=2)            # (h_blk, o_w, k_w, i_c)
    strip = jnp.transpose(strip, (1, 0, 2, 3))  # (o_w, h_blk, k_w, i_c)
    l_ref[0] = strip.reshape(o_w, h_blk, k_w * i_c).astype(l_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k_w", "s_w", "h_blk", "interpret"))
def mec_lower_pallas(inp: jnp.ndarray, k_w: int, s_w: int,
                     h_blk: int = 8, interpret: bool = True) -> jnp.ndarray:
    """Compact MEC lowering on TPU.  Returns L (n, o_w, i_h, k_w*i_c)."""
    i_n, i_h, i_w, i_c = inp.shape
    o_w = (i_w - k_w) // s_w + 1
    h_blk = min(h_blk, i_h)
    pad_h = (-i_h) % h_blk
    if pad_h:
        inp = jnp.pad(inp, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
    i_h_p = i_h + pad_h
    grid = (i_n, i_h_p // h_blk)
    out = pl.pallas_call(
        functools.partial(_lower_kernel, k_w=k_w, s_w=s_w, o_w=o_w),
        name="mec_lower",
        grid=grid,
        in_specs=[pl.BlockSpec((1, h_blk, i_w, i_c), lambda n, h: (n, h, 0, 0))],
        out_specs=pl.BlockSpec((1, o_w, h_blk, k_w * i_c),
                               lambda n, h: (n, 0, h, 0)),
        out_shape=jax.ShapeDtypeStruct((i_n, o_w, i_h_p, k_w * i_c), inp.dtype),
        interpret=interpret,
    )(inp)
    return out[:, :, :i_h, :]


# ---------------------------------------------------------------------------
# Shifted GEMM kernel over materialized L (paper-faithful)
# ---------------------------------------------------------------------------

def _gemm_kernel(l_ref, k_ref, o_ref, *, precision):
    # l_ref: (1, w_blk, 1, kwic); k_ref: (1, kwic, k_c); o_ref: (1,1,w_blk,k_c)
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.dot(l_ref[0, :, 0, :], k_ref[0], precision=precision,
                  preferred_element_type=jnp.float32)
    o_ref[0, 0] += acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("k_h", "s_h", "w_blk", "interpret",
                                    "precision"))
def mec_gemm_pallas(low: jnp.ndarray, kernel_mat: jnp.ndarray,
                    k_h: int, s_h: int, w_blk: int = 128,
                    interpret: bool = True,
                    precision=None) -> jnp.ndarray:
    """The o_h shifted GEMMs:  O[n,h] = sum_r L[n,:,h*s_h+r,:] @ K[r].

    low: (n, o_w, i_h, k_w*i_c)  (from mec_lower_pallas)
    kernel_mat: (k_h, k_w*i_c, k_c)
    Returns O (n, o_h, o_w, k_c) f32.
    """
    i_n, o_w, i_h, kwic = low.shape
    _, _, k_c = kernel_mat.shape
    o_h = (i_h - k_h) // s_h + 1
    w_blk = min(w_blk, o_w)
    pad_w = (-o_w) % w_blk
    if pad_w:
        low = jnp.pad(low, ((0, 0), (0, pad_w), (0, 0), (0, 0)))
    o_w_p = o_w + pad_w
    grid = (i_n, o_h, o_w_p // w_blk, k_h)
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, precision=precision),
        name="mec_gemm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w_blk, 1, kwic),
                         lambda n, h, w, r, s_h=s_h: (n, w, h * s_h + r, 0)),
            pl.BlockSpec((1, kwic, k_c), lambda n, h, w, r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, w_blk, k_c),
                               lambda n, h, w, r: (n, h, w, 0)),
        out_shape=jax.ShapeDtypeStruct((i_n, o_h, o_w_p, k_c), jnp.float32),
        interpret=interpret,
    )(low, kernel_mat)
    with jax.named_scope("conv2d_out"):
        return out[:, :, :o_w, :]


# ---------------------------------------------------------------------------
# Fused kernel: lowering in VMEM, no L in HBM (beyond-paper)
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# Fused v2: h-blocked with halo (beyond-paper, DESIGN §2 / EXPERIMENTS §Perf)
# v1 fetches each input row k_h/s_h times (once per output row using it).
# v2 processes oh_blk output rows per grid step; the input block is the
# oh_blk*s_h rows it owns plus a (k_h - s_h)-row halo fetched through a
# SECOND BlockSpec view of the same input pointing at the next block —
# each input row now crosses HBM ~(1 + halo/block) times.
# ---------------------------------------------------------------------------

def _fused2_kernel(i_ref, halo_ref, k_ref, o_ref, *, k_w: int, s_w: int,
                   s_h: int, w_blk: int, oh_blk: int, halo: int,
                   precision):
    r = pl.program_id(3)
    w = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    rows = i_ref[0]                        # (oh_blk*s_h, i_w, i_c)
    if halo > 0:                           # first rows of the next block
        rows = jnp.concatenate([rows, halo_ref[0][:halo]], axis=0)
    i_c = rows.shape[-1]
    base = w * (s_w * w_blk)
    span = s_w * (w_blk - 1) + 1
    acc = jnp.zeros((oh_blk, w_blk, k_ref.shape[-1]), jnp.float32)
    for dh in range(oh_blk):               # output rows in this block
        row = lax.dynamic_slice(rows, (dh * s_h + r, 0, 0),
                                (1, rows.shape[1], i_c))[0]
        cols = []
        for j in range(k_w):
            seg = lax.dynamic_slice(row, (base + j, 0), (span, i_c))
            cols.append(seg[::s_w])
        strip = jnp.stack(cols, axis=1).reshape(w_blk, k_w * i_c)
        acc = acc.at[dh].set(
            jnp.dot(strip, k_ref[0], precision=precision,
                    preferred_element_type=jnp.float32))
    o_ref[0] += acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "w_blk", "oh_blk", "interpret",
                                    "precision"))
def mec_conv_fused2_pallas(inp: jnp.ndarray, kernel: jnp.ndarray, stride=1,
                           w_blk: int = 128, oh_blk: int = 8,
                           interpret: bool = True,
                           precision=None) -> jnp.ndarray:
    """h-blocked fused MEC conv (halo via second BlockSpec view)."""
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    i_n, i_h, i_w, i_c = inp.shape
    k_h, k_w, _, k_c = kernel.shape
    o_h = (i_h - k_h) // s_h + 1
    o_w = (i_w - k_w) // s_w + 1
    halo = k_h - s_h
    if halo < 0 or halo > s_h * oh_blk:
        # non-overlapping kernels (or giant halo): fall back to v1
        return mec_conv_fused_pallas(inp, kernel, (s_h, s_w), w_blk=w_blk,
                                     interpret=interpret,
                                     precision=precision)
    oh_blk = min(oh_blk, o_h)
    w_blk = min(w_blk, o_w)
    pad_h = (-o_h) % oh_blk
    pad_w = (-o_w) % w_blk
    o_h_p, o_w_p = o_h + pad_h, o_w + pad_w
    rows_blk = s_h * oh_blk
    n_hblocks = o_h_p // oh_blk
    # one extra zero block so the h+1 halo view is always in bounds
    need_h = (n_hblocks + 1) * rows_blk
    need_w = s_w * (o_w_p - 1) + k_w
    with jax.named_scope("mec_fold"):
        inp = jnp.pad(inp, ((0, 0), (0, max(0, need_h - i_h)),
                            (0, max(0, need_w - i_w)), (0, 0)))
        kernel_mat = kernel.reshape(k_h, k_w * i_c, k_c)
    grid = (i_n, n_hblocks, o_w_p // w_blk, k_h)
    out = pl.pallas_call(
        functools.partial(_fused2_kernel, k_w=k_w, s_w=s_w, s_h=s_h,
                          w_blk=w_blk, oh_blk=oh_blk, halo=halo,
                          precision=precision),
        name="mec_fused2",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows_blk, inp.shape[2], i_c),
                         lambda n, h, w, r: (n, h, 0, 0)),
            # halo: the NEXT h-block of the same input (always in bounds
            # thanks to the extra zero block)
            pl.BlockSpec((1, rows_blk, inp.shape[2], i_c),
                         lambda n, h, w, r: (n, h + 1, 0, 0)),
            pl.BlockSpec((1, k_w * i_c, k_c), lambda n, h, w, r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, oh_blk, w_blk, k_c),
                               lambda n, h, w, r: (n, h, w, 0)),
        out_shape=jax.ShapeDtypeStruct((i_n, o_h_p, o_w_p, k_c), jnp.float32),
        interpret=interpret,
    )(inp, inp, kernel_mat)
    with jax.named_scope("conv2d_out"):
        return out[:, :o_h, :o_w, :].astype(inp.dtype)


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
        if fb.squeeze:
            inp = inp.reshape(i_n, 1, i_h, i_c)
            kernel = kernel.reshape(1, k_h, i_c, k_c)
            i_h, i_w, k_h, k_w, s_h, s_w = 1, i_h, 1, k_h, 1, s_h
        # Rows and columns past the last window feed no output; missing
        # ones (fewer than a stride) only meet zero taps.
        need_h, need_w = fb.i_h2 * s_h, fb.i_w2 * s_w
        inp = inp[:, :need_h, :need_w, :]
        if need_h > inp.shape[1] or need_w > inp.shape[2]:
            inp = jnp.pad(inp, ((0, 0), (0, need_h - inp.shape[1]),
                                (0, need_w - inp.shape[2]), (0, 0)))
        # The width fold first, materialized on its own: XLA then lays
        # it out as the plain (rows, cols, channels) fold does, and the
        # split into phases is free.  Folded in one reshape, it went
        # through a lane-padded copy of the whole input.
        x4 = lax.optimization_barrier(
            inp.reshape(i_n, need_h, fb.i_w2, s_w * i_c))
        x5 = x4.reshape(i_n, fb.i_h2, s_h, fb.i_w2, s_w * i_c)
        k2 = jnp.pad(kernel, ((0, 0), (0, fb.k_q * s_w - k_w), (0, 0),
                              (0, 0)))
        k2 = k2.reshape(k_h, fb.k_q, s_w * i_c, k_c)
    nb, hb, wb, kc = fb.nb, fb.hb, fb.w_blk, fb.kc
    _, n_b, n_h, n_w = fb.grid

    def el(size, over=0):       # a window that may run past the array
        return pl.Element(size, (0, over))

    # Input windows overlap by the halo, so they are placed by element;
    # the last ones run past the array, into rows, images and columns
    # whose outputs are cropped or never written.
    x_spec = pl.BlockSpec(
        (el(nb, n_b * nb - i_n), el(fb.rows_in, n_h * hb - fb.o_h),
         el(s_h), el(fb.cols_in, (n_w - 1) * wb + fb.cols_in - fb.i_w2),
         el(fb.c2)),
        lambda c, b, h, w: (b * nb, h * hb, 0, w * wb, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, fb=fb, precision=precision),
        name="mec_fused",
        grid=fb.grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((k_h, fb.k_q, fb.c2, kc),
                         lambda c, b, h, w: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((nb, hb, wb, kc),
                               lambda c, b, h, w: (b, h, w, c)),
        out_shape=jax.ShapeDtypeStruct((i_n, fb.o_h, n_w * wb, k_c),
                                       inp.dtype),
        scratch_shapes=[pltpu.VMEM((fb.dot_rows, kc), jnp.float32)],
        interpret=interpret,
    )(x5, k2)
    with jax.named_scope("conv2d_out"):
        out = out[:, :, :fb.o_w, :]
        if fb.squeeze:
            out = out.reshape(i_n, fb.o_w, 1, k_c)
        return out
