"""Pallas TPU kernels for MEC convolution (Cho & Brand, ICML 2017).

TPU adaptation (see DESIGN.md §2): the paper's BLAS ``ld``-aliased
overlapping sub-matrix views become BlockSpec *index maps*.  The key
observation making the shifted-window GEMM expressible with non-overlapping
BlockSpec blocks is the k_h-decomposition::

    O[n, h, :, :] = sum_{r=0}^{k_h-1}  L[n, :, h*s_h + r, :] @ K[r]

With block size 1 on the i_h axis of L, the index ``h*s_h + r`` is a plain
block index — the grid dimension ``r`` walks the kernel rows and the output
block accumulates in VMEM.  Three kernels:

* ``mec_lower``    — Algorithm 2 lines 4-6 (build compact L in HBM).
* ``mec_gemm``     — the o_h shifted GEMMs over a materialized L
                     (paper-faithful mode: Eq. 3 memory is observable).
* ``mec_conv_fused`` — beyond-paper: lowering happens in VMEM inside the
                     GEMM pipeline, L never exists in HBM.  HBM traffic is
                     I (k_h/s_h x) + K + O, vs. the lowered path's
                     additional |L| write + (k_h/s_h)|L| read.

All kernels accumulate in f32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


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

def _fused_kernel(x_ref, k_ref, o_ref, *, k_q: int, w_blk: int, halo: int,
                  n_wblk: int, precision):
    # x_ref: (1, 1, i_w2, s_w*i_c) — input row h*s_h + r, width folded by
    #        s_w into channels, so every window below has unit stride
    # k_ref: (1, k_q, s_w*i_c, k_c); o_ref: (1, 1, w_blk, k_c)
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # The block's columns plus a halo, loaded from a block-aligned start;
    # the k_q windows are then static slices of that value.
    base = 0 if n_wblk == 1 else \
        pl.multiple_of(pl.program_id(2) * w_blk, w_blk)
    row = x_ref[0, 0, pl.ds(base, w_blk + halo), :]
    acc = None
    for q in range(k_q):
        part = jnp.dot(row[q:q + w_blk], k_ref[0, q], precision=precision,
                       preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    o_ref[0, 0] += acc


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
    Returns (n, o_h, o_w, k_c) in inp.dtype (f32 accumulation).

    The width stride is folded out here (space-to-depth): input column
    ``m*s_w + p`` becomes column ``m``, channel block ``p``, and the
    kernel's k_w taps regroup into ``k_q = ceil(k_w/s_w)`` unit-stride
    taps over ``s_w*i_c`` channels (taps past k_w are zero).  Window
    ``q`` of output column ``ow`` is then folded column ``ow + q``, so
    the kernel body reads only contiguous, unit-stride slices.
    """
    s_h, s_w = (stride, stride) if isinstance(stride, int) else stride
    i_n, i_h, i_w, i_c = inp.shape
    k_h, k_w, _, k_c = kernel.shape
    o_h = (i_h - k_h) // s_h + 1
    o_w = (i_w - k_w) // s_w + 1
    w_blk = min(w_blk, o_w)
    o_w_p = o_w + (-o_w) % w_blk
    n_wblk = o_w_p // w_blk
    k_q = -(-k_w // s_w)
    # Window halo past a block; a whole multiple of 8 rows when blocks
    # start at a dynamic offset, which Mosaic loads only sublane-aligned.
    halo = k_q - 1 if n_wblk == 1 else -(-(k_q - 1) // 8) * 8
    i_w2 = o_w_p + halo                  # folded columns any block reads
    need_w = s_w * i_w2
    # Columns past need_w feed no output; missing ones only meet zero taps
    # or padded output columns.
    with jax.named_scope("mec_fold"):
        inp = inp[:, :, :need_w, :]
        if need_w > inp.shape[2]:
            inp = jnp.pad(inp, ((0, 0), (0, 0),
                                (0, need_w - inp.shape[2]), (0, 0)))
        x2 = inp.reshape(i_n, i_h, i_w2, s_w * i_c)
        k2 = jnp.pad(kernel, ((0, 0), (0, k_q * s_w - k_w), (0, 0), (0, 0)))
        k2 = k2.reshape(k_h, k_q, s_w * i_c, k_c)
    grid = (i_n, o_h, n_wblk, k_h)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, k_q=k_q, w_blk=w_blk, halo=halo,
                          n_wblk=n_wblk, precision=precision),
        name="mec_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, i_w2, s_w * i_c),
                         lambda n, h, w, r, s_h=s_h: (n, h * s_h + r, 0, 0)),
            pl.BlockSpec((1, k_q, s_w * i_c, k_c),
                         lambda n, h, w, r: (r, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, w_blk, k_c),
                               lambda n, h, w, r: (n, h, w, 0)),
        out_shape=jax.ShapeDtypeStruct((i_n, o_h, o_w_p, k_c), jnp.float32),
        interpret=interpret,
    )(x2, k2)
    with jax.named_scope("conv2d_out"):
        return out[:, :, :o_w, :].astype(inp.dtype)
