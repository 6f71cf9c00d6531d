"""Pallas TPU kernel: fused causal depthwise conv1d via MEC.

Used by the Mamba2 (zamba2) and xLSTM blocks.  In 1-D the MEC compact
lowering coincides with im2col (DESIGN.md §5), so the win is the *fused*
form: no lowered matrix at all.  The causal halo (k_w-1 steps of history)
is fetched through a second BlockSpec view of the same input pointing at
the previous time-block — BlockSpec index maps again standing in for the
paper's aliased BLAS views.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv1d_kernel(x_ref, prev_ref, k_ref, o_ref, *, k_w: int):
    # x_ref/prev_ref: (1, t_blk, c_blk); k_ref: (k_w, c_blk)
    i = pl.program_id(1)
    x = x_ref[0]                            # (t_blk, c_blk)
    tail = prev_ref[0, -(k_w - 1):, :]      # halo from previous block
    tail = jnp.where(i == 0, jnp.zeros_like(tail), tail)  # causal left pad
    xx = jnp.concatenate([tail, x], axis=0)  # (t_blk + k_w - 1, c_blk)
    t_blk = x.shape[0]
    acc = jnp.zeros(x.shape, jnp.float32)
    for j in range(k_w):
        acc += xx[j:j + t_blk, :].astype(jnp.float32) * k_ref[j][None, :]
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t_blk", "c_blk", "interpret"))
def mec_conv1d_pallas(x: jnp.ndarray, kernel: jnp.ndarray,
                      t_blk: int = 512, c_blk: int = 128,
                      interpret: bool = True) -> jnp.ndarray:
    """Causal depthwise conv1d.  x: (n, t, c); kernel: (k_w, c)."""
    n, t, c = x.shape
    k_w, kc = kernel.shape
    assert kc == c, (kernel.shape, x.shape)
    t_blk = min(t_blk, t)
    c_blk = min(c_blk, c)
    pad_t, pad_c = (-t) % t_blk, (-c) % c_blk
    if pad_t or pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_c)))
        kernel = jnp.pad(kernel, ((0, 0), (0, pad_c)))
    t_p, c_p = t + pad_t, c + pad_c
    assert t_blk >= k_w - 1, "time block must cover the causal halo"
    grid = (n, t_p // t_blk, c_p // c_blk)
    out = pl.pallas_call(
        functools.partial(_conv1d_kernel, k_w=k_w),
        name="mec_conv1d",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_blk, c_blk), lambda n, i, cc: (n, i, cc)),
            # halo view: previous time block (clamped at 0; masked in-kernel)
            pl.BlockSpec((1, t_blk, c_blk),
                         lambda n, i, cc: (n, jnp.maximum(i - 1, 0), cc)),
            pl.BlockSpec((k_w, c_blk), lambda n, i, cc: (0, cc)),
        ],
        out_specs=pl.BlockSpec((1, t_blk, c_blk), lambda n, i, cc: (n, i, cc)),
        out_shape=jax.ShapeDtypeStruct((n, t_p, c_p), x.dtype),
        interpret=interpret,
    )(x, x, kernel)
    return out[:, :t, :c]
