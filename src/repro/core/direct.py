"""Direct convolution (paper Fig. 1a) — via lax.conv_general_dilated.

XLA's direct convolution is the "no memory-overhead" reference point and
the numerical ground truth for every other algorithm in this package.
It is also the one algorithm that runs grouped convs: the group count is
the input's channels over the kernel's (HWIO ``I`` = ``i_c / groups``),
passed as ``feature_group_count``, and both gradients are XLA's.

Sub-f32 inputs need a custom VJP: ``preferred_element_type=f32`` makes
the forward emit an f32 accumulator (the numeric contract, DESIGN.md
§8.5), but jax's ``conv_general_dilated`` transpose rule cannot consume
the resulting f32 cotangent against bf16/f16 residuals ("requires
arguments to have the same dtypes") — dot_general's transpose handles
this, conv's does not.  The backward therefore differentiates the
f32-upcast convolution (bit-identical products: a bf16xbf16 product is
exact in f32 either way) and narrows each gradient back to its operand
dtype — the same one-terminal-narrow structure as the MEC VJP in
``conv_api``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _groups(inp: jnp.ndarray, kernel: jnp.ndarray) -> int:
    return inp.shape[-1] // kernel.shape[2]


def _conv(inp: jnp.ndarray, kernel: jnp.ndarray, s, precision):
    return lax.conv_general_dilated(
        inp, kernel,
        window_strides=s,
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=_groups(inp, kernel),
        precision=precision,
        preferred_element_type=jnp.float32,
    ).astype(inp.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _direct(inp: jnp.ndarray, kernel: jnp.ndarray, s, precision):
    return _conv(inp, kernel, s, precision)


def _direct_fwd(inp, kernel, s, precision):
    return _conv(inp, kernel, s, precision), (inp, kernel)


def _direct_bwd(s, precision, res, g):
    inp, kernel = res

    def f32_conv(x32, k32):
        return lax.conv_general_dilated(
            x32, k32,
            window_strides=s,
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=_groups(x32, k32),
            precision=precision)

    _, vjp = jax.vjp(f32_conv, inp.astype(jnp.float32),
                     kernel.astype(jnp.float32))
    d_inp, d_ker = vjp(g.astype(jnp.float32))
    return d_inp.astype(inp.dtype), d_ker.astype(kernel.dtype)


_direct.defvjp(_direct_fwd, _direct_bwd)


@functools.partial(jax.jit, static_argnames=("stride", "precision"))
def direct_conv2d(inp: jnp.ndarray, kernel: jnp.ndarray, stride=1,
                  precision=None) -> jnp.ndarray:
    """inp (n, h, w, c) pre-padded; kernel (k_h, k_w, c / groups, k_c);
    VALID."""
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    return _direct(inp, kernel, s, precision)
