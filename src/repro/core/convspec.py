"""Shared convolution geometry helpers (paper Table 1 / Eq. 1).

All tensors are NHWC (the paper's n-h-w-c) and kernels are HWIO
(k_h, k_w, i_c, k_c).  Padding is assumed to have been applied to the
input already (paper §2.1); helpers to apply SAME/VALID padding live here
so every algorithm sees an identical pre-padded input.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Geometry of one 2-D convolution, pre-padding (paper Eq. 1).

    ``groups`` splits the channels into that many independent convs
    (``feature_group_count``): the kernel is HWIO with ``i_c / groups``
    input channels, and output channel ``o`` reads input group
    ``o // (k_c / groups)``.  A depthwise conv has ``groups == i_c``.
    Only the ``direct`` algorithm runs a grouped conv."""

    i_n: int
    i_h: int
    i_w: int
    i_c: int
    k_h: int
    k_w: int
    k_c: int
    s_h: int = 1
    s_w: int = 1
    groups: int = 1

    @property
    def o_h(self) -> int:
        return (self.i_h - self.k_h) // self.s_h + 1

    @property
    def o_w(self) -> int:
        return (self.i_w - self.k_w) // self.s_w + 1

    @property
    def out_shape(self) -> Tuple[int, int, int, int]:
        return (self.i_n, self.o_h, self.o_w, self.k_c)

    def validate(self) -> None:
        if self.i_h < self.k_h or self.i_w < self.k_w:
            raise ValueError(f"kernel larger than input: {self}")
        if min(self.s_h, self.s_w) < 1:
            raise ValueError(f"strides must be >= 1: {self}")
        if self.groups < 1 or self.i_c % self.groups or \
                self.k_c % self.groups:
            raise ValueError(f"groups must divide both i_c and k_c: {self}")

    @property
    def group_c(self) -> int:
        """Input channels each output channel reads: the kernel's I."""
        return self.i_c // self.groups

    def to_dict(self) -> dict:
        """The fields as a dict, ``groups`` only where it is not 1, so a
        dense spec serializes as it did before groups existed."""
        out = dataclasses.asdict(self)
        if self.groups == 1:
            del out["groups"]
        return out


def normalize_stride(stride) -> Tuple[int, int]:
    """Canonical ``(s_h, s_w)`` from an int or a 2-sequence.

    The one stride normalizer in the repo: ``spec_of``, the ``conv2d``
    front-end, and the distributed layer all resolve strides here, so a
    bad stride fails identically everywhere."""
    s_h, s_w = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if min(s_h, s_w) < 1:
        raise ValueError(f"strides must be >= 1, got {(s_h, s_w)}")
    return s_h, s_w


def padding_amounts(i_h: int, i_w: int, k_h: int, k_w: int,
                    s_h: int, s_w: int, padding) -> Tuple[int, int]:
    """Total (rows, cols) ``conv_api.apply_padding`` would add — the same
    SAME/VALID/int/explicit resolution, as pure arithmetic (no arrays),
    so analytic models can size post-padding geometry without tracing."""
    if isinstance(padding, str):
        mode = padding.upper()
        if mode == "VALID":
            return 0, 0
        if mode == "SAME":
            o_h, o_w = -(-i_h // s_h), -(-i_w // s_w)
            return (max((o_h - 1) * s_h + k_h - i_h, 0),
                    max((o_w - 1) * s_w + k_w - i_w, 0))
        raise ValueError(f"unknown padding {padding!r}")
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    p_h, p_w = padding
    if isinstance(p_h, int):
        p_h = (p_h, p_h)
    if isinstance(p_w, int):
        p_w = (p_w, p_w)
    if min(tuple(p_h) + tuple(p_w)) < 0:
        raise ValueError(f"padding must be non-negative, got {(p_h, p_w)}")
    return sum(p_h), sum(p_w)


def padded_spec(s: ConvSpec, padding) -> ConvSpec:
    """The post-padding ConvSpec of a pre-padding geometry + padding mode
    — what ``conv2d`` actually dispatches (and every algorithm actually
    allocates) on.  VALID is the identity."""
    pad_h, pad_w = padding_amounts(s.i_h, s.i_w, s.k_h, s.k_w,
                                   s.s_h, s.s_w, padding)
    if pad_h == 0 and pad_w == 0:
        return s
    return dataclasses.replace(s, i_h=s.i_h + pad_h, i_w=s.i_w + pad_w)


def conv_groups(i_c: int, kernel_shape) -> int:
    """The group count of an HWIO kernel on ``i_c`` input channels:
    ``i_c`` over the kernel's input channels, which must divide it."""
    kic = kernel_shape[2]
    if kic < 1 or i_c % kic:
        raise ValueError(f"channel mismatch: input {i_c} is not a whole "
                         f"multiple of the kernel's {kic} input channels")
    return i_c // kic


def grouped_refusal(spec: ConvSpec, algorithm: str) -> Optional[str]:
    """Why ``algorithm`` does not run the grouped conv ``spec``, or None:
    ``direct`` (XLA's ``feature_group_count``) runs every grouped conv;
    the lowerings (im2col, MEC, FFT, Winograd) and the Pallas kernel
    contract over all of ``i_c`` and run dense convs only."""
    if spec.groups == 1 or algorithm == "direct":
        return None
    return (f"groups={spec.groups}: {algorithm} runs dense convs only; a "
            "grouped conv runs on direct")


def spec_of(inp: jnp.ndarray, kernel: jnp.ndarray, stride) -> ConvSpec:
    s_h, s_w = normalize_stride(stride)
    i_n, i_h, i_w, i_c = inp.shape
    k_h, k_w, _, k_c = kernel.shape
    spec = ConvSpec(i_n, i_h, i_w, i_c, k_h, k_w, k_c, s_h, s_w,
                    conv_groups(i_c, kernel.shape))
    spec.validate()
    return spec


def pad_same(inp: jnp.ndarray, k_h: int, k_w: int, s_h: int = 1, s_w: int = 1) -> jnp.ndarray:
    """Explicit SAME padding (the paper assumes pre-padded input)."""
    _, i_h, i_w, _ = inp.shape
    o_h = -(-i_h // s_h)
    o_w = -(-i_w // s_w)
    pad_h = max((o_h - 1) * s_h + k_h - i_h, 0)
    pad_w = max((o_w - 1) * s_w + k_w - i_w, 0)
    return jnp.pad(
        inp,
        ((0, 0), (pad_h // 2, pad_h - pad_h // 2), (pad_w // 2, pad_w - pad_w // 2), (0, 0)),
    )
