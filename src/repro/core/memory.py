"""Analytic memory-overhead model (paper §3.4, Eqs. 2-4) for every
convolution algorithm compared in §4.  "Overhead" = temporary storage
beyond input/kernel/output, in elements (multiply by dtype size for bytes).
"""
from __future__ import annotations

from repro.core.convspec import ConvSpec, padded_spec


def im2col_overhead(s: ConvSpec) -> int:
    """Eq. 2: the lowered Toeplitz matrix."""
    return s.i_n * s.o_h * s.o_w * s.k_h * s.k_w * s.i_c


def mec_overhead(s: ConvSpec) -> int:
    """Eq. 3: MEC's compact lowered matrix L."""
    return s.i_n * s.o_w * s.i_h * s.k_w * s.i_c


def mec_saving(s: ConvSpec) -> int:
    """Eq. 4: R = i_n k_c o_w k_w (i_h - k_h)(k_h/s_h - 1)  [elements].

    Note the paper's R is expressed per output channel block; we return the
    exact difference im2col_overhead - mec_overhead, which the paper shows
    equals i_n * i_c * o_w * k_w * (o_h*k_h - i_h).
    """
    return im2col_overhead(s) - mec_overhead(s)


def fft_overhead(s: ConvSpec, padding="VALID") -> int:
    """Kernels padded to input size + input/output spectra (complex => x2).

    rfft halves the last freq axis (+1); counted in real elements.
    The spectra are sized on the *post-padding* spatial dims — the input
    ``fft_conv2d`` actually transforms — so a pre-padding spec with
    SAME/explicit padding no longer understates the overhead.
    """
    s = padded_spec(s, padding)
    w_f = s.i_w // 2 + 1
    ker = s.i_h * w_f * s.i_c * s.k_c * 2        # padded kernel spectra
    inp = s.i_n * s.i_h * w_f * s.i_c * 2        # input spectrum
    out = s.i_n * s.i_h * w_f * s.k_c * 2        # product spectrum
    return ker + inp + out


def winograd_overhead(s: ConvSpec) -> int:
    """F(2x2,3x3): transformed kernels U, tiles V, and products M."""
    t_h, t_w = -(-s.o_h // 2), -(-s.o_w // 2)
    u = 16 * s.i_c * s.k_c
    v = 16 * s.i_n * t_h * t_w * s.i_c
    m = 16 * s.i_n * t_h * t_w * s.k_c
    return u + v + m


def direct_overhead(s: ConvSpec) -> int:  # lint-ignore: accepted-kwarg-not-forwarded
    return 0          # no temporaries; s kept for ALL_OVERHEADS uniformity


def conv_flops(s: ConvSpec) -> int:
    """Mult-adds x2 — identical for direct/im2col/MEC (paper §3.2)."""
    return 2 * s.i_n * s.o_h * s.o_w * s.k_h * s.k_w * s.i_c * s.k_c


ALL_OVERHEADS = {
    "direct": direct_overhead,
    "im2col": im2col_overhead,
    "mec": mec_overhead,
    "fft": fft_overhead,
    "winograd": winograd_overhead,
}

# conv2d dispatch names -> the base overhead model above.  The fused
# Pallas kernel keeps the lowering in VMEM, so its HBM overhead is the
# direct conv's (zero).
_DISPATCH_BASE = {"mecA": "mec", "mecB": "mec", "mec_fused": "direct"}


def algorithm_overhead(s: ConvSpec, algorithm: str,
                       padding="VALID") -> int:
    """Overhead in elements for any ``conv2d`` dispatch name (including
    solution/Pallas variants not listed in :data:`ALL_OVERHEADS`).

    ``padding`` resolves a *pre-padding* spec to the geometry the
    algorithm actually allocates on (``convspec.padded_spec``); the
    default VALID keeps post-padding specs — the repo norm — unchanged.
    """
    return ALL_OVERHEADS[_DISPATCH_BASE.get(algorithm, algorithm)](
        padded_spec(s, padding))
