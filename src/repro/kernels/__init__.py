"""Pallas TPU kernels for the paper's compute hot-spot: MEC convolution.

mec_conv.py   — the fused MEC kernel, its blocking and its geometry rule
mec_conv1d.py — fused causal depthwise conv1d (Mamba2/xLSTM blocks)
ops.py        — jit'd public wrappers (block-size selection, interpret auto)
ref.py        — pure-jnp oracles
"""
from repro.kernels.ops import mec_conv1d_tpu, mec_conv2d_tpu

__all__ = ["mec_conv2d_tpu", "mec_conv1d_tpu"]
