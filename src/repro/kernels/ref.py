"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.direct import direct_conv2d
from repro.core.mec import mec_conv1d_depthwise


def conv2d_ref(inp: jnp.ndarray, kernel: jnp.ndarray, stride=1) -> jnp.ndarray:
    """Oracle for mec_conv_fused_pallas."""
    return direct_conv2d(inp, kernel, stride)


def conv1d_ref(x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Oracle for mec_conv1d_pallas (causal depthwise)."""
    return mec_conv1d_depthwise(x, kernel, causal=True)
