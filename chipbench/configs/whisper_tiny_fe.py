"""Plain float32 reference of ``whisper_tiny_fe``, the whisper encoder's
conv frontend: Conv1d(80, 384, 3, padding 1), GELU, Conv1d(384, 384, 3,
stride 2, padding 1), GELU, as ``lax.conv_general_dilated`` at HIGHEST
precision over time.  GELU is the tanh form the configuration states.

It imports nothing of the program under test; the weights follow the
configuration's stated recipe from the same seeded key.  ``rounding=
"fp8"`` is the precision control: every conv operand and activation
rounded to float8 e4m3 with a per-tensor scale.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


ROUNDINGS = {None: lambda x: x, "fp8": _fp8}


def weights(key, config: Dict):
    """Conv kernels (taps, 1, in, out): standard normal over the fan-in
    per tap's square root, in the configuration's dtype."""
    n_mels, d = config["num_mel_bins"], config["d_model"]
    k1, k2 = jax.random.split(key)
    w1 = jax.random.normal(k1, (3, 1, n_mels, d)) * n_mels ** -0.5
    w2 = jax.random.normal(k2, (3, 1, d, d)) * d ** -0.5
    return w1.astype(config["dtype"]), w2.astype(config["dtype"])


def _conv1d(x, w, stride: int):
    return lax.conv_general_dilated(
        x, w, (stride, 1), ((1, 1), (0, 0)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST, preferred_element_type=F32)


def frontend(mel, w1, w2, rounding: Optional[str] = None):
    """(B, T, mels) -> (B, ceil(T / 2), d_model) in float32."""
    r = ROUNDINGS[rounding]
    x = r(mel.astype(F32))[:, :, None, :]
    x = r(jax.nn.gelu(_conv1d(x, r(w1.astype(F32)), 1), approximate=True))
    x = r(jax.nn.gelu(_conv1d(x, r(w2.astype(F32)), 2), approximate=True))
    return x[:, :, 0, :]
