"""The yardstick: chip peaks by device kind, and the work of a convolution
counted from its shapes alone, whatever algorithm runs it."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "flops_per_s": {"bfloat16": 197e12},
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB of HBM at 819 GB/s",
    },
}


class UnknownDevice(KeyError):
    """A device kind, or a dtype on it, that the peak table lacks."""


def peaks(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def peak_flops(device_kind: str, dtype: str) -> float:
    table = peaks(device_kind)["flops_per_s"]
    if dtype not in table:
        raise UnknownDevice(f"no {dtype} peak for {device_kind!r}")
    return table[dtype]


@dataclasses.dataclass(frozen=True)
class Conv:
    """One NHWC x HWIO convolution with symmetric zero padding."""

    n: int
    i_h: int
    i_w: int
    i_c: int
    k_h: int
    k_w: int
    o_c: int
    s_h: int = 1
    s_w: int = 1
    p_h: int = 0
    p_w: int = 0

    @property
    def o_h(self) -> int:
        return (self.i_h + 2 * self.p_h - self.k_h) // self.s_h + 1

    @property
    def o_w(self) -> int:
        return (self.i_w + 2 * self.p_w - self.k_w) // self.s_w + 1

    @property
    def flops(self) -> int:
        """Multiply-adds of the forward, counted as two operations."""
        return 2 * self.n * self.o_h * self.o_w * self.k_h * self.k_w * \
            self.i_c * self.o_c

    def bytes(self, itemsize: int) -> int:
        """Input, kernel and output, each moved once."""
        return itemsize * (self.n * self.i_h * self.i_w * self.i_c +
                           self.k_h * self.k_w * self.i_c * self.o_c +
                           self.n * self.o_h * self.o_w * self.o_c)

    def roofline_s(self, flops_per_s: float, bytes_per_s: float,
                   itemsize: int) -> float:
        """The least time the chip could take: compute or memory bound."""
        return max(self.flops / flops_per_s,
                   self.bytes(itemsize) / bytes_per_s)


def chain_convs(stages: Sequence[Dict], batch: int) -> List[Conv]:
    """Every conv of a config's ``stages``, each stage repeated ``count``
    times, in execution order."""
    out = []
    for st in stages:
        conv = Conv(batch, st["i_h"], st["i_w"], st["i_c"], st["k_h"],
                    st["k_w"], st["o_c"], st["stride"], st["stride"],
                    st["pad"], st["pad"])
        out.extend([conv] * st["count"])
    return out


def forward_flops(convs: Sequence[Conv]) -> int:
    return sum(c.flops for c in convs)


def train_flops(stages: Sequence[Dict], batch: int) -> int:
    """The operations a training step requires: the forward, every kernel
    gradient (as many operations as the forward), and an input gradient
    only where the input is a layer's output, so not for the first conv
    of a stage.  Recomputation is not counted."""
    total = 0
    for st in stages:
        conv = chain_convs([st], batch)[0]
        total += st["count"] * 2 * conv.flops + (st["count"] - 1) * conv.flops
    return total


def roofline_s(convs: Sequence[Conv], device_kind: str, dtype: str,
               itemsize: int) -> float:
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    fl = peak_flops(device_kind, dtype)
    return math.fsum(c.roofline_s(fl, bw, itemsize) for c in convs)
