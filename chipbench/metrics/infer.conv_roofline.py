"""infer.conv_roofline: the least time the chip could take for the
forward's convs (each at the larger of its operations over peak FLOP/s and
its input, kernel and output bytes over HBM bandwidth, counted from shapes)
as a share of the device's busy time per forward, over the traced part
of the window."""
import jax.numpy as jnp

from chipbench import yardstick


def read(run, trace, device, config, traffic):
    if trace is None or not run.work.get("traced_forwards"):
        return None
    itemsize = jnp.dtype(run.work["dtype"]).itemsize
    least = yardstick.roofline_s(run.work["convs"], device["kind"],
                                 run.work["dtype"], itemsize)
    busy = trace.fullest().busy_s
    return 100.0 * least * run.work["traced_forwards"] / busy
