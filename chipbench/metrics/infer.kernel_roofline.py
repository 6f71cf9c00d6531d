"""infer.kernel_roofline: the forward's conv roofline (as in
``infer.conv_roofline``) over the device time of the ops that do the conv
FLOPs: Mosaic, conv and dot ops inside the program's ``conv2d`` scope,
plus conv and dot ops outside it (an XLA fusion can carry the name of
its root, a caller's op).  Nothing where the trace has no program
scopes."""
import jax.numpy as jnp

from chipbench import yardstick

KERNEL_KINDS = ("mosaic", "conv", "dot")


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or \
            not run.work.get("traced_forwards"):
        return None
    kernels = d.scoped_s("conv2d", KERNEL_KINDS) + d.outside_s(("conv",
                                                                 "dot"))
    if kernels == 0:
        return None
    itemsize = jnp.dtype(run.work["dtype"]).itemsize
    least = yardstick.roofline_s(run.work["convs"], device["kind"],
                                 run.work["dtype"], itemsize)
    return 100.0 * least * run.work["traced_forwards"] / kernels
