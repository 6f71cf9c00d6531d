"""train.dwconv_roofline: the least time the chip could take for a
training step's depthwise convs, over their device time per step inside
the program's ``depthwise`` scope.

The least time is the larger of two floors, from ``run.work["depthwise"]``:
the bytes of the forward, the input gradient and the kernel gradient
(each one's input, kernel and output moved once) over the chip's HBM
bandwidth, and their operations over the chip's peak.  The peak is the
MXU's bf16 peak, the one the yardstick publishes; a depthwise conv runs
on the VPU, whose rate is not published, so the operations' floor is
too low and the bytes bind (about 2.2 ms against 0.2 at batch 32).
Steps are the program launches begun in the traced window.  Nothing
where the trace has no program scopes or no depthwise conv."""
from chipbench import yardstick


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    work = run.work.get("depthwise")
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not work or not d.scoped_s("depthwise"):
        return None
    least = max(work["bytes"] / yardstick.peaks(
        device["kind"])["hbm_bytes_per_s"],
        work["flops"] / yardstick.peak_flops(device["kind"],
                                             run.work["dtype"]))
    return 100.0 * least * d.launches / d.scoped_s("depthwise")
