"""train.pointwise_roofline: the least time the chip could take for a
training step's 1x1 convs, over their device time per step inside the
program's ``pointwise`` scope.

Each 1x1 conv's forward, input gradient and kernel gradient do the
forward's operations, so the step's 1x1 convs do three times the
forward operations of ``run.work["pointwise"]``; the least time is those
operations at the chip's peak.  Bytes set no floor here: XLA keeps these
convs' operands in on-chip memory between ops where they fit, so the ops
can run faster than their input, kernel and output would take through
HBM.  The reductions XLA fuses into a conv's op (batch-norm sums, the
optimizer's sums of squares) are part of that op's time.  Steps are the
program launches begun in the traced window.  Nothing where the trace
has no program scopes."""
from chipbench import yardstick


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    convs = run.work.get("pointwise")
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not convs or not d.scoped_s("pointwise"):
        return None
    least = 3 * yardstick.forward_flops(convs) / yardstick.peak_flops(
        device["kind"], run.work["dtype"])
    return 100.0 * least * d.launches / d.scoped_s("pointwise")
