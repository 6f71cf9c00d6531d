"""infer.executor_ms: device milliseconds per forward in ops of the
program's ``conv2d`` scope that are neither a Mosaic kernel nor an XLA
convolution or dot (the padding pass, the stride fold, the output crop
and cast).  Nothing where the trace has no program scopes."""


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or \
            not run.work.get("traced_forwards") or \
            not d.scoped_s("conv2d"):
        return None
    return 1e3 * d.scoped_s("conv2d", ("other",)) / \
        run.work["traced_forwards"]
