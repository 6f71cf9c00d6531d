"""train.bwd_weight_ms: device milliseconds per training step inside the
program's ``mec_weight_grad`` scope (the MEC custom VJP's kernel
gradient), steps counted as the program launches begun in the traced
window.  Nothing where the trace has no program scopes."""


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not d.scoped_s("conv2d"):
        return None
    return 1e3 * d.scoped_s("mec_weight_grad") / d.launches
