"""train.norm_ms: device milliseconds per training step inside the
program's ``batch_norm`` scope (every batch norm, forward and backward),
steps counted as the program launches begun in the traced window.  An op
counts where XLA puts it: a norm's reductions that XLA fuses into the
op of the conv before it count in that conv's scope, not here.  Nothing
where the trace has no program scopes or no batch norm."""


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not d.scoped_s("batch_norm"):
        return None
    return 1e3 * d.scoped_s("batch_norm") / d.launches
