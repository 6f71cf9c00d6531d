"""train.layer_norm_ms: device milliseconds per training step inside the
program's ``layer_norm`` scope (every layer norm, forward and backward),
steps counted as the program launches begun in the traced window.  An op
counts where XLA puts it: a norm's work that XLA fuses into the op of a
conv beside it counts in that conv's scope, not here.  Nothing where the
trace has no program scopes or no layer norm."""


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not d.scoped_s("layer_norm"):
        return None
    return 1e3 * d.scoped_s("layer_norm") / d.launches
