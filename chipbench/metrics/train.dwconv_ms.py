"""train.dwconv_ms: device milliseconds per training step inside the
program's ``depthwise`` scope (every depthwise conv call with its bias,
forward and backward), steps counted as the program launches begun in
the traced window.  Nothing where the trace has no program scopes or no
depthwise conv."""


def read(run, trace, device, config, traffic):
    d = trace.fullest() if trace is not None else None
    if not getattr(d, "scope_kind_s", None) or not d.launches or \
            not d.scoped_s("depthwise"):
        return None
    return 1e3 * d.scoped_s("depthwise") / d.launches
