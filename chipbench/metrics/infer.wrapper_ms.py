"""infer.wrapper_ms: device milliseconds per forward in ops that are
neither a Mosaic kernel nor an XLA convolution or dot (padding, the
stride fold, ReLU, casts and copies around the convs)."""


def read(run, trace, device, config, traffic):
    if trace is None or not run.work.get("traced_forwards"):
        return None
    other = trace.fullest().kind_s["other"]
    return 1e3 * other / run.work["traced_forwards"]
