"""serve.idle_pct: the share of the traced window in which no op ran on
the device."""


def read(run, trace, device, config, traffic):
    return None if trace is None else 100.0 * trace.idle_share()
