"""serve.launches_per_req: device program executions in the traced window
over the requests served in it."""


def read(run, trace, device, config, traffic):
    if trace is None or not run.work.get("traced_requests"):
        return None
    return trace.fullest().launches / run.work["traced_requests"]
