"""Median wait in the queue, in milliseconds, of the requests admitted in
the traced slice: from submission to the start of admission
(``Request.t_admit - Request.t_submit``), which the program's
``serve.admit`` span carries as ``queued_us``."""
from bench import common, spans


def read(ctx):
    s = spans.of(ctx)
    if s is None:
        return None
    waits = [args["queued_us"] * 1e-3
             for _, _, args in spans.named(s, "serve.admit")
             if "queued_us" in args]
    return common.quantile(waits, 0.5) if waits else None
