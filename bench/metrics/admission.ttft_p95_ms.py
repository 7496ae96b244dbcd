"""The 95th percentile of the time to first token over all requests due in
the window, on the host clock: from each request's due time to the end of
the tick that admitted it (queue wait, prefill and insert).  A request
never admitted makes it infinite; the result line's ``failed`` counts
that request, and the metric is left out."""
import math

from bench import common


def read(ctx):
    ttft = (ctx.get("window_metrics") or {}).get("ttft")
    if not ttft:
        return None
    p95 = common.quantile(ttft, 0.95)
    return p95 * 1e3 if math.isfinite(p95) else None
