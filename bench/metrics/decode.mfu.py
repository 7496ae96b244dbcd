"""Model FLOPs of the tokens the decode steps produced in the traced slice
(each at its own context length), over the slice, over the bf16 peak of
all the cell's chips."""
from bench import flops, peaks, serving


def read(ctx):
    ticks = serving.traced_ticks(ctx)
    if ctx["trace"] is None or not ticks:
        return None
    m = ctx["conf"]["model"]
    f = sum(flops.decode_flops(m, c) for _, live in ticks for _, c in live)
    if not f:
        return None
    peak = peaks.peak(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * f / ctx["trace"]["window_s"] / peak
