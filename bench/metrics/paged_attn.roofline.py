"""Share of the HBM roofline the paged-attention kernel reaches: bytes of
the live K/V pages of the traced decode steps (from the lengths the harness
tracks) plus queries and outputs, over the kernel's device time, over the
chip's HBM bandwidth.  Block-table padding is not counted."""
from bench import flops, peaks, serving, trace

KERNEL = r"paged"


def read(ctx):
    if ctx["trace"] is None:
        return None
    m = ctx["conf"]["model"]
    ticks = [t for t in serving.traced_ticks(ctx) if t[1]]
    sec, calls = trace.time_of(ctx["trace"], KERNEL, "labels")
    if not ticks or not calls or sec <= 0:
        return None
    ps = ctx["conf"]["engine"]["page_size"]
    per_step = sum(flops.live_kv_bytes(m, [c for _, c in live], ps)
                   for _, live in ticks) / len(ticks)
    steps = calls / flops.kv_layers(m)
    bw = peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_step * steps / sec / bw
