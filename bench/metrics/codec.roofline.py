"""Share of the HBM roofline the channel codec's kernels reach: the bytes
quantize and dequantize need for the traced rounds' uploads and deliveries
(from the upload shapes), over the kernels' device time, over the chip's
HBM bandwidth."""
from bench import peaks, trace

KERNEL = r"quantize"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds_traced"] or not ctx["codec_bytes"]:
        return None
    sec, calls = trace.time_of(t, KERNEL, "labels")
    if not calls or sec <= 0:
        return None
    bw = peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * ctx["codec_bytes"] * ctx["rounds_traced"] / sec / bw
