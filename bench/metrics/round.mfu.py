"""Model FLOPs of the rounds in the traced slice (every client CCL/AMT step
and SE-CCL step: forward, backward to the activations and the LoRA
gradients; recomputation not counted), over the slice, over the bf16 peak
of all the cell's chips."""
from bench import peaks


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds_traced"] or t["window_s"] <= 0:
        return None
    peak = peaks.peak(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return (100.0 * ctx["round_flops"] * ctx["rounds_traced"]
            / t["window_s"] / peak)
