"""Model FLOP/s utilization of training: the forward and backward
matrix products of every real (non-pad) token the window's steps took,
over the window times the chip's peak."""
from chip import flops


def read(ctx, metric):
    lay = ctx["outcome"].layers
    if not lay.get("steps"):
        return None
    peak = flops.peaks(ctx["device"]["kind"])["flops_per_s"]
    return 100.0 * lay["model_flops"] / (ctx["outcome"].window_s * peak)
