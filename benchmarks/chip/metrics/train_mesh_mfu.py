"""Model FLOP/s utilization of training on a mesh: ``train_mfu``'s
operations (the forward and backward matrix products of every real
token the window's steps took) over the window times the peak of every
chip the cell holds."""
from chip import flops


def read(ctx, metric):
    lay = ctx["outcome"].layers
    if not lay.get("steps"):
        return None
    chips = ctx["device"]["count"]
    peak = flops.peaks(ctx["device"]["kind"])["flops_per_s"]
    return 100.0 * lay["model_flops"] / (ctx["outcome"].window_s * peak
                                         * chips)
