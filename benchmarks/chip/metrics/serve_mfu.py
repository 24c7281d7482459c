"""Model FLOP/s utilization of serving: the operations of the real rows
forwarded in the window (at the width they ran at), over the window
times the chip's peak."""
from chip import flops


def read(ctx, metric):
    fwd = ctx["outcome"].layers.get("forwards") or []
    if not fwd:
        return None
    cfg = ctx["outcome"].layers["config"]
    peak = flops.peaks(ctx["device"]["kind"])["flops_per_s"]
    ops = sum(flops.forward(cfg, n, w)[0] for n, _, w in fwd)
    return 100.0 * ops / (ctx["outcome"].window_s * peak)
