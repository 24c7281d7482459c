"""The device forward's share of its roofline: the least time the chip
could take for every (batch, width) forward the gateway dispatched in
the window (``flops.forward`` against the peaks table), over the
device's busy time in the window, in which only those forwards run."""
from chip import flops


def read(ctx, metric):
    fwd = ctx["outcome"].layers.get("forwards") or []
    busy = ctx["trace"]["busy_s"]
    if not fwd or busy <= 0:
        return None
    cfg = ctx["outcome"].layers["config"]
    kind = ctx["device"]["kind"]
    least = sum(flops.least_time(*flops.forward(cfg, nb, w), kind)[0]
                for _, nb, w in fwd)
    return 100.0 * least / busy
