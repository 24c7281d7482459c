"""Share of the window in which no operation ran on the device (trace)."""


def read(ctx, metric):
    return 100.0 * ctx["trace"]["idle_share"]
