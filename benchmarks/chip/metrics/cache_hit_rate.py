"""Share of requests in the window answered by the prediction cache at
submit (``ServerMetrics`` cache_hits over requests)."""


def read(ctx, metric):
    c = ctx["outcome"].layers["counts"]
    return 100.0 * c["cache_hits"] / c["requests"] if c["requests"] else None
