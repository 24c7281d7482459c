"""Rows per forward batch the gateway flushed in the window
(``ServerMetrics`` batched_entries over batches)."""


def read(ctx, metric):
    c = ctx["outcome"].layers["counts"]
    return c["batched_entries"] / c["batches"] if c["batches"] else None
