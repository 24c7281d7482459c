"""Share of the positions fed to the window's training steps that were
padding (the loader's batches: rows padded to the widest row's bucket)."""


def read(ctx, metric):
    lay = ctx["outcome"].layers
    if not lay.get("positions"):
        return None
    return 100.0 * (1.0 - lay["tokens"] / lay["positions"])
