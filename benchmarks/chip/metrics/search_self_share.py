"""Share of the searches' time spent in the search itself, outside the
gateway's ``predict_all`` (host clock, searches inside the window)."""


def read(ctx, metric):
    lay = ctx["outcome"].layers
    if not lay.get("search_s"):
        return None
    return 100.0 * (1.0 - lay["predict_s"] / lay["search_s"])
