"""Share of the rows the gateway featurized in the window that were cut
at the model's ``max_seq``: the ``server.featurize`` spans that start
in the window and encoded their graph (``hit`` 0), the sum of their
``truncated`` over their count (program spans; see
``program_trace.py``). A program whose spans carry no ``truncated``
gives no reading."""
from chip import program_trace as PT
from chip import trace_reduce as TR


def truncation_share(events):
    lo, hi = TR.window_bounds(events, PT.WINDOW)
    rows = [e["args"] for e in PT.spans(events, "server.featurize")
            if lo <= e["t0"] < hi and not e["args"].get("hit")]
    if not rows or any("truncated" not in a for a in rows):
        return None
    return 100.0 * sum(a["truncated"] for a in rows) / len(rows)


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else truncation_share(ev)
