"""Share of the positions the window's training steps computed that
were padding: 1 - sum of ``tokens`` over sum of ``positions``, over the
``train.step`` spans that start in the window. ``positions`` is the
program's own count of what a step computes (each width-sorted chunk's
rows times its width, or the whole batch's), so this reads the step,
where ``train_pad_share`` reads the loader's batch padded to its widest
row. A program whose spans carry no ``positions`` gives no reading."""
from chip import program_trace as PT
from chip import trace_reduce as TR


def step_pad_share(events):
    lo, hi = TR.window_bounds(events, PT.WINDOW)
    steps = [e["args"] for e in PT.spans(events, "train.step")
             if lo <= e["t0"] < hi]
    if not steps or any("positions" not in a for a in steps):
        return None
    positions = sum(a["positions"] for a in steps)
    return 100.0 * (1.0 - sum(a["tokens"] for a in steps) / positions)


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else step_pad_share(ev)
