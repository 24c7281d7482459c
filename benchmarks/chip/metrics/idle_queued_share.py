"""Share of the device's idle time in the window during which the
gateway held a queued request (from each ``server.dispatch`` span's
``oldest_wait_us`` back from its start): idle that the flush policy,
not the callers, left."""
from chip import program_trace as PT


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else PT.idle_queued_share(ev)
