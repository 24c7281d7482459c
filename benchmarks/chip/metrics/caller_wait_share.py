"""Share of the callers' time in the gateway's ``predict_all`` spent
blocked on their futures: ``server.wait`` over ``client.predict_all``
in the window (program spans on the host clock)."""
from chip import program_trace as PT


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else PT.caller_wait_share(ev)
