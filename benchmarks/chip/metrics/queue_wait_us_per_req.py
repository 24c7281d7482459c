"""Mean time a request the gateway dispatched in the window waited in
its queue: the ``server.dispatch`` spans' ``wait_us`` over their
``rows`` (program spans; see ``program_trace.py``)."""
from chip import program_trace as PT


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else PT.queue_wait_us_per_req(ev)
