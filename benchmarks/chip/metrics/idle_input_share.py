"""Share of the device's idle time in the window during which the train
loop was fetching (``train.batch``) or placing (``train.place``) its
next batch: idle that the input pipeline left."""
from chip import program_trace as PT


def read(ctx, metric):
    ev = PT.events_for(ctx)
    return None if ev is None else PT.idle_input_share(ev)
