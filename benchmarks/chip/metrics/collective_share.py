"""Share of the devices' busy time in the window spent in collectives:
the device operations whose HLO name is an all-reduce, reduce-scatter
or all-gather (their ``-start``/``-done`` halves and fusions named
after them included), summed per device and averaged over the devices,
over the busy time (the union of all operations, averaged the same
way). A run whose trace holds no such operation reads 0."""
import re

COLLECTIVE = re.compile(r"(all-reduce|reduce-scatter|all-gather)")


def collective_share(op_time_s, busy_s):
    if busy_s <= 0:
        return None
    t = sum(s for name, s in op_time_s.items() if COLLECTIVE.search(name))
    return 100.0 * t / busy_s


def read(ctx, metric):
    tr = ctx["trace"]
    return collective_share(tr.get("op_time_s", {}), tr["busy_s"])
