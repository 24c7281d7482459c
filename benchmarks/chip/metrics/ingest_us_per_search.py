"""Front-door time a search paid in the window: the summed duration of
the program's ``frontdoor.parse`` spans that start in the window, over
the number of the benchmark's ``bench.search`` spans that start in it
(one text parsed a search). ``program_trace.py`` keeps only the spans of
the serving and training layers, so the run's xplane is read here for
these names. A program without the span gives no reading."""
import glob
import os
import warnings

from chip import trace_reduce as TR

PARSE, SEARCH, WINDOW = "frontdoor.parse", "bench.search", "bench.window"


def load(path):
    """Host events named ``frontdoor.parse``, ``bench.search`` or
    ``bench.window`` of a trace directory (its newest ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return []
        path = found[-1]
    keep = (PARSE, SEARCH, WINDOW)
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if TR.is_device(plane.name):
                continue
            for tid, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name in keep:
                        events.append({"plane": plane.name,
                                       "line": line.name, "tid": tid,
                                       "name": e.name,
                                       "t0": float(e.start_ns),
                                       "dur": float(e.duration_ns)})
    return events


def ingest_us_per_search(events):
    lo, hi = TR.window_bounds(events, WINDOW)

    def inside(name):
        return [e for e in events if e["name"] == name
                and lo <= e["t0"] < hi]
    parses, searches = inside(PARSE), inside(SEARCH)
    if not parses or not searches:
        return None
    return sum(e["dur"] for e in parses) / 1e3 / len(searches)


def read(ctx, metric):
    from chip import harness as H
    d = H.TRACE_DIR / ctx["cell"].name
    return ingest_us_per_search(load(str(d))) if d.is_dir() else None
