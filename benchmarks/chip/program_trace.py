"""The program's own layer spans in a run's profile, for the per-layer
readers that look inside the program.

The program marks its layers on the profiler's clock while a profile is
recorded (``repro.obs.trace.annotate``): ``search.beam``,
``client.predict_all``, ``server.featurize``/``wait``/``dispatch``/
``collect``/``resolve`` and ``train.batch``/``place``/``step``, with
their arguments as event stats. A program without them (an older
commit) leaves every reading here ``None``.

The run's xplane is loaded once (cached by path), keeping the device's
``XLA Ops``, the benchmark's ``bench.*`` spans and the program spans
with their ``args``. Events are windowed by ``bench.window`` with
``trace_reduce``'s bounds, and device idle time is the gaps between the
union of device operations, as ``trace_reduce`` computes it.

    python3 benchmarks/chip/program_trace.py <trace dir or .json.gz>

prints the device's idle time in the window by the innermost program
span active at each gap (the most common across host threads).
"""
from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import sys
import warnings
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from chip import trace_reduce as TR  # noqa: E402

PROGRAM_PREFIXES = ("search.", "client.", "server.", "train.")
WINDOW = "bench.window"


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def _kept(plane: str, line: str, name: str) -> bool:
    return ((TR.is_device(plane) and line == TR.DEVICE_OPS_LINE)
            or name.startswith(TR.BENCH_PREFIX) or is_program(name))


@functools.lru_cache(maxsize=1)
def _load_xplane(path: str, mtime_ns: int) -> Tuple[dict, ...]:
    from jax.profiler import ProfileData
    events = []
    with warnings.catch_warnings():     # the stats type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for tid, line in enumerate(plane.lines):
                for e in line.events:
                    if not _kept(plane.name, line.name, e.name):
                        continue
                    ev = {"plane": plane.name, "line": line.name,
                          "tid": tid, "name": e.name,
                          "t0": float(e.start_ns),
                          "dur": float(e.duration_ns)}
                    if is_program(e.name):
                        ev["args"] = dict(e.stats)
                    events.append(ev)
    return tuple(events)


def load(path) -> Sequence[dict]:
    """Events of a trace directory (its newest ``.xplane.pb``), of an
    ``.xplane.pb`` file, or of a JSON file that :func:`trim` wrote."""
    path = str(path)
    if path.endswith((".json", ".json.gz")):
        return TR.load_events(path)
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return _load_xplane(path, os.stat(path).st_mtime_ns)


def events_for(ctx) -> Optional[Sequence[dict]]:
    """The traced run's events (the readers run before ``run.py``
    removes the trace directory), or None where there is no trace."""
    from chip import harness as H
    d = H.TRACE_DIR / ctx["cell"].name
    return load(d) if d.is_dir() else None


def trim(events: Sequence[dict], path: str, seconds: float = 1.0) -> None:
    """Keep the first ``seconds`` of the window: the device operations,
    ``bench.*`` and program spans that overlap it, with ``bench.window``
    cut to it. Written as (gzipped) JSON for the tests' data."""
    lo, _ = TR.window_bounds(events, WINDOW)
    hi = lo + seconds * 1e9
    keep = []
    for e in events:
        if e["t0"] >= hi or e["t0"] + e["dur"] <= lo:
            continue
        if e["name"] == WINDOW and not TR.is_device(e["plane"]):
            e = dict(e, t0=lo, dur=hi - lo)
        keep.append(e)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(keep, f)


# ------------------------------------------------------------ windowing
def spans(events: Sequence[dict], name: str) -> List[dict]:
    """The host events named ``name``."""
    return [e for e in events
            if e["name"] == name and not TR.is_device(e["plane"])]


def _clipped_s(evs: Sequence[dict], lo: float, hi: float) -> float:
    return sum(b - a for a, b in TR.clip(
        [(e["t0"], e["t0"] + e["dur"]) for e in evs], lo, hi)) / 1e9


def device_idle(events: Sequence[dict], lo: float, hi: float
                ) -> Dict[str, List[Tuple[float, float]]]:
    """Each device's idle intervals in [lo, hi): the gaps between the
    union of its ``XLA Ops`` (``trace_reduce``'s busy time)."""
    per_dev = defaultdict(list)
    for e in events:
        if TR.is_device(e["plane"]) and e["line"] == TR.DEVICE_OPS_LINE:
            per_dev[e["plane"]].append((e["t0"], e["t0"] + e["dur"]))
    return {d: TR.gaps(TR.clip(TR.union(iv), lo, hi), lo, hi)
            for d, iv in per_dev.items()}


def _overlap(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists, by one merge."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_under(events: Sequence[dict],
                     intervals: Sequence[Tuple[float, float]]
                     ) -> Optional[float]:
    """Share (%) of the devices' idle time in the window that falls
    inside the union of host ``intervals``; None without a device."""
    lo, hi = TR.window_bounds(events, WINDOW)
    idle = device_idle(events, lo, hi)
    if not idle:
        return None
    cover = TR.union(intervals)
    total = sum(b - a for iv in idle.values() for a, b in iv)
    if total <= 0:
        return 0.0
    return 100.0 * sum(_overlap(iv, cover) for iv in idle.values()) / total


# --------------------------------------------------------------- metrics
def queue_wait_us_per_req(events: Sequence[dict]) -> Optional[float]:
    """Mean queue wait of the requests the gateway dispatched in the
    window: sum of ``wait_us`` over sum of ``rows``, over the
    ``server.dispatch`` spans that start in it."""
    lo, hi = TR.window_bounds(events, WINDOW)
    inside = [e["args"] for e in spans(events, "server.dispatch")
              if lo <= e["t0"] < hi]
    rows = sum(a["rows"] for a in inside)
    return sum(a["wait_us"] for a in inside) / rows if rows else None


def caller_wait_share(events: Sequence[dict]) -> Optional[float]:
    """Share (%) of the callers' time in ``client.predict_all`` that they
    spent blocked on their futures (``server.wait``), in the window."""
    lo, hi = TR.window_bounds(events, WINDOW)
    total = _clipped_s(spans(events, "client.predict_all"), lo, hi)
    if total <= 0:
        return None
    return 100.0 * _clipped_s(spans(events, "server.wait"), lo, hi) / total


def idle_queued_share(events: Sequence[dict]) -> Optional[float]:
    """Share (%) of device idle time in the window during which the
    gateway held a queued request: the union over ``server.dispatch`` of
    [its start - ``oldest_wait_us``, its start]."""
    held = [(e["t0"] - 1e3 * e["args"]["oldest_wait_us"], e["t0"])
            for e in spans(events, "server.dispatch")]
    return idle_share_under(events, held) if held else None


def idle_input_share(events: Sequence[dict]) -> Optional[float]:
    """Share (%) of device idle time in the window during which the
    train loop was fetching (``train.batch``) or placing
    (``train.place``) its next batch."""
    fed = [(e["t0"], e["t0"] + e["dur"])
           for n in ("train.batch", "train.place")
           for e in spans(events, n)]
    return idle_share_under(events, fed) if fed else None


def idle_by_span(events: Sequence[dict]) -> List[Tuple[str, float]]:
    """Device idle seconds in the window by the innermost program span
    active at each gap's midpoint (most common across host threads),
    averaged over devices, largest first."""
    lo, hi = TR.window_bounds(events, WINDOW)
    host = defaultdict(list)
    for e in events:
        if is_program(e["name"]) and not TR.is_device(e["plane"]):
            host[(e["plane"], e["line"], e.get("tid", 0))].append(
                (e["t0"], e["t0"] + e["dur"], e["name"]))
    idle = device_idle(events, lo, hi)
    out = defaultdict(float)
    for iv in idle.values():
        mids = [(a + b) / 2 for a, b in iv]
        for (a, b), name in zip(iv, TR._labels(host, mids)):
            label = "no program span" if name == "no bench span" else name
            out[label] += (b - a) / len(idle) / 1e9
    return sorted(out.items(), key=lambda x: -x[1])


if __name__ == "__main__":
    ev = load(sys.argv[1])
    lo, hi = TR.window_bounds(ev, WINDOW)
    print(f"window {(hi - lo) / 1e9:.6f} s; device idle by the innermost "
          f"program span:")
    for name, s in idle_by_span(ev):
        print(f"  {name}: {s:.6f} s")
    for fn in (queue_wait_us_per_req, caller_wait_share, idle_queued_share,
               idle_input_share):
        print(f"{fn.__name__}: {fn(ev)}")
