"""From a profiler trace to the numbers the benchmark reports.

A trace is read once into plain events (plane, line, the line's index
in its plane, name, start and duration in nanoseconds), which is also
the format of the small traces kept under ``tests/data``. Host threads
are lines of one name (``python3``), told apart by that index. The reduction then needs no profiler:

* the window is the host span the run opened around it (a
  ``TraceAnnotation`` named ``bench.window``);
* busy time is the union of the intervals of the device's operations
  (its ``XLA Ops`` line) inside the window, averaged over the chips,
  and the idle share is one minus busy over the window;
* ``device_ops`` sums each operation's time in the window, largest
  first;
* ``idle_gaps`` sums the device's idle time by what the host was doing:
  the benchmark's own ``bench.*`` spans active at each gap's midpoint,
  the most common innermost one across host threads.

    python3 benchmarks/chip/trace_reduce.py <trace dir>   # summary
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
from collections import Counter, defaultdict
from typing import Dict, List, Sequence, Tuple

DEVICE_OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench."


def load_xplane(path: str) -> List[dict]:
    """Events of an ``.xplane.pb`` file (or the newest under a trace
    directory) as plain dicts."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        for tid, line in enumerate(plane.lines):
            for e in line.events:
                events.append({"plane": plane.name, "line": line.name,
                               "tid": tid, "name": e.name,
                               "t0": float(e.start_ns),
                               "dur": float(e.duration_ns)})
    return events


def load_events(path: str) -> List[dict]:
    """Events kept as JSON (optionally gzipped), as ``trim`` writes them."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def trim(events: Sequence[dict], path: str) -> None:
    """Write the device-op and ``bench.*`` events to a small JSON file."""
    keep = [e for e in events
            if (is_device(e["plane"]) and e["line"] == DEVICE_OPS_LINE)
            or e["name"].startswith(BENCH_PREFIX)]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(keep, f)


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The complement of merged ``busy`` within [lo, hi)."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def window_bounds(events: Sequence[dict], name: str) -> Tuple[float, float]:
    spans = [e for e in events if e["name"] == name
             and not is_device(e["plane"])]
    if not spans:
        raise ValueError(f"the trace holds no {name!r} span")
    e = max(spans, key=lambda e: e["dur"])
    return e["t0"], e["t0"] + e["dur"]


def _innermost(spans: List[Tuple[float, float, str]],
               times: Sequence[float]) -> List[str]:
    """For sorted ``times``, the innermost of one thread's nested spans
    active at each (None where none is), by one sweep."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, j = [], [], 0
    for t in times:
        while j < len(spans) and spans[j][0] <= t:
            while stack and stack[-1][1] <= spans[j][0]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def _labels(host: Dict[tuple, List[Tuple[float, float, str]]],
            times: Sequence[float]) -> List[str]:
    """What the host was doing at each of the sorted ``times``: the most
    common innermost ``bench.*`` span across threads (ties: the name that
    sorts first)."""
    per_thread = [_innermost(spans, times) for spans in host.values()]
    out = []
    for k in range(len(times)):
        votes = Counter(p[k] for p in per_thread if p[k] is not None)
        if not votes:
            out.append("no bench span")
            continue
        best = max(votes.values())
        out.append(sorted(n for n, v in votes.items() if v == best)[0])
    return out


def op_name(name: str) -> str:
    """An HLO op event's short name: ``%fusion.3 = f32[...] ...`` ->
    ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(events: Sequence[dict], window_name: str = "bench.window"
           ) -> dict:
    """Busy and idle time, top device ops and idle gaps in the window."""
    lo, hi = window_bounds(events, window_name)
    per_dev: Dict[str, list] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    host: Dict[tuple, list] = defaultdict(list)
    for e in events:
        a, b = e["t0"], e["t0"] + e["dur"]
        if is_device(e["plane"]) and e["line"] == DEVICE_OPS_LINE:
            per_dev[e["plane"]].append((a, b))
            c = clip([(a, b)], lo, hi)
            if c:
                op_time[op_name(e["name"])] += c[0][1] - c[0][0]
        elif (e["name"].startswith(BENCH_PREFIX)
              and e["name"] != window_name):
            host[(e["plane"], e["line"], e.get("tid", 0))].append(
                (a, b, e["name"]))
    if not per_dev:
        raise ValueError("the trace holds no device operations")
    busy_ns, idle = 0.0, defaultdict(float)
    for plane, iv in per_dev.items():
        merged = clip(union(iv), lo, hi)
        busy_ns += sum(b - a for a, b in merged)
        idle_iv = gaps(merged, lo, hi)
        mids = [(a + b) / 2 for a, b in idle_iv]
        for (a, b), name in zip(idle_iv, _labels(host, mids)):
            idle[name] += (b - a) / len(per_dev)
    busy_s = busy_ns / len(per_dev) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t / len(per_dev) / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(idle.items(), key=lambda x: -x[1])],
        "op_time_s": {n: t / len(per_dev) / 1e9 for n, t in op_time.items()},
        "n_devices": len(per_dev),
    }


def reduce_dir(path, window_name: str = "bench.window") -> dict:
    return reduce(load_xplane(str(path)), window_name)


def summary(events: Sequence[dict]) -> str:
    lines = Counter((e["plane"], e["line"]) for e in events)
    names = defaultdict(Counter)
    for e in events:
        names[(e["plane"], e["line"])][e["name"]] += 1
    out = []
    for (plane, line), n in sorted(lines.items()):
        top = ", ".join(f"{k} x{v}" for k, v in
                        names[(plane, line)].most_common(4))
        out.append(f"{plane} | {line}: {n} events; {top}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(load_xplane(sys.argv[1])))
