"""What every cell shares: the manifest, the cell's files found by name,
the device, the compile cache, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its
``config`` names ``configs/<config>.json`` (sizes, heads, precision) and
its ``traffic`` names ``traffic/<traffic>.json`` (the parameters of the
mix, and the ``driver`` module under ``drivers/`` that generates it).
Per-layer metrics are read by ``metrics/<name>.py``, the part of the
metric's name before its first dot. Adding a cell, a mix or a metric is
adding files and manifest entries.
"""
from __future__ import annotations

import importlib
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad manifest, no program)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclass
class Cell:
    """One workload with its configuration, traffic and metric entries."""
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, man: Optional[dict] = None,
            bench: Path = BENCH) -> Cell:
    """Find a cell's files by the names the manifest gives."""
    man = man if man is not None else manifest(bench.parents[1])
    wl = {w["name"]: w for w in man["workloads"]}
    if name not in wl:
        raise BenchError(f"no workload {name!r}; one of {sorted(wl)}")
    w = wl[name]
    cfgs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in cfgs:
        raise BenchError(f"workload {name!r} names no known config "
                         f"{w['config']!r}")
    config = load_json(bench.parents[1] / cfgs[w["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(name, w, config, traffic,
                [m for m in man["end_to_end"] if _applies(m, name)],
                [m for m in man["per_layer"] if _applies(m, name)])


def driver_module(name: str):
    return importlib.import_module(f"chip.drivers.{name}")


def metric_reader(metric_name: str) -> Callable:
    return importlib.import_module(
        f"chip.metrics.{metric_name.split('.')[0]}").read


# --------------------------------------------------------------- device
def require_chips(n: int) -> None:
    """Fail unless JAX's default backend is a TPU with ``n`` chips."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no device: {e}") from e
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (device 0 is "
                         f"{devs[0].platform!r}); this benchmark runs on "
                         f"the chip only")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, with
    every program written to it however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def start_trace(path) -> None:
    """Profile the device and the host's annotated spans; Python
    function tracing stays off (it would slow every host thread)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)


class _Unannotated:
    def __init__(self, name: str):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def annotation(traced: bool):
    """``jax.profiler.TraceAnnotation`` in a traced run, else a no-op:
    the benchmark's own host spans, on the profiler's clock."""
    if not traced:
        return _Unannotated
    import jax
    return jax.profiler.TraceAnnotation


def sleep_until(t: float) -> None:
    while True:
        left = t - now()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def settle() -> None:
    """Collect, then freeze what set-up built (inputs, weights, caches)
    so that the collector's full passes in the window do not walk it."""
    import gc
    gc.unfreeze()
    gc.collect()
    gc.freeze()


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


class CompileCounter:
    """Counts the backend compiles JAX reports while ``active`` (the
    window), and the persistent cache's hits and misses of the run."""

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.n = 0
        self.cache = {"hits": 0, "misses": 0}

        def on_duration(event, duration, **_):
            if self.active and event == COMPILE_EVENT:
                self.n += 1

        def on_event(event, **_):
            if event in CACHE_EVENTS:
                self.cache[CACHE_EVENTS[event]] += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


# --------------------------------------------------------------- result
@dataclass
class Check:
    """One number compared beside its limit (it passes at or below)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back once its window has closed."""
    setup_s: float
    window_s: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    # called once device memory has been read and the program's state
    # dropped: runs the reference, returns the numbers compared
    check: Callable[[], List[Check]]
    # every number the check reads, filled when it runs; those that a
    # limit names are compared
    readings: Dict[str, float] = field(default_factory=dict)
    # raw readings for the per-layer readers (counters, spans, proxies)
    layers: Dict[str, Any] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)


def checks(readings: Dict[str, float], limits: Dict[str, float]
           ) -> List[Check]:
    """The numbers that ``limits`` names, each beside its limit."""
    return [Check(k, float(readings[k]), float(v)) for k, v in limits.items()]


def metric_entry(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()

