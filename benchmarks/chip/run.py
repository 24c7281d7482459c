#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; see
``harness.py`` for how its files are found. The run makes its weights
and inputs from ``--seed``, warms every shape it will use (set-up),
measures for ``--seconds``, then compares what the timed path produced
with the plain reference (``reference.py``). With ``--trace 1`` it
records a profiler trace of the window and reports the per-layer
metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``busy_s`` and
``window_s`` too when traced), optionally ``breakdown``, and last
``checks``, each compared number beside its limit. The same numbers
close standard error. A JAX that finds no TPU, or fewer chips than the
cell asks for, exits non-zero with no result line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.join(ROOT, "src"))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, require_chip: bool = True, cell=None,
            t_process: float = T_PROCESS, hooks=None,
            readings: dict = None) -> dict:
    """Set up, measure and check one cell; return the result object.

    Tests pass ``require_chip=False`` with a small ``cell`` and ``hooks``
    (faults planted under the timed path); the command line never does.
    ``readings``, where given, receives every number the check read,
    compared or not (``calibrate.py``)."""
    from chip import harness as H
    cell = cell or H.resolve(args.workload)
    if require_chip:
        H.require_chips(int(cell.workload["chips"]))
        H.enable_compile_cache()
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        raise H.BenchError(f"cannot import the program: {e}") from e
    from chip import trace_reduce as TR
    driver = H.driver_module(cell.driver)
    counter = H.CompileCounter()
    trace_dir = None
    if args.trace:
        trace_dir = H.TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace_dir=trace_dir, t_process=t_process,
                     compiles=counter, hooks=hooks or {})
    device = H.device_info(int(cell.workload["chips"]))
    gc.collect()
    checks = out.check()
    correct = bool(checks) and all(c.ok for c in checks)
    if readings is not None:
        readings.update(out.readings)
    if counter.n:
        out.notes.append(f"{counter.n} compiles inside the window")
    out.notes.append("compile cache: {hits} programs loaded, {misses} "
                     "compiled".format(**counter.cache))
    if hooks and hooks.get("control"):
        out.notes.append("control: the reference in bfloat16 stands in "
                         "for the program")
    metrics = {}
    breakdown = None
    if args.trace:
        red = TR.reduce_dir(trace_dir)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        ctx = {"cell": cell, "outcome": out, "trace": red,
               "device": device}
        for m in cell.per_layer:
            v = H.metric_reader(m["name"])(ctx, m)
            if v is not None:
                metrics[m["name"]] = H.metric_entry(v, m["unit"])
        if os.environ.get("BENCH_KEEP_TRACE") is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = H.metric_entry(out.setup_s, "s")
            elif m["name"] in out.end_to_end:
                metrics[m["name"]] = H.metric_entry(
                    out.end_to_end[m["name"]], m["unit"])
    for note in out.notes:
        H.log(f"note: {note}")
    for c in checks:
        H.log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}")
    res = {"correct": correct, "attempted": int(out.attempted),
           "failed": int(out.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return res


def main(argv=None) -> int:
    args = parse(argv)
    try:
        from chip import harness as H
    except ImportError as e:
        print(f"run.py: cannot load the benchmark: {e}", file=sys.stderr)
        return 2
    try:
        res = execute(args)
    except H.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        print("run.py: the run failed", file=sys.stderr)
        return 1
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
