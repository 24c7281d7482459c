#!/usr/bin/env python3
"""The readings that a cell's limits of ``correct`` are set from, on the
chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds s1,s2,... [--control-seeds s1,s2,s3] [--fault <name>] \
        --seconds <s>

Runs the cell once per seed in one process, each with a short window at
the cell's own load and sizes, and prints every number the check
reads (``readings``) and those ``correct`` compares, each beside the
limit in the cell's traffic file. On the
control seeds the control (the reference in bfloat16) takes the
program's place; with ``--fault`` the program runs with that fault
(``faults.py``) planted.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.join(ROOT, "src"))


def readings(args) -> None:
    from chip import faults as F
    from chip import harness as H
    from chip import run as R
    name = args.workload
    H.require_chips(int(H.resolve(name).workload["chips"]))
    runs = [(s, False) for s in args.seeds.split(",") if s]
    runs += [(s, True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        run_args = R.parse(["--workload", name, "--seed", seed,
                            "--seconds", str(args.seconds), "--trace", "0"])
        t0 = H.now()
        hooks = F.hooks(args.fault) if args.fault else {}
        hooks["control"] = control
        read = {}
        res = R.execute(run_args, t_process=t0, hooks=hooks, readings=read)
        print(json.dumps({"seed": int(seed), "fault": args.fault,
                          "control": hooks["control"],
                          "correct": res["correct"],
                          "readings": read,
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "wall_s": H.now() - t0}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="", help="a name in faults.HOOKS")
    readings(ap.parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
