"""Correctness gates over BENCH_*.json records (dispatched on bench name).

* ``opt_search`` — fails if beam search's mean *oracle* latency
  improvement fell below the greedy one-shot fusion baseline's (within
  ``--opt-tolerance``) — i.e. if the model-guided search stopped beating
  the single-rule advisor it replaced.
* ``ingest`` — fails if the arch corpus stops ingesting cleanly (any
  structured error or any collapse onto bare ``<unk>``), or if the fuzz
  corpus shrinks below 200 inputs or escapes the never-raises contract.
* ``chaos_serve`` — fails if the supervised tier under the scripted
  fault plan (kill/wedge/drop/delay/dup/corrupt) dropped below
  ``--chaos-availability`` availability, let any non-degraded reply
  diverge bit-wise from the fault-free reference, failed to land or
  recover both process faults within ``--chaos-recovery-s``, never
  returned to clean parity after the plan drained, or stopped
  publishing supervisor/router counters to the obs registry.

    python benchmarks/gate.py bench-artifacts/BENCH_opt_search.json
    python benchmarks/gate.py bench-artifacts/BENCH_ingest.json
    python benchmarks/gate.py bench-artifacts/BENCH_chaos_serve.json
"""
from __future__ import annotations

import argparse
import json
import sys


def gate_opt_search(rec, args) -> int:
    s = rec["result"]["summary"]
    beam = s["oracle_improvement_mean"]
    base = s["baseline_oracle_improvement_mean"]
    print(f"opt_search: beam oracle improvement {beam:.1%} vs one-shot "
          f"fusion baseline {base:.1%} "
          f"(gate: beam >= baseline - {args.opt_tolerance:.1%}; "
          f"strictly better on "
          f"{s['frac_strictly_better_than_baseline']:.0%} of graphs)")
    if beam < base - args.opt_tolerance:
        print("PERF GATE FAILED: beam search is not matching the greedy "
              "single-rule fusion baseline on the oracle", file=sys.stderr)
        return 1
    print("perf gate passed")
    return 0


def gate_ingest(rec, args) -> int:
    """Hard robustness gate on the real-MLIR front door: the arch
    corpus must ingest without a single structured error or collapse
    onto bare ``<unk>``, and the fuzz corpus must never escape the
    never-raises contract."""
    r = rec["result"]
    arch, fuzz = r["arch"], r["fuzz"]
    print(f"ingest: {arch['texts']} arch texts "
          f"(errors={arch['errors']}, "
          f"unk_rate_max={arch['unk_rate_max']:.3f}, "
          f"oov_rate_mean={arch['oov_rate_mean']:.3f}); "
          f"fuzz n={fuzz['n']} uncaught={fuzz['uncaught']}")
    rc = 0
    if arch["errors"] != 0:
        print("INGEST GATE FAILED: real-arch lowered texts no longer "
              "ingest cleanly", file=sys.stderr)
        rc = 1
    if arch["unk_rate_max"] != 0.0:
        print("OOV GATE FAILED: some arch-corpus tokens collapsed onto "
              "bare <unk> despite shard/byte fallback", file=sys.stderr)
        rc = 1
    if fuzz["n"] < 200:
        print("FUZZ GATE FAILED: fuzz corpus shrank below 200 inputs",
              file=sys.stderr)
        rc = 1
    if fuzz["uncaught"] != 0:
        print("FUZZ GATE FAILED: predict_text raised instead of "
              "returning a structured IngestError", file=sys.stderr)
        rc = 1
    if rc == 0:
        print("ingest gate passed")
    return rc


def gate_chaos_serve(rec, args) -> int:
    """Robustness gate on the supervised tier under scripted chaos:
    availability across chaos rounds, bounded in-slot recovery, zero
    bit-level divergence of non-degraded replies from the fault-free
    reference, the kill+wedge events actually landing, a clean final
    round after the plan drains, and the supervisor/router counters
    present in the obs registry snapshot."""
    r = rec["result"]
    print(f"chaos_serve: {r['rounds']} rounds, availability "
          f"{r['availability']:.3f} (gate: >= "
          f"{args.chaos_availability:.2f}); diverged={r['diverged']} "
          f"over {r['non_degraded_rounds']} non-degraded rounds "
          f"(gate: == 0); degraded_rounds={r['degraded_rounds']}; "
          f"restarts={r['restarts_total']} "
          f"recovered={r['restarts_recovered']} "
          f"recovery_s_max={r['recovery_s_max']:.1f}s (gate: <= "
          f"{args.chaos_recovery_s:.0f}s); "
          f"faults_applied={r['faults_applied']}; "
          f"final_clean={r['final_clean']}; "
          f"obs_counters_present={r['obs_counters_present']}")
    rc = 0
    if r["availability"] < args.chaos_availability:
        print("CHAOS GATE FAILED: availability under fault injection "
              "fell below the floor", file=sys.stderr)
        rc = 1
    if r["diverged"] != 0:
        print("CHAOS GATE FAILED: a non-degraded reply diverged from "
              "the fault-free reference (wrong answer under chaos)",
              file=sys.stderr)
        rc = 1
    if not (r["kill_applied"] and r["wedge_applied"]
            and r["plan_exhausted"]):
        print("CHAOS GATE FAILED: the fault schedule did not fully "
              "land (kill/wedge missing or plan not drained) — the "
              "run proved nothing", file=sys.stderr)
        rc = 1
    if r["restarts_recovered"] < 2:
        print("CHAOS GATE FAILED: the supervisor did not recover both "
              "the killed and the wedged replica", file=sys.stderr)
        rc = 1
    if r["recovery_s_max"] > args.chaos_recovery_s:
        print("CHAOS GATE FAILED: in-slot respawn exceeded the "
              "recovery-time bound", file=sys.stderr)
        rc = 1
    if not r["final_clean"]:
        print("CHAOS GATE FAILED: the tier never returned to "
              "non-degraded bit-parity after the plan drained",
              file=sys.stderr)
        rc = 1
    if not r["obs_counters_present"]:
        print("CHAOS GATE FAILED: supervisor/router counters are "
              "missing from the obs registry snapshot",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print("chaos gate passed")
    return rc


GATES = {
    "opt_search": gate_opt_search,
    "ingest": gate_ingest,
    "chaos_serve": gate_chaos_serve,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("record", help="path to a BENCH_<name>.json record")
    ap.add_argument("--opt-tolerance", type=float, default=0.01,
                    help="opt_search: slack on beam-vs-baseline oracle "
                         "improvement (absolute)")
    ap.add_argument("--chaos-availability", type=float, default=0.99,
                    help="chaos_serve: minimum fraction of chaos-loop "
                         "rounds answered without an exception (the "
                         "graceful-degradation floor)")
    ap.add_argument("--chaos-recovery-s", type=float, default=120.0,
                    help="chaos_serve: maximum seconds for one in-slot "
                         "respawn to report recovered (spawn + JAX "
                         "import + warmup on a shared runner)")
    args = ap.parse_args()
    with open(args.record) as f:
        rec = json.load(f)
    gate = GATES.get(rec.get("bench"))
    if gate is None:
        print(f"no gate defined for bench {rec.get('bench')!r}; skipping")
        return 0
    return gate(rec, args)


if __name__ == "__main__":
    sys.exit(main())
