#!/usr/bin/env python3
"""Run one cell several times, one process a run, and say how far the
runs spread: what a builder does on the chip to set a bound or a limit.

    python3 benchmarks/tools/prove.py --workload <name> --seeds 11,12,13 \
        [--sets 2] [--seconds 45] [--trace 0] [--control 0] [--tag x] \
        [--override '{"config": {...}, "traffic": {...}}']

Every run's result line, with the lines the run printed before it
(census, engine, peak, reference time), goes to
``chiprun_out/prove-<tag>.jsonl``; the table at the end has, for every
metric, the median and the spread (first to third quartile of
``statistics.quantiles(values, n=4)`` over the median) of each set. It
never touches jax itself, so the chip is the child's alone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEEP = ("census:", "engine:", "peak_bytes_in_use=", "reference_s=",
        "compile cache:", "errors:", "late:", "modules:", "gc:",
        "idle_by_program_span:")


def spread(values) -> float:
    """Inter-quartile distance as a share of the median."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = abs(statistics.median(values))
    return (q[2] - q[0]) / med if med else float("nan")


def one_run(args, seed: int, set_no: int) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    cmd = command + ["--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds),
                     "--trace", str(args.trace)]
    if args.control:
        cmd += ["--control", "1"]
    if args.override:
        cmd += ["--override", args.override]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    took = time.perf_counter() - t
    lines = p.stdout.strip().splitlines()
    rec = {"workload": args.workload, "seed": seed, "set": set_no,
           "trace": args.trace, "seconds": args.seconds, "rc": p.returncode,
           "wall_s": took,
           "said": [ln for ln in lines[:-1] if ln.startswith(KEEP)]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["result"] = None
        rec["stdout_tail"] = lines[-5:]
    if p.returncode != 0 or rec["result"] is None \
            or not rec["result"].get("correct"):
        rec["stderr_tail"] = p.stderr[-3000:]
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--override", default=None)
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    tag = args.tag or args.workload
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"prove-{tag}.jsonl")
    recs = []
    with open(path, "a") as f:
        for s in range(args.sets):
            for seed in seeds:
                rec = one_run(args, seed, s)
                recs.append(rec)
                f.write(json.dumps(rec) + "\n")
                f.flush()
                r = rec["result"] or {}
                print(f"set {s} seed {seed} rc={rec['rc']} "
                      f"wall={rec['wall_s']:.1f}s correct="
                      f"{r.get('correct')} " + " ".join(
                          f"{k}={v['value']:.6g}"
                          for k, v in r.get("metrics", {}).items())
                      + " | " + " ".join(
                          f"{k}={v['value']}"
                          for k, v in r.get("compared", {}).items())
                      + " | " + " ".join(
                          f"{k}={v:.5g}"
                          for k, v in r.get("also_read", {}).items()),
                      flush=True)
                for ln in rec["said"]:
                    if ln.startswith(("census:", "reference_s=")):
                        print("    " + ln[:400], flush=True)
                if "stderr_tail" in rec:
                    print(rec["stderr_tail"][-1500:], flush=True)
    names = sorted({k for r in recs if r["result"]
                    for k in r["result"]["metrics"]})
    print(f"\n{args.workload}: {len(seeds)} seeds x {args.sets} sets, "
          f"{args.seconds:g} s, trace {args.trace}")
    for name in names:
        row = []
        for s in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in recs
                    if r["set"] == s and r["result"]
                    and name in r["result"]["metrics"]]
            if name == "setup_s" and s == 0 and len(vals) > 1:
                vals = vals[1:]          # the first run may compile
            if vals:
                row.append(f"set{s} median {statistics.median(vals):.6g} "
                           f"spread {100 * spread(vals):.3f}% "
                           f"[{min(vals):.6g}..{max(vals):.6g}]")
        print(f"  {name}: " + "; ".join(row))
    bad = [r for r in recs if r["rc"] != 0 or not r["result"]
           or not r["result"].get("correct")]
    print(f"  runs not correct: {len(bad)} of {len(recs)}")
    if any(r["rc"] != 0 or not r["result"] for r in recs):
        return 2                 # a run gave no result at all
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
