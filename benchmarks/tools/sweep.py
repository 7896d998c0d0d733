#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest
rate the system sustains. One process, one engine; the cell's own
traffic file at each of some rates, a window and a drain each.

    python3 benchmarks/tools/sweep.py --workload mistral-7b.chat-open \
        --rates 1.5,2,2.5,3,3.5,4 --seconds 30 --seed 7

A rate is sustained where the backlog does not grow through the window:
the requests of its second half wait no longer for their first token
than those of its first half, and few streams are left waiting at the
close. The table goes to standard output and, as JSON lines, to
``chiprun_out/sweep-<workload>.jsonl``. The builder writes 0.8 x the
knee into the traffic file as ``rate_rps``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, BENCH)
    sys.path.insert(1, ROOT)
    import run as runner
    from harness import device, manifest, serve_cell as sc
    from harness.readers import percentile
    from harness.spans import Recorder

    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        runner._rehearsal(cell)
    else:
        from zoo_tpu.common.compile_cache import ensure_compile_cache
        ensure_compile_cache()
    devs, _ = device.claim(cell.chips, args.rehearse_cpu)
    ref_mod = manifest.reference_of(cell.config)
    rec = Recorder()
    sys_ = sc.ServedDecoder(cell.config, args.seed, rec, ref_mod)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            cell.traffic["rate_rps"] = rate
            win = sc.run_open(sys_, cell, args.seed, args.seconds,
                              lambda t0, t1: None, T_START)
            reqs = win["requests"]
            t0 = win["t0"]
            half = args.seconds / 2
            ttft = lambda rs: [(r.token_times[0] - (t0 + r.due)) * 1e3
                               for r in rs if r.token_times]
            first = ttft([r for r in reqs if r.due < half])
            second = ttft([r for r in reqs if r.due >= half])
            done_in = sum(1 for r in reqs for t in r.token_times
                          if t0 <= t < win["t1"])
            last = max((r.token_times[-1] for r in reqs if r.token_times),
                       default=win["t1"])
            waiting = rec.samples_in("zoo_llm_waiting_streams",
                                     t0, win["t1"])
            occ = rec.samples_in("zoo_llm_slot_occupancy", t0, win["t1"])
            row = {
                "rate_rps": rate, "requests": len(reqs),
                "failed": win["failed"],
                "ttft_p50_ms": percentile(ttft(reqs), 50),
                "ttft_p95_ms": win["end_to_end"]["ttft_p95_ms"],
                "itl_p99_ms": win["end_to_end"]["itl_p99_ms"],
                "ttft_p50_first_half_ms": percentile(first, 50),
                "ttft_p50_second_half_ms": percentile(second, 50),
                "tokens_per_s_in_window": done_in / args.seconds,
                "tokens_offered_per_s": sum(r.max_new for r in reqs)
                / args.seconds,
                "drain_s": last - win["t1"],
                "occupancy_mean": statistics.fmean(occ) if occ else None,
                "waiting_max": max(waiting) if waiting else None,
                "waiting_at_close": waiting[-1] if waiting else None,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(
                    out_dir, f"sweep-{args.workload}.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        sys_.close()
    print("\nrate  reqs  ttft_p50  ttft_p95  itl_p99  p50 2nd/1st  "
          "tok/s  offered  drain_s  occ  wait_max")
    for r in rows:
        ratio = (r["ttft_p50_second_half_ms"] / r["ttft_p50_first_half_ms"]
                 if r["ttft_p50_first_half_ms"] else float("nan"))
        print(f"{r['rate_rps']:<5g} {r['requests']:<5d} "
              f"{r['ttft_p50_ms']:<9.1f} {r['ttft_p95_ms']:<9.1f} "
              f"{r['itl_p99_ms']:<8.1f} {ratio:<12.2f} "
              f"{r['tokens_per_s_in_window']:<6.1f} "
              f"{r['tokens_offered_per_s']:<8.1f} {r['drain_s']:<8.1f} "
              f"{(r['occupancy_mean'] or 0):<4.1f} "
              f"{(r['waiting_max'] or 0):g}")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
