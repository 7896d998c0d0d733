#!/usr/bin/env python3
"""Read what decides ``correct`` in a training cell on many seeds in
one process: the program's first call, the plain reference, its control
(float8 operands) and the planted fault "half of the batch left out",
each compared with the reference. What a builder runs on the chip to set
the cell's limits (``limits/<cell>.json``) or to try other data or
another schedule (``--override``); no window is measured.

    python3 benchmarks/tools/train_readings.py --workload <name> \
        --seeds 11,12,13 [--tag x] [--rehearse-cpu] \
        [--override '{"config": {...}, "traffic": {...}}']

A line a seed goes to ``chiprun_out/readings-<tag>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--override", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()
    import run as runner
    from harness import device, manifest, train_cell as tc
    from harness.spans import Recorder

    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        runner._rehearsal(cell)
    else:
        from zoo_tpu.common.compile_cache import ensure_compile_cache
        ensure_compile_cache()
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.override:
        over = json.loads(args.override)
        cell.config = runner._merge(cell.config, over.get("config", {}))
        cell.traffic = runner._merge(cell.traffic, over.get("traffic", {}))
    devs, _ = device.claim(cell.chips, args.rehearse_cpu)
    ref_mod = manifest.reference_of(cell.config)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"readings-{args.tag or args.workload}.jsonl")
    with open(path, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            job = tc.TrainedClassifier(cell, seed, devs, Recorder(), ref_mod)
            first = job.first_call()
            data = job.host_data
            job.close()
            ref = tc.reference_first_call(cell, seed, devs, ref_mod, data)
            rec = {"seed": seed, "override": args.override,
                   "program_loss": first["loss"], "reference_loss": ref["loss"],
                   "program_step_losses": first["step_losses"],
                   "reference_step_losses": ref["step_losses"]
                   + ref["epoch_losses"],
                   "program": tc.compare_first_call(first, ref)}
            for tag, kw in (("control", {"lower": True}),
                            ("half_batch", {"rows": 0.5})):
                bad = tc.reference_first_call(cell, seed, devs, ref_mod,
                                              data, **kw)
                rec[tag] = tc.compare_first_call(bad, ref)
                if tag == "control":
                    rec["control_loss"] = bad["loss"]
                    low = bad
            # every leaf's signed gap, program / control, for a look at
            # where a reading comes from
            rec["leaves"] = {
                what: {k: [first[what][k] / v - 1, low[what][k] / v - 1]
                       for k, v in ref[what].items() if v > 0}
                for what in ("moment", "change", "first_change")
                if what in first}
            rec["wall_s"] = time.perf_counter() - t
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps({k: v for k, v in rec.items() if k not in (
                "reference_step_losses", "leaves")}), flush=True)
            sl = rec["reference_step_losses"]
            print("    reference losses: " + " ".join(
                f"{x:.3g}" for x in sl[:8] + sl[-4:]), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
