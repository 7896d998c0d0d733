#!/usr/bin/env python3
"""What decides ``correct`` in a cell of a mixture-of-experts decoder,
read position by position on the chip, a seed at a time.

For every seed: the weights, then as many prompts as the cell checks
(``checked_requests``), of the cell's own lengths, are prefilled in the configuration's chunks and decoded
greedily, sharing ticks, through the model the adapter builds (the
executables the engine drives; no scheduler, no wire). The program is
freed and the reference runs over each prompt with its served tokens
three times: in float32 (the gap of every served token below the
reference's best, and every position's routing margin), with float8
operands (the harness's control) and with the router ALONE in bfloat16
(the control of the near-tie rule: a program that narrowed the router
where the configuration states float32). The gaps are what
``served_gaps`` returns before ``compare.router_margin_min`` takes
positions out; the tool applies the same rule for a list of thresholds
and prints, per seed and threshold, the share kept and the largest gap
kept of the program and of either control. Every array goes to
``--out`` (.npz) for a later look.

    python3 benchmarks/tools/moe_margin_readings.py \\
        --workload glm-4.7-flash.longctx-closed --seeds 2147484101,2147484102

``--rehearse-cpu`` runs it at the toy widths of ``rehearsal/`` (a check
of the tool, never a reading).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import numpy as np  # noqa: E402

THRESHOLDS = (0.0, 0.00025, 0.0005, 0.001, 0.0015, 0.002, 0.004)


def serve(model, cfg: dict, prompts, new: int):
    """Greedy tokens of the prompts: chunked prefill a prompt at a
    time, then decode ticks that all of them share."""
    eng = cfg["engine"]
    bs, chunk = eng["block_size"], eng["prefill_chunk"]
    tables = np.zeros((eng["num_slots"], eng["max_blocks_per_seq"]),
                      np.int32)
    served = []
    for i, prompt in enumerate(prompts):
        need = -(-(len(prompt) + new) // bs)
        tables[i, :need] = 1 + i * need + np.arange(need)
        for start in range(0, len(prompt), chunk):
            tok = model.prefill_chunk(prompt[start:start + chunk], start,
                                      len(prompt), tables[i])
        served.append([int(tok)])
    toks = np.zeros((eng["num_slots"],), np.int32)
    pos = np.zeros((eng["num_slots"],), np.int32)
    for step in range(new - 1):
        for i, prompt in enumerate(prompts):
            toks[i], pos[i] = served[i][-1], len(prompt) + step
        out = model.decode(toks, tables, pos)
        for i in range(len(prompts)):
            served[i].append(int(out[i]))
    return [np.asarray(s, np.int32) for s in served]


def position_gaps(ref, params, cfg: dict, prompt, served, pad_to: int):
    """``(program, float8, router_bf16, margin)``, one value a served
    position: ``served_gaps`` without its rule."""
    import jax.numpy as jnp
    n, p = len(served), len(prompt)
    inputs = np.concatenate([prompt, served[:-1]])
    tokens = np.zeros((-(-len(inputs) // pad_to) * pad_to,), np.int32)
    tokens[:len(inputs)] = inputs
    rows = np.arange(p - 1, p - 1 + n).astype(np.int32)
    logits, margin = ref.logits_at(params, cfg, tokens, rows)

    def below(chosen):
        return np.asarray(ref.gap_below_best(
            logits, jnp.asarray(chosen, jnp.int32)))

    def first(**kw):
        return jnp.argmax(ref.logits_at(params, cfg, tokens, rows, **kw)[0],
                          axis=-1)

    return (below(served), below(first(lower=True)),
            below(first(router_bf16=True)), np.asarray(margin))


def main():
    from harness import manifest
    from zoo_tpu.common.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="glm-4.7-flash.longctx-closed")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    ensure_compile_cache()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if args.rehearse_cpu:
        import run
        run._rehearsal(cell)
    cfg, traffic = cell.config, cell.traffic
    adapter, ref = manifest.adapter_of(cfg), manifest.reference_of(cfg)
    plen = traffic["prompt_tokens"]["value"]
    new = traffic["output_tokens"]["value"]
    context = cfg["engine"]["max_blocks_per_seq"] * cfg["engine"]["block_size"]
    kept = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
                   for _ in range(traffic["checked_requests"])]
        model = adapter.model(cfg, adapter.weights(seed, cfg, ref))
        served = serve(model, cfg, prompts, new)
        adapter.free(model)
        t1 = time.time()
        params = ref.make_params(seed, cfg)
        cols = [np.concatenate(c) for c in zip(*(
            position_gaps(ref, params, cfg, p, s, context)
            for p, s in zip(prompts, served)))]
        ref.free(params)
        prog, f8, router, margin = cols
        kept[str(seed)] = np.stack(cols)
        for thr in THRESHOLDS:
            keep = margin >= thr
            print(json.dumps({
                "seed": seed, "router_margin_min": thr,
                "kept": round(float(keep.mean()), 4),
                "program_gap_max": float(prog[keep].max()),
                "router_bf16_gap_max": float(router[keep].max()),
                "float8_gap_max": float(f8[keep].max())}), flush=True)
        print(json.dumps({
            "seed": seed, "positions": len(margin),
            "margin_median": float(np.median(margin)),
            "program_gap_over_0.05": float((prog > 0.05).mean()),
            "router_bf16_gap_over_0.05": float((router > 0.05).mean()),
            "serve_s": round(t1 - t0, 1),
            "reference_s": round(time.time() - t1, 1)}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        np.savez(args.out, **kept)


if __name__ == "__main__":
    main()
