#!/usr/bin/env python3
"""Can ``correct`` of ``minicpm-sala.ctx32k-closed`` see a fault in the
layers the cell exists for? Plant one in the PROGRAM, serve the cell's
request (32,768 + 2,048 tokens, greedy, the published widths) through
``LLMEngine``, and read it through the harness's own comparison
(``reference/minicpm_sala.py::served_gaps``, teacher forcing, the widest
gap of a served token below the reference's best): the number that the
cell's ``served_logit_gap_max`` limit judges.

    chiprun --timeout 1500 -- python3 scripts/check_sala_faults.py \\
        --seeds 4100000007,4200000011 [--faults none,forced_only,...] \\
        [--control 1] [--new-tokens 2048]

The faults (``none`` is the sound program; ``--control 1`` adds the
reference's lower precision on it):

* ``forced_only``  the selection keeps the forced pages alone (page 0
  and the last 2,048 tokens: 33 of the 64), in chunks and in ticks;
* ``sparse_zero``  a decode tick's sparse mixers give zeros (what a
  kernel that never ran leaves; the prompt is prefilled soundly);
* ``bf16_state``   the Lightning state is rounded to bfloat16 after
  every chunk and every tick (it is stated float32);
* ``stale_state``  a decode tick reads the state and does not write it
  back (it stays as the prompt left it).

One model a seed serves them all (a subclass whose hooks plant the
fault): the fault is a scalar leaf of every layer's weights, so the two
executables compile once. The engine is the
cell's but for its slots and pages (2 and 1,152: one request at a time
needs no more, and the reference then fits beside the weights); a
slot's arithmetic does not depend on either. Results go to
``chiprun_out/sala_faults.jsonl`` and, as a table, to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELL = "minicpm-sala.ctx32k-closed"
FAULTS = ("none", "forced_only", "sparse_zero", "bf16_state", "stale_state")


def faulty_class():
    """``(a PagedMiniCpmSalaModel whose hooks plant a fault, the faults'
    codes)``: ``p["fault"]``, an int32 scalar among every layer's
    leaves, switches one on inside the executables."""
    import jax
    import jax.numpy as jnp
    from zoo_tpu.serving.llm import model_sala as ms

    code = {name: i for i, name in enumerate(FAULTS)}

    def rounded(state):
        return jax.lax.reduce_precision(state, exponent_bits=8,
                                        mantissa_bits=7)

    class Faulty(ms.PagedMiniCpmSalaModel):
        def _attend_decode(self, p, x, cache, kind, i, slopes, at):
            f = self._fault = p["fault"]
            a, new, n = super()._attend_decode(p, x, cache, kind, i,
                                               slopes, at)
            if kind == ms.LIGHTNING:
                st = new["state"]
                st = jnp.where(f == code["bf16_state"], rounded(st), st)
                st = jnp.where(f == code["stale_state"], cache["state"], st)
                return a, dict(new, state=st), n
            return jnp.where(f == code["sparse_zero"], 0.0, a), new, n

        def _attend_rows(self, p, x, cache, kind, i, slopes, at):
            f = self._fault = p["fault"]
            a, new, n = super()._attend_rows(p, x, cache, kind, i, slopes,
                                             at)
            if kind == ms.LIGHTNING:
                st = new["state"]
                new = dict(new, state=jnp.where(f == code["bf16_state"],
                                                rounded(st), st))
            return a, new, n

        _attend_chunk = _attend_bucket = _attend_verify = _attend_rows

        def _page_scores(self, s, pos):
            score = super()._page_scores(s, pos)
            return jnp.where((self._fault == code["forced_only"])
                             & jnp.isfinite(score), -jnp.inf, score)

    return Faulty, code


def set_fault(model, code: int):
    import jax.numpy as jnp
    for p in model.params["blocks"]:
        p["fault"] = jnp.asarray(code, jnp.int32)


def serve(model, code, fault, prompt, new_tokens):
    """One request through a fresh engine over ``model``; its tokens."""
    from zoo_tpu.serving.llm.engine import LLMEngine

    set_fault(model, code[fault])
    eng = LLMEngine(model).start()
    try:
        t = time.perf_counter()
        h = eng.submit(prompt, new_tokens)
        while not h.done:
            time.sleep(0.05)
        if h.outcome != "ok":
            raise RuntimeError(f"{fault}: request ended {h.outcome}")
        return list(h.tokens), time.perf_counter() - t
    finally:
        eng.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    faults = args.faults.split(",")

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from harness import manifest, traffic as tg

    cell = manifest.Cell(manifest.load_benchmark(), CELL)
    if args.rehearse_cpu:
        import run as bench_run
        bench_run._rehearsal(cell)
    else:
        from zoo_tpu.common.compile_cache import ensure_compile_cache
        print(f"compile cache: {ensure_compile_cache()}", flush=True)
    import jax
    cfg = cell.config
    prompt_len, out_len = tg.closed_lengths(cell.traffic)
    new_tokens = args.new_tokens or out_len
    eng = cfg["engine"]
    context = eng["max_blocks_per_seq"] * eng["block_size"]
    small = dict(cfg, engine=dict(eng, num_slots=2,
                                  num_blocks=2 * eng["max_blocks_per_seq"]
                                  + 64))
    ref_mod = manifest.reference_of(cfg)
    adapter = manifest.adapter_of(cfg)
    from zoo_tpu.serving.llm import model_sala
    # the adapter builds whatever the module names at the call
    model_sala.PagedMiniCpmSalaModel, code = faulty_class()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.devnull if args.rehearse_cpu else os.path.join(
        ROOT, "chiprun_out", "sala_faults.jsonl"), "a")

    for seed in (int(s) for s in args.seeds.split(",")):
        prompt = tg.token_ids(np.random.default_rng([seed, 11]), prompt_len,
                              cfg["vocab_size"])
        model = adapter.model(small, adapter.weights(seed, cfg, ref_mod))
        served = {}
        for fault in faults:
            served[fault], took = serve(model, code, fault, prompt,
                                        new_tokens)
            print(f"seed {seed} {fault}: served {len(served[fault])} "
                  f"tokens in {took:.1f} s", flush=True)
        adapter.free(model)
        params = ref_mod.make_params(seed, cfg)
        try:
            for fault in faults:
                t = time.perf_counter()
                gaps, low = ref_mod.served_gaps(
                    params, cfg, prompt, served[fault], pad_to=context,
                    lower_too=bool(args.control) and fault == "none")
                rec = {"seed": seed, "fault": fault,
                       "device": jax.devices()[0].device_kind,
                       "positions": int(len(gaps)),
                       "served_logit_gap_max": float(gaps.max()),
                       "gap_p99": float(np.quantile(gaps, 0.99)),
                       "widest_gaps": [float(g) for g in
                                       np.sort(gaps)[::-1][:12]],
                       "tokens_off_best": float(np.mean(gaps > 0)),
                       "same_as_sound": float(np.mean(
                           np.asarray(served[fault])
                           == np.asarray(served[faults[0]]))),
                       "limit": cell.limits.get(
                           "served_logit_gap_max", {}).get("limit"),
                       "reference_s": time.perf_counter() - t}
                if low is not None:
                    rec["control_logit_gap_max"] = float(low.max())
                    rec["control_p99"] = float(np.quantile(low, 0.99))
                print(json.dumps(rec), flush=True)
                out.write(json.dumps(rec) + "\n")
                out.flush()
        finally:
            ref_mod.free(params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
