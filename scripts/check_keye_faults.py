#!/usr/bin/env python3
"""Can ``correct`` of ``keye-vl-2.0.doc32k-closed`` see a fault in the
layers the cell exists for? Plant one in the PROGRAM, serve the cell's
request (32,768 + 1,024 tokens, greedy, the published widths) through
``LLMEngine``, and read it through the harness's own comparison
(``reference/keye_vl2.py``: teacher forcing, the widest gap of a served
token below the reference's best, with the configuration's rules for
near-ties): the number that the cell's ``served_logit_gap_max`` limit
judges.

    python3 scripts/check_keye_faults.py --seeds 4200000007,4200000011 \\
        [--faults none,dense,...] [--control 1] [--new-tokens 1024]

on a TPU (about 4.5 minutes a seed with the control; ``--rehearse-cpu``
runs it at toy widths on the CPU).

The faults (``none`` is the sound program; ``--control 1`` adds the
reference's lower precision on it):

* ``dense``    every query attends every cached token at or before it:
  dense attention in place of the selection, in chunks and in ticks;
* ``no_relu``  the indexer's score without its ReLU, ``sum_j w_j (q_I,j .
  k_I)``: another selection;
* ``no_w``     the indexer's score without its heads' weights, ``sum_j
  ReLU(q_I,j . k_I)``: another selection;
* ``sigmoid``  the experts routed by sigmoid scores (no bias) in place of
  the softmax.

One model a seed serves them all (a subclass whose hooks plant the
fault): the fault is a scalar leaf of every layer's weights, so the two
executables compile once. The engine is the cell's but for its slots and
pages (2 and 2 x 528 + 64: one request at a time needs no more, and the
reference then fits beside the weights); a slot's arithmetic does not
depend on either. Besides the comparison under the configuration's
rules, each record gives the widest gap with no rule and with the rules
at other thresholds (``by_rule``), which is how the rules were set.
Results go to ``chiprun_out/keye_faults.jsonl`` and to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELL = "keye-vl-2.0.doc32k-closed"
FAULTS = ("none", "dense", "no_relu", "no_w", "sigmoid")
# (router margin, selection margin) pairs the records are read at
RULES = ((0.0, 0.0), (0.005, 0.0), (0.01, 0.0), (0.02, 0.0), (0.03, 0.0),
         (0.05, 0.0), (0.1, 0.0), (0.2, 0.0))


def faulty_class():
    """``(a PagedKeyeModel whose hooks plant a fault, the faults'
    codes)``: ``p["fault"]``, an int32 scalar among every layer's
    leaves, switches one on inside the executables."""
    import jax
    import jax.numpy as jnp
    from zoo_tpu.ops.moe import moe_ffn_dropless
    from zoo_tpu.ops.pallas.dsa_decode import dsa_decode_reference
    from zoo_tpu.serving.llm import model_keye as mk
    from zoo_tpu.serving.llm.model import _weight_dot

    code = {name: i for i, name in enumerate(FAULTS)}

    class Faulty(mk.PagedKeyeModel):
        def _attend_decode(self, p, x, cache, layer, at):
            f = self._fault = p["fault"]
            a, new, n = super()._attend_decode(p, x, cache, layer, at)
            # every resident token, through the plain gather
            q, _, _ = self._proj(p, x, at)
            bs, tables = self.block_size, at["tables"]
            idx = jnp.arange(tables.shape[1] * bs)
            rows = jnp.take(tables, idx // bs, axis=1) * bs + idx % bs
            live = jnp.any(tables != 0, axis=-1)
            o = dsa_decode_reference(
                q, new["kv"], rows, jnp.where(live, at["pos"] + 1, 0),
                layer=layer)
            dense = _weight_dot(o.reshape(self.num_slots, -1), p["wo"])
            return jnp.where(f == code["dense"], dense, a), new, n

        def _attend_rows(self, p, x, cache, layer, at):
            self._fault = p["fault"]
            return super()._attend_rows(p, x, cache, layer, at)

        _attend_chunk = _attend_bucket = _attend_verify = _attend_rows

        def _select_mask(self, qi, w, ki, layer, table, pos):
            picked = super()._select_mask(qi, w, ki, layer, table, pos)
            return picked | (self._fault == code["dense"])

        def _scores(self, qi, w, keys):
            f = self._fault
            keys = keys[..., :qi.shape[-1]]
            s = jnp.einsum("nhd,ntd->nht" if keys.ndim == 3
                           else "nhd,td->nht", qi.astype(keys.dtype), keys,
                           preferred_element_type=jnp.float32)
            gate = jnp.where(f == code["no_relu"], s, jax.nn.relu(s))
            weight = jnp.where(f == code["no_w"], 1.0, w)
            return jnp.sum(gate * weight[:, :, None], axis=1)

        def _moe(self, p, h, at):
            sound, counts = super()._moe(p, h, at)
            c = self.cfg
            x = mk._rms_norm(h, p["mlp_norm"], c.rms_eps)
            y, _ = moe_ffn_dropless(
                dict(p, bias=jnp.zeros((c.n_experts,), jnp.float32)),
                x.reshape(-1, c.hidden), top_k=c.experts_per_tok,
                norm_topk=c.norm_topk_prob, routing="sigmoid")
            return jnp.where(p["fault"] == code["sigmoid"],
                             h + y.reshape(h.shape), sound), counts

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


def widest(gaps, keep) -> float:
    kept = gaps[keep]
    return float(kept.max()) if kept.size else 0.0


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
    import jax.numpy as jnp
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
    from zoo_tpu.serving.llm import model_keye
    # the adapter builds whatever the module names at the call
    model_keye.PagedKeyeModel, code = faulty_class()
    rule = cfg.get("compare", {})
    rule = (float(rule.get("router_margin_min", 0.0)),
            float(rule.get("select_margin_min", 0.0)))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.devnull if args.rehearse_cpu else os.path.join(
        ROOT, "chiprun_out", "keye_faults.jsonl"), "a")

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
                tokens, rows, chosen, n = ref_mod._teacher_forced(
                    prompt, served[fault], context)
                ref, sel, rte = ref_mod.logits_at(params, cfg, tokens, rows)
                gaps = np.asarray(ref_mod.gap_below_best(
                    ref, jnp.asarray(chosen)))[:n]
                sel, rte = np.asarray(sel)[:n], np.asarray(rte)[:n]

                def keep(r):
                    return (rte >= r[0]) & (sel >= r[1])

                order = np.argsort(gaps)[::-1][:12]
                rec = {"seed": seed, "fault": fault,
                       "device": jax.devices()[0].device_kind,
                       "positions": int(n),
                       "served_logit_gap_max": widest(gaps, keep(rule)),
                       "rule": list(rule),
                       "kept": float(np.mean(keep(rule))),
                       "by_rule": {f"{a}/{b}": [widest(gaps, keep((a, b))),
                                                float(np.mean(keep((a, b))))]
                                   for a, b in RULES},
                       "widest_gaps": [[float(gaps[i]), float(rte[i]),
                                        float(sel[i])] for i in order],
                       "tokens_off_best": float(np.mean(gaps > 0)),
                       "same_as_sound": float(np.mean(
                           np.asarray(served[fault])
                           == np.asarray(served[faults[0]]))),
                       "limit": cell.limits.get(
                           "served_logit_gap_max", {}).get("limit")}
                if args.control and fault == "none":
                    low, _, _ = ref_mod.logits_at(params, cfg, tokens, rows,
                                                  lower=True)
                    low_gaps = np.asarray(ref_mod.gap_below_best(
                        ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[:n]
                    rec["control_logit_gap_max"] = widest(low_gaps,
                                                          keep(rule))
                    rec["control_by_rule"] = {
                        f"{a}/{b}": widest(low_gaps, keep((a, b)))
                        for a, b in RULES}
                rec["reference_s"] = time.perf_counter() - t
                print(json.dumps(rec), flush=True)
                out.write(json.dumps(rec) + "\n")
                out.flush()
        finally:
            ref_mod.free(params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
