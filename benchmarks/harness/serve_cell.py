"""Driving a served decoder: ``HAServingClient.generate`` over TCP to an
in-process ``ServingServer`` over ``LLMEngine`` over the model that the
configuration's adapter builds (the entry ``chip_smoke.py``'s serve leg
shows).

What is particular to an architecture is the adapter's
(``adapters/<name>.py``, named in the configuration's file): the tree of
weights the program takes, the model object, and how to free it. Here
is what every served decoder shares: the engine, server and client, the
spans round the engine's calls into the model, the gauges, warm-up and
the windows. The benchmark makes the weights from the seed and keeps no
copy while the window runs.
"""

from __future__ import annotations

import gc
import json
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import manifest, traffic as tg
from .readers import percentile
from .spans import GaugeSampler, GcWatch, Recorder

GAUGES = ("zoo_llm_slot_occupancy", "zoo_llm_kv_blocks_used",
          "zoo_llm_waiting_streams")


class ServedDecoder:
    """The system under test and the spans the benchmark puts round it."""

    def __init__(self, cfg: dict, seed: int, rec: Recorder, ref_mod):
        from zoo_tpu.serving.ha_client import HAServingClient
        from zoo_tpu.serving.llm.engine import LLMEngine
        from zoo_tpu.serving.server import ServingServer

        self.cfg, self.rec = cfg, rec
        self.adapter = manifest.adapter_of(cfg)
        eng = cfg["engine"]
        with rec.span("setup.weights"):
            weights = self.adapter.weights(seed, cfg, ref_mod)
        with rec.span("setup.engine"):
            self.model = self.adapter.model(cfg, weights)
            del weights
            self.engine = LLMEngine(self.model, mode="continuous",
                                    overlap=eng["overlap"],
                                    prefix_cache=eng["prefix_cache"])
            self._instrument()
            self.engine.start()
            self.server = ServingServer(None, host="127.0.0.1", port=0,
                                        llm_engine=self.engine).start()
            self.client = HAServingClient(
                [(self.server.host, self.server.port)], hedge=False,
                deadline_ms=600_000)
        self._handles: Dict[str, object] = {}
        self._lock = threading.Lock()
        self.sampler = GaugeSampler(rec, self._gauges).start()
        self.gc_watch = GcWatch(rec).start()

    # -- spans round the program's layers ----------------------------------
    def _instrument(self):
        rec, eng, model = self.rec, self.engine, self.model
        pending: Dict[int, float] = {}

        def after_dispatch(args, kwargs, out, t0, t1):
            tables, positions = np.asarray(args[3]), np.asarray(args[4])
            live = tables.any(axis=1)
            rec.add_count("decode_ticks", 1, t0)
            rec.add_count("decode_tokens", int(live.sum()), t0)
            rec.add_count("attended_positions",
                          int((positions[live] + 1).sum()), t0)
            pending[id(out)] = t0

        def after_read(args, kwargs, out, t0, t1):
            t_d = pending.pop(id(args[0]), None)
            if t_d is not None:
                rec.add_span("model.decode_tick", t_d, t1 - t_d)

        def after_prefill(args, kwargs, out, t0, t1):
            rec.add_count("prefill_ticks", 1, t0)
            rec.add_count("prefill_tokens", int(len(args[0])), t0)

        def after_submit(args, kwargs, out, t0, t1):
            tid = kwargs.get("trace_id")
            if tid is not None:
                with self._lock:
                    self._handles[tid] = (out, t0)

        rec.wrap(model, "decode_step", "model.decode_dispatch",
                 after_dispatch)
        rec.wrap(model, "read_tokens", "model.read_tokens", after_read)
        rec.wrap(model, "prefill_chunk", "model.prefill_chunk",
                 after_prefill)
        rec.wrap(eng, "_admit", "engine.admit")
        rec.wrap(eng, "_build_tick", "engine.build_tick")
        rec.wrap(eng, "_prefill_tick", "engine.prefill_tick")
        rec.wrap(eng, "submit", "engine.submit", after_submit)

    def _gauges(self) -> Dict[str, float]:
        from zoo_tpu.obs.metrics import get_registry
        out: Dict[str, float] = {}
        for g in get_registry().snapshot()["gauges"]:
            if g["name"] in GAUGES:
                out[g["name"]] = out.get(g["name"], 0.0) + g["value"]
        return out

    # -- the client's side -------------------------------------------------
    def send(self, req: tg.Request):
        tid = f"bench-{req.client}-{req.index}"
        req.trace_id = tid
        return self.client.generate(req.prompt, req.max_new, trace_id=tid)

    def engine_ttft(self, req: tg.Request) -> Optional[float]:
        """Seconds from the engine's ``submit`` to its first token for
        this request, by the engine's own handle."""
        with self._lock:
            got = self._handles.get(getattr(req, "trace_id", None))
        if got is None:
            return None
        handle, _ = got
        return handle.ttft()

    def counters(self) -> Dict[str, float]:
        from zoo_tpu.obs.metrics import get_registry
        out: Dict[str, float] = {}
        for c in get_registry().snapshot()["counters"]:
            out[c["name"]] = out.get(c["name"], 0.0) + c["value"]
        return out

    def compiles(self) -> int:
        counts = self.engine.stats().get("compiles", {})
        if min(counts.values(), default=0) < 0:
            raise RuntimeError(f"jit's cache size is unreadable: {counts}")
        return int(sum(counts.values()))

    def tally(self) -> tuple:
        """(programs compiled so far, the program's counters)."""
        return self.compiles(), self.counters()

    def warm(self, vocab: int, prompt_tokens: int, new_tokens: int):
        """One request through every executable the window will use."""
        rng = np.random.default_rng(0)
        toks = list(self.client.generate(
            tg.token_ids(rng, prompt_tokens, vocab), new_tokens))
        if len(toks) != new_tokens:
            raise RuntimeError(f"warm-up request delivered {len(toks)} of "
                               f"{new_tokens} tokens")

    def settle(self):
        """Before the window opens: collect what set-up left and put
        every object that is alive now beyond the collector's reach, so
        that a full pass inside the window (it holds every thread for as
        long as it walks the heap) looks only at what the window made."""
        gc.collect()
        gc.freeze()

    def close(self):
        """Stop the threads and free the device: the reference runs
        next and needs the room."""
        gc.unfreeze()
        self.sampler.stop()
        self.gc_watch.stop()
        self.client.close()
        self.server.stop()
        self.engine.stop()
        self.rec.unwrap_all()
        self.adapter.free(self.model)
        with self._lock:
            self._handles.clear()
        gc.collect()


# ------------------------------------------------------------------ windows

def _grown(before: tuple, after: tuple, cell) -> dict:
    """What two tallies say of the window between them."""
    (compiles0, counters0), (compiles1, counters1) = before, after
    return {"facts": {"compiles_in_window": compiles1 - compiles0,
                      "num_blocks": cell.config["engine"]["num_blocks"]},
            "counters": {k: v - counters0.get(k, 0.0)
                         for k, v in counters1.items()}}


def run_closed(sys_: ServedDecoder, cell, seed: int, seconds: float,
               hook, t_start: float) -> dict:
    """Start the clients one after another, wait until every one holds
    a request that has produced a token, then measure for ``seconds``. ``hook(t0, t1)`` may trace a part of the
    window; it is called with the window's ends before it runs."""
    tr, vocab = cell.traffic, cell.config["vocab_size"]
    loop = tg.ClosedLoop(tr, seed, vocab, sys_.send)
    sys_.warm(vocab, tg.closed_lengths(tr)[0], 4)
    sys_.settle()
    loop.start()
    while not loop.all_started():
        time.sleep(0.02)
    before = sys_.tally()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    hook(t0, t1)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    after = sys_.tally()
    loop.close()
    reqs = loop.snapshot()
    tokens = sum(1 for r in reqs for t in r.token_times if t0 <= t < t1)
    done = [r for r in reqs if r.finished
            and t0 <= r.token_times[-1] < t1]
    failed = [r for r in reqs if r.error is not None]
    return {
        "t0": t0, "t1": t1, "setup_s": t0 - t_start,
        "requests": reqs, "finished": done,
        "attempted": len(reqs),
        "failed": len(failed),
        "errors": [r.error for r in failed][:3],
        "end_to_end": {"decode_tokens_per_s": tokens / seconds},
        **_grown(before, after, cell),
    }


def run_open(sys_: ServedDecoder, cell, seed: int, seconds: float,
             hook, t_start: float) -> dict:
    tr, vocab = cell.traffic, cell.config["vocab_size"]
    schedule = tg.open_schedule(tr, seed, seconds, vocab)
    sys_.warm(vocab, int(tr["prompt_tokens"]["max"]), 4)
    for _ in range(int(tr.get("warm_requests", 0))):
        sys_.warm(vocab, int(tr["prompt_tokens"]["median"]), 4)
    loop = tg.OpenLoop(schedule, sys_.send)
    sys_.settle()
    before = sys_.tally()
    loop.start()
    t0 = loop.t_start
    t1 = t0 + seconds
    hook(t0, t1)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    after = sys_.tally()
    loop.close(float(tr["drain_seconds"]))
    failed = [r for r in schedule if not r.finished]
    ttft = [(r.token_times[0] - (t0 + r.due)) * 1e3
            for r in schedule if r.token_times]
    # a request that failed or never answered missed every limit: it
    # stands in the tail as the longest wait there could have been
    ttft += [(float(tr["drain_seconds"]) + seconds) * 1e3] * sum(
        1 for r in schedule if not r.token_times)
    gaps = [(b - a) * 1e3 for r in schedule
            for a, b in zip(r.token_times, r.token_times[1:])]
    late = [(r.sent - (t0 + r.due)) * 1e3 for r in schedule
            if r.sent is not None]
    wire = []
    for r in schedule:
        e = sys_.engine_ttft(r)
        if e is not None and r.token_times and r.sent is not None:
            wire.append((r.token_times[0] - r.sent - e) * 1e3)
    print("late: " + json.dumps({
        "sent": len(late), "p50_ms": percentile(late, 50),
        "p99_ms": percentile(late, 99), "max_ms": max(late, default=None)}),
        flush=True)
    for v in late:
        sys_.rec.add_span("loadgen.late", t0, v / 1e3)
    for v in wire:
        sys_.rec.add_span("wire.ttft_overhead", t0, max(v, 0.0) / 1e3)
    for v in ttft:
        sys_.rec.add_span("client.ttft", t0, v / 1e3)
    return {
        "t0": t0, "t1": t1, "setup_s": t0 - t_start,
        "requests": schedule,
        "finished": [r for r in schedule if r.finished],
        "attempted": len(schedule), "failed": len(failed),
        "errors": [r.error for r in failed if r.error][:3],
        "end_to_end": {"ttft_p95_ms": percentile(ttft, 95),
                       "itl_p99_ms": percentile(gaps, 99)},
        **_grown(before, after, cell),
    }


LOOPS = {"closed": run_closed, "open": run_open}


def census(rec: Recorder, t0: float, t1: float, finished: int) -> dict:
    return {"decode_ticks": rec.n_in("decode_ticks", t0, t1),
            "prefill_ticks": rec.n_in("prefill_ticks", t0, t1),
            "decode_tokens": int(rec.count_in("decode_tokens", t0, t1)),
            "attended_positions": int(rec.count_in("attended_positions",
                                                   t0, t1)),
            "requests_completed": finished}


# ---------------------------------------------------------------- `correct`

def pick_checked(finished: List[tg.Request], seed: int, k: int
                 ) -> List[tg.Request]:
    """A sample of the finished requests drawn from the seed, with the
    longest in it."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (r.client, r.index))
    longest = max(order, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng([seed, 7])
    take = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in take]


def compare(cell, seed: int, checked: List[tg.Request], ref_mod,
            control: bool) -> dict:
    """Run the reference once over each checked request's prompt with
    its served tokens and take the widest gap by which a served token's
    logit lies below the reference's best. With ``control`` also the
    same for the token that the lower precision puts first."""
    cfg = cell.config
    # one padded length for every request, the longest context a slot
    # can hold: the reference then compiles once for a configuration
    context = cfg["engine"]["max_blocks_per_seq"] * cfg["engine"]["block_size"]
    params = ref_mod.make_params(seed, cfg)
    worst, worst_low, n = 0.0, 0.0, 0
    try:
        for r in checked:
            gaps, low = ref_mod.served_gaps(params, cfg, r.prompt,
                                            r.tokens, pad_to=context,
                                            lower_too=control)
            worst = max(worst, float(gaps.max()))
            n += len(gaps)
            if low is not None:
                worst_low = max(worst_low, float(low.max()))
    finally:
        ref_mod.free(params)
    out = {"served_logit_gap_max": worst, "served_tokens_checked": n}
    if control:
        out["control_logit_gap_max"] = worst_low
    return out
