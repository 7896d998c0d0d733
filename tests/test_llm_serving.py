"""LLM serving engine (docs/llm_serving.md): the paged KV block
allocator, the iteration-level (continuous) scheduler, the
prefill/decode split's correctness against the full-context Llama
reference, and the streaming generate op over the real TCP door with
HA failover-resume.

The allocator and scheduler tests run against pure-python fakes (no
jax), so most of this file is tier-1 cheap; the paged-model and wire
tests share ONE tiny compiled model via a module fixture. The 2-replica
SIGKILL smoke (scripts/check_llm_serving.py) runs as a subprocess under
the ``chaos`` marker like its serving-HA sibling.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from zoo_tpu.serving.llm.engine import AdmissionError, LLMEngine
from zoo_tpu.serving.llm.kv_cache import BlockAllocator
from zoo_tpu.serving.llm.spec import parse_llm_spec
from zoo_tpu.util.resilience import Deadline

from llm_tick import HostStepped, tick


# ------------------------------------------------------- block allocator

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.free_blocks == 7  # block 0 is the reserved trash block
    got = a.allocate("s1", 3)
    assert len(got) == 3 and 0 not in got
    assert a.used_blocks == 3 and a.free_blocks == 4
    assert a.blocks_of("s1") == got
    assert a.free("s1") == 3
    assert a.used_blocks == 0 and a.free_blocks == 7
    # LIFO: the just-freed blocks come back first (warm reuse), in the
    # same order the sequence held them
    again = a.allocate("s2", 3)
    assert again == got


def test_allocator_never_hands_out_block_zero():
    a = BlockAllocator(num_blocks=6, block_size=2)
    got = a.allocate("s", 5)
    assert sorted(got) == [1, 2, 3, 4, 5]
    assert a.allocate("s2", 1) is None   # block 0 is never handed out


def test_allocator_all_or_nothing():
    a = BlockAllocator(num_blocks=5, block_size=4)  # 4 usable
    assert a.allocate("s1", 3) is not None
    # asking for more than the free list holds changes NOTHING
    assert a.allocate("s2", 2) is None
    assert a.used_blocks == 3 and a.free_blocks == 1
    assert a.blocks_of("s2") == []


def test_allocator_block_table_growth():
    a = BlockAllocator(num_blocks=10, block_size=2)
    first = a.allocate("s", a.blocks_for_tokens(3))   # 3 tokens -> 2
    assert len(first) == 2
    # crossing each block boundary appends to the SAME table, order
    # preserved (the block table is positional: row i covers tokens
    # [i*bs, (i+1)*bs) )
    for _ in range(3):
        assert a.allocate("s", 1) is not None
    table = a.blocks_of("s")
    assert len(table) == 5 and table[:2] == first


def test_allocator_admission_refusal_when_empty():
    a = BlockAllocator(num_blocks=4, block_size=4)  # 3 usable
    assert a.can_admit(prompt_len=7)   # 2 blocks for 7+1 tokens
    assert a.allocate("hog", 3) is not None
    assert not a.can_admit(prompt_len=1)
    assert a.allocate("late", 1) is None
    a.free("hog")
    assert a.can_admit(prompt_len=7)


def test_allocator_free_is_idempotent():
    a = BlockAllocator(num_blocks=6, block_size=2)
    a.allocate("s", 2)
    assert a.free("s") == 2
    assert a.free("s") == 0          # abort paths may race: no double free
    assert a.free("never-seen") == 0
    assert a.free_blocks == 5


def test_allocator_blocks_for_tokens_math():
    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.blocks_for_tokens(1) == 1
    assert a.blocks_for_tokens(8) == 1
    assert a.blocks_for_tokens(9) == 2
    assert a.blocks_for_tokens(0) == 1  # a sequence always owns a block


def test_allocator_publishes_gauges():
    from zoo_tpu.obs.metrics import gauge
    used = gauge("zoo_llm_kv_blocks_used")
    free = gauge("zoo_llm_kv_blocks_free")
    a = BlockAllocator(num_blocks=9, block_size=4)
    assert free.value == 8.0 and used.value == 0.0
    a.allocate("s", 5)
    assert used.value == 5.0 and free.value == 3.0
    a.free("s")
    assert used.value == 0.0 and free.value == 8.0


# ------------------------------------------------ scheduler (fake model)

class _FakeModel(HostStepped):
    """Deterministic 'llm' with the PagedLlamaModel surface but no jax.

    Greedy lanes: next token = ``(2*tok + pos) % 97``, a pure function
    of (last token, position). Sampled lanes (temperature > 0): next
    token = ``(31*seed + 7*pos + 3*tok) % 97`` — a pure function of the
    SLOT'S OWN (seed, position, last token) and nothing else. Both make
    preemption's re-prefill-from-prompt+generated provably seamless and
    per-slot isolation provable, exactly the properties the real model
    gets from greedy decode / ``fold_in(seed, token_index)`` sampling."""

    def __init__(self, num_slots=2, block_size=4, num_blocks=8,
                 max_blocks_per_seq=4, max_prompt_len=12,
                 decode_delay=0.0, eos_id=None, prefill_chunk=0):
        self.num_slots = num_slots
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_context = block_size * max_blocks_per_seq
        self.max_prompt_len = max_prompt_len
        self.prefill_chunk_size = prefill_chunk
        self.decode_delay = decode_delay
        self.eos_id = eos_id
        self.prefills = []   # tokens fed per prefill/chunk call
        self.chunks = []     # (start, take) per chunk call

    @staticmethod
    def _next(tok, pos, temp=0.0, seed=0):
        if temp > 0:
            return (31 * int(seed) + 7 * int(pos) + 3 * int(tok)) % 97
        return (2 * int(tok) + int(pos)) % 97

    def prefill(self, prompt, block_table_row, sampling=None):
        self.prefills.append(len(prompt))
        t, _, _, s = sampling or (0.0, 0, 1.0, 0)
        return self._next(prompt[-1], len(prompt), t, s)

    def prefill_chunk(self, chunk, start, total_len, block_table_row,
                      sampling=None):
        self.chunks.append((int(start), len(chunk)))
        self.prefills.append(len(chunk))
        t, _, _, s = sampling or (0.0, 0, 1.0, 0)
        # only meaningful on the final chunk (contains the last token)
        return self._next(chunk[-1], total_len, t, s)

    def decode(self, tokens, block_tables, positions, sampling=None):
        if self.decode_delay:
            time.sleep(self.decode_delay)
        if sampling is None:
            temps = seeds = [0] * len(tokens)
        else:
            temps, _, _, seeds = sampling
        # ``positions[i]`` is the cache index the incoming token is
        # WRITTEN at, so the sequence is ``position + 1`` tokens long
        # once it lands — the same length prefill sees for the same
        # sequence, which is what makes preemption's re-prefill seamless
        return np.array([self._next(t, p + 1, tt, s)
                         for t, p, tt, s in zip(tokens, positions,
                                                temps, seeds)], np.int32)


def _reference(prompt, n, temp=0.0, seed=0):
    """What any correct schedule must emit for ``prompt``."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        out.append(_FakeModel._next(seq[-1], len(seq), temp, seed))
        seq.append(out[-1])
    return out


def _drain(handles, budget=20.0):
    deadline = time.monotonic() + budget
    while not all(h.done for h in handles):
        if time.monotonic() > deadline:
            raise AssertionError(
                f"streams stuck: {[h.outcome for h in handles]}")
        time.sleep(0.005)


def test_engine_collects_young_garbage_when_it_drains(monkeypatch):
    """The moment the last stream of a burst leaves (nothing active,
    nothing waiting) the engine runs a young-generation collection —
    never while a stream is live — so the collector's next pass does
    not land in whoever allocates next."""
    from zoo_tpu.serving.llm import engine as E
    calls = []

    eng = LLMEngine(_FakeModel(num_slots=2, num_blocks=32,
                               max_blocks_per_seq=8)).start()

    def collect(gen):
        calls.append((gen, sum(1 for s in eng._slots if s.handle),
                      len(eng._wait)))
        return 0

    monkeypatch.setattr(E.gc, "collect", collect)
    try:
        for burst in range(2):
            seen = len(calls)
            hs = [eng.submit(p, 4) for p in ([3, 5], [7], [1, 2, 3])]
            _drain(hs)
            deadline = time.monotonic() + 5.0
            while len(calls) == seen and time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(calls) > seen, "no collection after the burst"
    finally:
        eng.stop()
    # generation 0, and only ever with the engine empty
    assert set(calls) == {(0, 0, 0)}, calls


def test_engine_continuous_more_streams_than_slots():
    eng = LLMEngine(_FakeModel(num_slots=2, num_blocks=32,
                               max_blocks_per_seq=8)).start()
    try:
        prompts = [[3, 5], [7], [1, 2, 3], [9, 9], [4], [8, 1]]
        hs = [eng.submit(p, 5) for p in prompts]
        _drain(hs)
        for p, h in zip(prompts, hs):
            assert h.outcome == "ok"
            assert h.tokens == _reference(p, 5)
        assert eng.allocator.used_blocks == 0
        assert eng.allocator.live_sequences() == 0
    finally:
        eng.stop()


def test_engine_continuous_admits_into_freed_slots_midflight():
    """The Orca property itself: with 1 slot and bimodal lengths, a
    short stream admitted behind a long one starts as soon as ANY slot
    frees — i.e. the long stream is still running when the short one
    finishes (request-level batching would serialize whole waves)."""
    eng = LLMEngine(_FakeModel(num_slots=2, num_blocks=64,
                               max_blocks_per_seq=8,
                               decode_delay=0.002)).start()
    try:
        long_h = eng.submit([1], 25)
        short = [eng.submit([2 + i], 2) for i in range(3)]
        _drain(short)
        assert not long_h.done, \
            "short streams should finish while the long one decodes"
        _drain([long_h])
        assert long_h.tokens == _reference([1], 25)
    finally:
        eng.stop()


def test_engine_deadline_dead_in_queue():
    eng = LLMEngine(_FakeModel()).start()
    try:
        h = eng.submit([1, 2], 4, deadline=Deadline.from_ms(0.0))
        _drain([h])
        assert h.outcome == "expired" and h.tokens == []
        assert eng.allocator.used_blocks == 0
    finally:
        eng.stop()


def test_engine_deadline_expires_midstream_and_frees_blocks():
    eng = LLMEngine(_FakeModel(num_blocks=32, max_blocks_per_seq=8,
                               decode_delay=0.01)).start()
    try:
        h = eng.submit([5], 10_000, deadline=Deadline.from_ms(120.0))
        _drain([h], budget=10.0)
        assert h.outcome == "expired"
        assert 0 < len(h.tokens) < 10_000
        assert h.tokens == _reference([5], len(h.tokens))
        deadline = time.monotonic() + 5
        while eng.allocator.used_blocks and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.allocator.used_blocks == 0, "expiry leaked KV blocks"
    finally:
        eng.stop()


def test_engine_cancel_frees_blocks():
    eng = LLMEngine(_FakeModel(num_blocks=32, max_blocks_per_seq=8,
                               decode_delay=0.01)).start()
    try:
        h = eng.submit([5, 6], 10_000)
        while not h.tokens:
            time.sleep(0.005)
        assert eng.cancel(h.id)
        _drain([h])
        assert h.outcome == "cancelled"
        deadline = time.monotonic() + 5
        while eng.allocator.used_blocks and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.allocator.used_blocks == 0, "abort leaked KV blocks"
        assert not eng.cancel(h.id)   # already finished: no-op
    finally:
        eng.stop()


def test_engine_admission_sheds_when_waiting_queue_full():
    eng = LLMEngine(_FakeModel(num_slots=1, decode_delay=0.01),
                    max_waiting=2).start()
    try:
        running = eng.submit([1], 1000)
        while not running.tokens:
            time.sleep(0.005)
        eng.submit([2], 4)
        eng.submit([3], 4)
        with pytest.raises(AdmissionError) as ei:
            eng.submit([4], 4)
        assert ei.value.retry_after_ms > 0
    finally:
        eng.stop()


def test_engine_duplicate_rid_joins_stream():
    eng = LLMEngine(_FakeModel()).start()
    try:
        h1 = eng.submit([3, 4], 4, rid="r-1")
        h2 = eng.submit([9, 9, 9], 999, rid="r-1")  # args ignored: join
        assert h2 is h1
        _drain([h1])
        assert h1.tokens == _reference([3, 4], 4)
    finally:
        eng.stop()


def test_engine_prompt_too_long_and_empty_rejected():
    eng = LLMEngine(_FakeModel(max_prompt_len=8))
    with pytest.raises(ValueError):
        eng.submit(list(range(9)), 4)
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit([1], 0)
    eng.stop()


def test_engine_preempts_youngest_and_resumes_exactly():
    """KV pressure: two long streams on a pool that cannot hold both to
    completion. The youngest-admitted one is evicted (blocks freed,
    re-queued) and later RE-PREFILLED from prompt+generated; because
    decode is deterministic its final token stream is byte-identical to
    an uncontended run."""
    # 6 usable blocks, bs=2: each stream needs 1 block per 2 tokens;
    # two 12-token streams want 2x6 > 6 -> somebody must be preempted.
    # White-box manual ticks (engine not started): both streams are
    # admitted in the SAME tick, so concurrent growth — and therefore
    # the preemption — is deterministic, not a thread-timing accident.
    model = _FakeModel(num_slots=2, block_size=2, num_blocks=7,
                       max_blocks_per_seq=6, max_prompt_len=8)
    eng = LLMEngine(model)
    from zoo_tpu.obs.metrics import counter
    preempts0 = counter("zoo_llm_preempt_total").value
    a = eng.submit([1, 2], 9)
    b = eng.submit([3, 4], 9)
    for _ in range(60):
        tick(eng)
        if a.done and b.done:
            break
    assert a.outcome == "ok" and b.outcome == "ok"
    assert a.tokens == _reference([1, 2], 9)
    assert b.tokens == _reference([3, 4], 9)
    assert counter("zoo_llm_preempt_total").value > preempts0
    # the victim was re-prefilled with its context so far
    assert max(model.prefills) > 4
    assert eng.allocator.used_blocks == 0
    eng.stop()


def test_engine_rejects_prompt_larger_than_whole_pool():
    """A prompt whose blocks can NEVER be satisfied (bigger than the
    entire pool) must be rejected at submit — not parked at the head of
    the waiting queue forever, wedging everything behind it."""
    model = _FakeModel(num_slots=1, block_size=2, num_blocks=4,
                       max_blocks_per_seq=16, max_prompt_len=64)
    eng = LLMEngine(model).start()
    try:
        with pytest.raises(ValueError, match="whole pool"):
            eng.submit(list(range(20)), 4)   # 11 blocks > 3 usable
        # feasible traffic still flows
        h = eng.submit([1, 2], 2)
        _drain([h])
        assert h.outcome == "ok"
    finally:
        eng.stop()


def test_engine_sole_stream_out_of_pool_errors():
    """A stream that cannot grow and has no preemption victim must end
    loudly (error outcome), not wedge the scheduler."""
    model = _FakeModel(num_slots=1, block_size=2, num_blocks=3,
                       max_blocks_per_seq=16, max_prompt_len=3)
    eng = LLMEngine(model).start()
    try:
        h = eng.submit([1], 50)   # needs 25 blocks, pool holds 2
        _drain([h])
        assert h.outcome == "error"
        assert "kv cache exhausted" in h.error
        assert eng.allocator.used_blocks == 0
    finally:
        eng.stop()


def test_engine_context_ceiling_truncates_ok():
    model = _FakeModel(num_slots=1, block_size=2, num_blocks=32,
                       max_blocks_per_seq=3, max_prompt_len=4)
    eng = LLMEngine(model).start()
    try:
        h = eng.submit([1, 2], 50)   # table caps context at 6 tokens
        _drain([h])
        assert h.outcome == "ok" and h.truncated
        assert len(h.tokens) < 50
        assert h.tokens == _reference([1, 2], len(h.tokens))
    finally:
        eng.stop()


def test_engine_eos_stops_stream():
    ref = _reference([6], 10)
    eos = ref[3]
    eng = LLMEngine(_FakeModel(eos_id=eos)).start()
    try:
        h = eng.submit([6], 10)
        _drain([h])
        assert h.outcome == "ok"
        assert h.tokens == ref[:4]   # eos token is emitted, then stop
    finally:
        eng.stop()


def test_engine_stop_frees_everything():
    eng = LLMEngine(_FakeModel(num_blocks=32, max_blocks_per_seq=8,
                               decode_delay=0.01)).start()
    h = eng.submit([1], 10_000)
    while not h.tokens:
        time.sleep(0.005)
    eng.stop()
    assert h.outcome == "cancelled"
    assert eng.allocator.used_blocks == 0


# --------------------------------------------- overlapped tick pipeline

def test_running_engine_matches_hand_stepped_engine():
    """The double-buffered pipeline is a pure latency optimization: for
    every stream the running engine (two threads, two ticks in flight)
    emits exactly the tokens an engine stepped by hand emits (one pass,
    then its landing, nothing else in flight), and both emit what any
    correct schedule must."""
    prompts = [[3, 5], [7], [1, 2, 3], [9, 9], [4], [8, 1]]

    def engine():
        return LLMEngine(_FakeModel(num_slots=2, num_blocks=32,
                                    max_blocks_per_seq=8))

    eng = engine()
    stepped = [eng.submit(p, 5) for p in prompts]
    for _ in range(200):
        tick(eng)
        if all(h.done for h in stepped):
            break
    assert all(h.outcome == "ok" for h in stepped)
    assert eng.allocator.used_blocks == 0
    eng.stop()

    eng = engine().start()
    try:
        running = [eng.submit(p, 5) for p in prompts]
        _drain(running)
        assert eng.allocator.used_blocks == 0
    finally:
        eng.stop()
    assert [h.tokens for h in running] == [h.tokens for h in stepped]
    for p, h in zip(prompts, running):
        assert h.tokens == _reference(p, 5)


@pytest.mark.parametrize("kwargs", [{"mode": "oneshot"},
                                    {"overlap": False}],
                         ids=["mode-oneshot", "overlap-false"])
def test_engine_refuses_the_schedulers_that_went(kwargs):
    """The constructor still takes ``mode`` and ``overlap`` (the
    benchmark's harness passes them) and accepts only the one value
    each that names the scheduler there is."""
    with pytest.raises(ValueError, match="one"):
        LLMEngine(_FakeModel(), **kwargs)
    eng = LLMEngine(_FakeModel(), mode="continuous", overlap=None)
    assert "mode" not in eng.stats() and "overlap" not in eng.stats()
    LLMEngine(_FakeModel(), overlap=True)


def test_engine_refuses_a_model_without_the_dispatch_surface():
    """``decode_step`` / ``read_tokens`` are what the engine requires
    of a model: one with ``decode`` alone is refused by name, not
    handed another scheduler."""

    class _DecodeOnly:
        num_slots, block_size, num_blocks = 2, 4, 8
        max_blocks_per_seq, max_prompt_len = 4, 12

        def prefill(self, prompt, row, sampling=None):
            return 0

        def decode(self, tokens, tables, positions, sampling=None):
            return np.zeros((len(tokens),), np.int32)

    with pytest.raises(TypeError, match="decode_step and read_tokens"):
        LLMEngine(_DecodeOnly())


@pytest.mark.parametrize("prefix", ["llama:", "glm_moe_lite:"])
def test_spec_string_with_overlap_is_an_unknown_key(prefix):
    with pytest.raises(ValueError, match=r"unknown llm spec keys "
                                         r"\['overlap'\]"):
        parse_llm_spec(prefix + "tiny:slots=2,overlap=0")
    parse_llm_spec(prefix + "tiny:slots=2")


def test_overlap_eos_discards_speculative_tokens():
    """Under overlap the engine keeps dispatching while a tick is in
    flight; when eos lands, the speculatively decoded extra tokens must
    be discarded — the stream ends exactly at eos like the sync loop."""
    ref = _reference([6], 10)
    eos = ref[3]
    eng = LLMEngine(_FakeModel(eos_id=eos, num_blocks=32,
                               max_blocks_per_seq=8,
                               decode_delay=0.002)).start()
    try:
        h = eng.submit([6], 50)
        _drain([h])
        assert h.outcome == "ok"
        assert h.tokens == ref[:4]   # eos emitted, then stop — no spill
        assert eng.allocator.used_blocks == 0
    finally:
        eng.stop()


def test_overlap_publishes_tick_metrics():
    from zoo_tpu.obs.metrics import gauge, histogram
    hist = histogram("zoo_llm_tick_seconds", labels=("phase",))
    before = {ph: hist.labels(phase=ph).snapshot_value()["count"]
              for ph in ("schedule", "decode", "readback")}
    eng = LLMEngine(_FakeModel(num_blocks=32, max_blocks_per_seq=8,
                               decode_delay=0.001)).start()
    try:
        _drain([eng.submit([2, 3], 8)])
    finally:
        eng.stop()
    for ph in ("schedule", "decode", "readback"):
        after = hist.labels(phase=ph).snapshot_value()["count"]
        assert after > before[ph], f"no {ph} tick samples recorded"
    ratio = gauge("zoo_llm_tick_overlap_ratio").value
    assert 0.0 <= ratio <= 1.0


def test_overlap_readback_failure_fails_streams_loudly():
    """A failed readback (device error mid-stream) must END the
    affected streams with an error outcome — not leave a silent
    one-token hole and a wedged slot — and the engine must keep
    serving fresh streams afterwards (chain re-seeded)."""

    class _FlakyModel(_FakeModel):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.fail_at = 3
            self.reads = 0

        def read_tokens(self, batch):
            self.reads += 1
            if self.reads == self.fail_at:
                raise RuntimeError("injected readback failure")
            return super().read_tokens(batch)

    model = _FlakyModel(num_slots=2, num_blocks=32, max_blocks_per_seq=8)
    eng = LLMEngine(model).start()
    try:
        h = eng.submit([4], 30)
        _drain([h])
        assert h.outcome == "error"
        assert "tokens lost" in h.error
        # no silent hole: everything delivered is the exact prefix
        assert h.tokens == _reference([4], len(h.tokens))
        deadline = time.monotonic() + 5
        while eng.allocator.used_blocks and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.allocator.used_blocks == 0, "failure leaked blocks"
        # the engine survives and fresh streams decode correctly
        h2 = eng.submit([9], 5)
        _drain([h2])
        assert h2.outcome == "ok" and h2.tokens == _reference([9], 5)
    finally:
        eng.stop()


def test_prefill_failure_fails_stream_not_scheduler():
    """A prefill exception ends THAT stream with an error (blocks
    freed) — it must not kill the scheduler thread and wedge the
    queue behind it."""

    class _BadPrefill(_FakeModel):
        def prefill(self, prompt, row, sampling=None):
            if len(prompt) >= 5:
                raise RuntimeError("injected prefill failure")
            return super().prefill(prompt, row, sampling)

    eng = LLMEngine(_BadPrefill(num_slots=2, num_blocks=32,
                                max_blocks_per_seq=8)).start()
    try:
        bad = eng.submit([1, 2, 3, 4, 5], 4)
        good = eng.submit([7], 4)
        _drain([bad, good])
        assert bad.outcome == "error" and "prefill failed" in bad.error
        assert good.outcome == "ok" and good.tokens == _reference([7], 4)
        assert eng.allocator.used_blocks == 0, "failed prefill leaked"
    finally:
        eng.stop()


# ------------------------------------------------------------- sampling

def test_parse_sampling_defaults_env_and_errors(monkeypatch):
    from zoo_tpu.serving.llm.engine import parse_sampling, stream_seed
    assert parse_sampling(None, "r") == (0.0, 0, 1.0, stream_seed("r"))
    t, k, p, s = parse_sampling(
        dict(temperature=0.8, top_k=40, top_p=0.9, seed=7), "r")
    assert (t, k, p, s) == (0.8, 40, 0.9, 7)
    # env sets the deployment default; the request overrides it
    monkeypatch.setenv("ZOO_LLM_SAMPLING", "temperature=0.5,top_k=10")
    t, k, p, s = parse_sampling(None, "r")
    assert (t, k) == (0.5, 10) and s == stream_seed("r")
    t, k, _, _ = parse_sampling(dict(temperature=0.0), "r")
    assert (t, k) == (0.0, 10)
    monkeypatch.delenv("ZOO_LLM_SAMPLING")
    with pytest.raises(ValueError, match="unknown sampling"):
        parse_sampling(dict(temp=1.0), "r")
    with pytest.raises(ValueError, match="top_p"):
        parse_sampling(dict(top_p=0.0), "r")
    with pytest.raises(ValueError, match="temperature"):
        parse_sampling(dict(temperature=-1.0), "r")
    # the rid-derived seed is stable across processes/replicas
    assert stream_seed("some-rid") == stream_seed("some-rid")


def test_sampling_per_slot_isolation():
    """One stream's sampling params must never bleed into a neighbor
    slot: the same seeded stream decodes identically regardless of what
    its slot neighbors sample with."""
    ref = _reference([5], 6, temp=1.0, seed=42)
    for i, neighbor in enumerate((dict(temperature=5.0, seed=123),
                                  dict(temperature=0.0),
                                  dict(temperature=2.0, seed=9))):
        eng = LLMEngine(_FakeModel(num_slots=2, num_blocks=64,
                                   max_blocks_per_seq=8)).start()
        try:
            a = eng.submit([5], 6,
                           sampling=dict(temperature=1.0, seed=42))
            b = eng.submit([7], 6, sampling=neighbor)
            _drain([a, b])
        finally:
            eng.stop()
        assert a.tokens == ref, f"neighbor {i} bled into the stream"


def test_sampled_stream_survives_preemption_deterministically():
    """Seeded sampling across a mid-stream preemption: the PRNG draw is
    a pure function of (seed, token index), so the re-prefilled
    continuation is byte-identical to an uncontended run — same
    white-box setup as the greedy preemption test."""
    model = _FakeModel(num_slots=2, block_size=2, num_blocks=7,
                       max_blocks_per_seq=6, max_prompt_len=8)
    eng = LLMEngine(model)
    from zoo_tpu.obs.metrics import counter
    preempts0 = counter("zoo_llm_preempt_total").value
    samp = dict(temperature=1.0, seed=5)
    a = eng.submit([1, 2], 9, sampling=samp)
    b = eng.submit([3, 4], 9, sampling=samp)
    for _ in range(60):
        tick(eng)
        if a.done and b.done:
            break
    assert a.outcome == "ok" and b.outcome == "ok"
    assert a.tokens == _reference([1, 2], 9, 1.0, 5)
    assert b.tokens == _reference([3, 4], 9, 1.0, 5)
    assert counter("zoo_llm_preempt_total").value > preempts0
    eng.stop()


def test_allocator_aux_checkpoints_with_block_table_entry():
    """The per-sequence PRNG seed rides the block-table entry: set on
    admission, readable while the sequence holds blocks, cleared with
    them on free."""
    a = BlockAllocator(num_blocks=8, block_size=4)
    a.allocate("s1", 2)
    a.set_aux("s1", seed=42, resumed_at=3)
    assert a.get_aux("s1") == {"seed": 42, "resumed_at": 3}
    assert a.get_aux("never") is None
    a.free("s1")
    assert a.get_aux("s1") is None


def test_engine_checkpoints_seed_in_block_table_entry():
    eng = LLMEngine(_FakeModel(num_blocks=32, max_blocks_per_seq=8,
                               decode_delay=0.01)).start()
    try:
        h = eng.submit([3], 1000, sampling=dict(temperature=1.0,
                                                seed=77))
        while not h.tokens:
            time.sleep(0.005)
        aux = eng.allocator.get_aux(h.id)
        assert aux is not None and aux["seed"] == 77
    finally:
        eng.stop()


# ------------------------------------------------------- chunked prefill

def test_chunked_prefill_interleaves_with_decode():
    """The anti-stall property: a long prompt's prefill advances one
    chunk per tick while an already-live stream keeps decoding — the
    whole-prompt stall the chunk executable removes."""
    model = _FakeModel(num_slots=2, num_blocks=64, max_blocks_per_seq=8,
                       max_prompt_len=32, prefill_chunk=4)
    eng = LLMEngine(model)

    a = eng.submit([1], 30)
    for _ in range(3):
        tick(eng)
    before = len(a.tokens)
    assert before > 0
    long_h = eng.submit(list(range(1, 13)), 4)   # 12 tokens = 3 chunks
    progress = []
    for _ in range(2):
        tick(eng)
        progress.append(len(a.tokens))
    # two ticks in: the long prompt is still mid-prefill (2 of 3 chunks
    # fed), yet the short stream gained a token EVERY tick
    assert not long_h.tokens
    assert model.chunks[-2:] == [(0, 4), (4, 4)]
    assert progress == [before + 1, before + 2]
    for _ in range(40):
        tick(eng)
        if a.done and long_h.done:
            break
    assert a.tokens == _reference([1], 30)
    assert long_h.tokens == _reference(list(range(1, 13)), 4)
    assert eng.allocator.used_blocks == 0
    eng.stop()


def test_only_a_prompts_last_chunk_is_waited_for():
    """The scheduler waits for the device once a prompt, for the first
    generated token its LAST chunk returns; a chunk before it is
    dispatched and left to the device, which goes on with the pass's
    decode tick behind it. A model whose chunk call returns a plain int
    (every fake here) is served alike."""
    events = []

    class _Token:
        def __init__(self, value, start):
            self.value, self.start = value, start

        def __int__(self):
            events.append(("wait", self.start))
            return self.value

    class _Model(_FakeModel):
        def prefill_chunk(self, chunk, start, total_len, row,
                          sampling=None):
            tok = super().prefill_chunk(chunk, start, total_len, row,
                                        sampling)
            events.append(("chunk", int(start)))
            return _Token(tok, int(start))

        def decode_step(self, *args):
            events.append(("tick", None))
            return super().decode_step(*args)

    model = _Model(num_slots=2, num_blocks=64, max_blocks_per_seq=16,
                   max_prompt_len=32, prefill_chunk=4,
                   decode_delay=0.002)
    eng = LLMEngine(model).start()
    try:
        a = eng.submit([1], 60)
        while len(a.tokens) < 3:
            time.sleep(0.001)
        del events[:]
        prompt = list(range(1, 13))          # 12 tokens = 3 chunks
        b = eng.submit(prompt, 4)
        _drain([a, b])
    finally:
        eng.stop()
    assert a.tokens == _reference([1], 60)
    assert b.tokens == _reference(prompt, 4)
    assert [e for e in events if e[0] != "tick"] == [
        ("chunk", 0), ("chunk", 4), ("chunk", 8), ("wait", 8)]
    # a decode tick goes out between two chunks: the live stream keeps
    # its pace while the prompt feeds
    first, last = events.index(("chunk", 0)), events.index(("chunk", 8))
    assert events[first:last].count(("tick", None)) >= 2


def test_chunked_prefill_preemption_resets_cleanly():
    """A stream preempted MID-PREFILL re-queues with just its prompt
    (nothing generated yet) and completes correctly later."""
    model = _FakeModel(num_slots=2, block_size=2, num_blocks=7,
                       max_blocks_per_seq=6, max_prompt_len=8,
                       prefill_chunk=2)
    eng = LLMEngine(model)
    a = eng.submit([1, 2], 9)
    b = eng.submit([3, 4], 9)
    for _ in range(80):
        tick(eng)
        if a.done and b.done:
            break
    assert a.tokens == _reference([1, 2], 9)
    assert b.tokens == _reference([3, 4], 9)
    assert eng.allocator.used_blocks == 0
    eng.stop()


# ------------------------------------------------------------ spec parse

def test_parse_llm_spec_forms():
    cfg, eng = parse_llm_spec("llama:tiny")
    assert cfg["hidden"] == 64 and eng == {}
    cfg, eng = parse_llm_spec(
        "llama:tiny:seed=3,slots=4,block=8,blocks=64,buckets=16/64")
    assert eng == {"seed": 3, "num_slots": 4, "block_size": 8,
                   "num_blocks": 64, "prefill_buckets": (16, 64)}
    cfg, _ = parse_llm_spec(
        "llama:vocab=256,hidden=32,n_block=1,n_head=4,n_kv_head=2,"
        "intermediate=64")
    assert cfg["vocab"] == 256 and cfg["n_kv_head"] == 2
    with pytest.raises(ValueError):
        parse_llm_spec("llama:gguf")
    with pytest.raises(ValueError):
        parse_llm_spec("llama:tiny:slots")
    with pytest.raises(ValueError):
        parse_llm_spec("llama:tiny:warp=9")


# --------------------------------------------- paged model (jax, shared)

@pytest.fixture(scope="module")
def paged():
    """ONE tiny compiled model + its config, shared by every jax test
    in this file (each test runs its own engine; freed blocks are fully
    rewritten by the next owner, so sharing the cache is safe)."""
    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg = LlamaConfig(vocab=64, hidden=32, n_block=2, n_head=4,
                      n_kv_head=2, intermediate=64, rope_theta=10000.0)
    model = PagedLlamaModel(cfg, seed=0, num_slots=2, block_size=4,
                            num_blocks=24, max_blocks_per_seq=6,
                            prefill_buckets=(8, 16))
    return cfg, model


def test_gqa_cache_layout(paged):
    """K/V are stored at num_kv_heads (2), NOT num_heads (4) — the GQA
    memory saving is real, not re-expanded into the cache."""
    cfg, model = paged
    import jax.numpy as jnp
    assert cfg.n_kv_head < cfg.n_head
    expect = (cfg.n_block, model.num_blocks, cfg.n_kv_head,
              model.block_size, cfg.head_dim)
    assert model._kc.shape == expect
    assert model._vc.shape == expect
    assert model._kc.dtype == jnp.float32


def test_paged_decode_matches_full_context_reference(paged):
    """The correctness anchor: greedy generation through the paged
    prefill + block-gathered decode must match token-for-token a greedy
    loop over the ORIGINAL full-context Llama forward (same params) —
    across a block boundary and a preemption-free multi-stream mix."""
    cfg, model = paged
    import jax.numpy as jnp
    from zoo_tpu.models.llm.llama import Llama

    layer = Llama(cfg, lm_head=True)

    def ref_generate(prompt, n):
        seq = list(int(t) for t in prompt)
        out = []
        for _ in range(n):
            logits = layer.call(model.params,
                                jnp.asarray([seq], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
            seq.append(out[-1])
        return out

    eng = LLMEngine(model).start()
    try:
        rs = np.random.RandomState(7)
        prompts = [rs.randint(0, cfg.vocab, (n,)) for n in (3, 9, 14)]
        n_new = 9   # crosses the 4-token block boundary repeatedly
        hs = [eng.submit(p, n_new) for p in prompts]
        _drain(hs, budget=300.0)
        for p, h in zip(prompts, hs):
            assert h.outcome == "ok"
            assert h.tokens == ref_generate(p, n_new), \
                f"paged decode diverged for prompt len {len(p)}"
        assert eng.allocator.used_blocks == 0
    finally:
        eng.stop()


def test_decode_compiles_exactly_one_executable(paged):
    """The fixed-shape contract: after streams of every shape mix, the
    decode jit cache holds ONE executable and prefill at most one per
    bucket — request churn must never recompile."""
    cfg, model = paged
    eng = LLMEngine(model).start()
    try:
        rs = np.random.RandomState(3)
        hs = [eng.submit(rs.randint(0, cfg.vocab, (n,)), 3)
              for n in (2, 7, 8, 13)]   # both buckets, varied fill
        _drain(hs, budget=300.0)
    finally:
        eng.stop()
    counts = model.compile_counts()
    if counts["decode"] < 0:
        pytest.skip("jit cache size API unavailable on this jax")
    assert counts["decode"] == 1, counts
    assert 0 < counts["prefill"] <= len(model.prefill_buckets), counts
    # compiled-artifact contracts on the ONE decode executable: the
    # donated cache is aliased in the HLO (a dropped donation doubles
    # decode HBM) and the outfeed stays slots x 1 int32 ids, never
    # slots x vocab logits (zoo-lint HLO-DONATION / HLO-HOST-TRANSFER)
    from zoo_tpu.analysis.hlo import assert_llm_executable
    assert_llm_executable(model, "decode")


def _generate_all(model, prompts, n, sampling=None, rids=None,
                  budget=300.0):
    eng = LLMEngine(model).start()
    try:
        hs = [eng.submit(p, n, sampling=sampling,
                         rid=None if rids is None else rids[i])
              for i, p in enumerate(prompts)]
        _drain(hs, budget=budget)
        assert all(h.outcome == "ok" for h in hs), \
            [(h.outcome, h.error) for h in hs]
        assert eng.allocator.used_blocks == 0
        return [h.tokens for h in hs]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def paged_streams(paged):
    """Reference streams (greedy + seeded sampling) through the shared
    dense-gather model — the anchor the flash-kernel and chunked-prefill
    variants must reproduce byte-for-byte."""
    cfg, model = paged
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, cfg.vocab, (n,)) for n in (3, 9, 14)]
    greedy = _generate_all(model, prompts, 7)
    rids = [f"ref-{i}" for i in range(len(prompts))]
    samp = dict(temperature=0.9, top_k=16, top_p=0.95)
    sampled = _generate_all(model, prompts, 7, sampling=samp, rids=rids)
    assert sampled != greedy   # the sampler actually sampled
    return prompts, greedy, sampled, samp, rids


def test_paged_flash_decode_token_identical_to_dense(paged,
                                                     paged_streams):
    """The kernel-selection contract: decode through the paged
    flash-decode Pallas kernel (interpret off-TPU) emits byte-identical
    streams — greedy AND seeded sampling — to the dense-gather
    reference path on the same weights."""
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg, model = paged
    prompts, greedy, sampled, samp, rids = paged_streams
    flash = PagedLlamaModel(
        cfg, params=model.params, num_slots=2, block_size=4,
        num_blocks=24, max_blocks_per_seq=6, prefill_buckets=(8, 16),
        decode_impl="flash")
    assert flash.decode_attention_impl == "flash"
    assert _generate_all(flash, prompts, 7) == greedy
    assert _generate_all(flash, prompts, 7, sampling=samp,
                         rids=rids) == sampled


def test_chunked_prefill_streams_byte_identical(paged, paged_streams):
    """Chunked prefill is the same math fed through the cache in
    slices: every stream must match the whole-prompt bucket path
    byte-for-byte, and the prefill census must collapse to the ONE
    chunk executable."""
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg, model = paged
    prompts, greedy, sampled, samp, rids = paged_streams
    chunked = PagedLlamaModel(
        cfg, params=model.params, num_slots=2, block_size=4,
        num_blocks=24, max_blocks_per_seq=6, prefill_buckets=(8, 16),
        prefill_chunk=4)
    assert _generate_all(chunked, prompts, 7) == greedy
    assert _generate_all(chunked, prompts, 7, sampling=samp,
                         rids=rids) == sampled
    counts = chunked.compile_counts()
    if counts["decode"] >= 0:
        assert counts["prefill"] == 0 and counts["prefill_chunk"] == 1, \
            counts


def test_decode_host_transfer_is_token_ids_only(paged):
    """The transfer contract the on-device sampler exists for: per
    decode tick, exactly slots x 1 int32 ids cross to the host — never
    the slots x vocab logits."""
    from zoo_tpu.obs.metrics import counter
    cfg, model = paged
    fam = counter("zoo_llm_host_transfer_bytes_total", labels=("kind",))
    before = fam.labels(kind="tokens").value
    eng = LLMEngine(model).start()
    try:
        _drain([eng.submit(np.arange(1, 5) % cfg.vocab, 6)],
               budget=120.0)
    finally:
        eng.stop()
    # read AFTER stop(): the readback thread has joined, so the step
    # counter and the transfer counter are settled together
    steps = eng._decode_steps
    delta = fam.labels(kind="tokens").value - before
    assert steps > 0
    assert delta == steps * model.num_slots * 4, \
        (delta, steps, model.num_slots)


def test_preempt_resume_greedy_matches_host_argmax_reference():
    """On-device greedy across a REAL preemption equals a host-side
    argmax loop over the full-context forward (the pre-PR reference):
    a tiny pool forces eviction + re-prefill mid-stream."""
    import jax.numpy as jnp

    from zoo_tpu.models.llm.llama import Llama, LlamaConfig
    from zoo_tpu.obs.metrics import counter
    from zoo_tpu.serving.llm.model import PagedLlamaModel

    cfg = LlamaConfig(vocab=64, hidden=32, n_block=2, n_head=4,
                      n_kv_head=2, intermediate=64, rope_theta=10000.0)
    # 7 usable blocks x 4 tokens: two 20-token streams cannot coexist
    model = PagedLlamaModel(cfg, seed=0, num_slots=2, block_size=4,
                            num_blocks=8, max_blocks_per_seq=8,
                            prefill_buckets=(8, 32))
    layer = Llama(cfg, lm_head=True)

    def host_argmax(prompt, n):
        seq = [int(t) for t in prompt]
        out = []
        for _ in range(n):
            logits = layer.call(model.params,
                                jnp.asarray([seq], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
            seq.append(out[-1])
        return out

    preempts0 = counter("zoo_llm_preempt_total").value
    prompts = [np.arange(2, 8) % cfg.vocab, np.arange(3, 9) % cfg.vocab]
    toks = _generate_all(model, prompts, 14, budget=300.0)
    assert counter("zoo_llm_preempt_total").value > preempts0, \
        "pool sizing failed to force a preemption"
    for p, got in zip(prompts, toks):
        assert got == host_argmax(p, 14), \
            "preempt-resume diverged from the host-argmax reference"


# ------------------------------------------------- streaming over the wire

@pytest.fixture(scope="module")
def llm_server(paged):
    """The shared model behind a REAL ServingServer TCP door (llm-only
    replica: no predict model mounted)."""
    from zoo_tpu.serving.server import ServingServer
    _, model = paged
    eng = LLMEngine(model)
    server = ServingServer(None, llm_engine=eng.start(), port=0,
                           batch_size=2, max_wait_ms=1.0).start()
    yield server, eng
    server.stop()


def _stream_tokens(host, port, prompt, n, rid=None, resume_from=0,
                   deadline=None, **sampling):
    from zoo_tpu.serving.tcp_client import _Connection
    conn = _Connection(host, port)
    frames, toks = [], []
    msg = {"op": "generate", "id": rid,
           "prompt": np.asarray(prompt, np.int32),
           "max_new_tokens": n, "resume_from": resume_from}
    msg.update(sampling)
    try:
        for f in conn.stream(msg, deadline=deadline):
            frames.append(f)
            toks.extend(f.get("tokens") or ())
    finally:
        conn.close()
    return toks, frames


def test_generate_streams_over_wire(paged, llm_server):
    cfg, model = paged
    server, eng = llm_server
    prompt = np.arange(1, 6) % cfg.vocab
    toks, frames = _stream_tokens(server.host, server.port, prompt, 6)
    assert len(toks) == 6
    assert frames[-1]["done"] and frames[-1]["outcome"] == "ok"
    assert frames[-1]["n_tokens"] == 6
    # a direct engine replay of the same rid would dedup; a fresh id
    # reproduces the same tokens (deterministic greedy decode)
    again, _ = _stream_tokens(server.host, server.port, prompt, 6)
    assert again == toks


def test_generate_sampling_on_the_wire(paged, llm_server):
    """temperature/top_k/top_p/seed ride the generate frame; an
    explicit seed makes the stream reproducible across fresh request
    ids, and sampling actually changes the tokens vs greedy."""
    cfg, _ = paged
    server, _ = llm_server
    prompt = np.arange(3, 9) % cfg.vocab
    greedy, _ = _stream_tokens(server.host, server.port, prompt, 6)
    kw = dict(temperature=0.9, top_k=16, top_p=0.95, seed=1234)
    a, frames = _stream_tokens(server.host, server.port, prompt, 6,
                               **kw)
    b, _ = _stream_tokens(server.host, server.port, prompt, 6, **kw)
    assert frames[-1]["outcome"] == "ok" and len(a) == 6
    assert a == b, "explicit seed must reproduce the stream"
    assert a != greedy, "sampling params were ignored on the wire"


def test_generate_resume_from_skips_prefix(paged, llm_server):
    cfg, _ = paged
    server, _ = llm_server
    prompt = np.arange(2, 8) % cfg.vocab
    full, _ = _stream_tokens(server.host, server.port, prompt, 6)
    suffix, frames = _stream_tokens(server.host, server.port, prompt, 6,
                                    resume_from=4)
    assert suffix == full[4:]
    assert frames[-1]["n_tokens"] == 6   # server-side count is total


def test_generate_dead_on_arrival_deadline(paged, llm_server):
    server, _ = llm_server
    from zoo_tpu.serving.tcp_client import _Connection
    conn = _Connection(server.host, server.port)
    try:
        frames = list(conn.stream({"op": "generate", "prompt": [1, 2],
                                   "max_new_tokens": 4,
                                   "deadline_ms": 0.0}))
    finally:
        conn.close()
    assert frames[-1].get("expired") and frames[-1]["outcome"] == "expired"


def test_generate_client_disconnect_frees_blocks(paged, llm_server):
    """The last subscriber dropping mid-stream cancels the stream and
    returns its KV blocks — an abandoned client must not pin the pool
    until max_new_tokens."""
    from zoo_tpu.serving.tcp_client import _Connection
    server, eng = llm_server
    before = eng.allocator.used_blocks
    conn = _Connection(server.host, server.port)
    it = conn.stream({"op": "generate", "prompt": [3, 1],
                      "max_new_tokens": 100_000})
    first = next(it)
    assert first.get("tokens") or first.get("done") is False
    conn.close()   # walk away mid-stream
    deadline = time.monotonic() + 10
    while eng.allocator.used_blocks > before and \
            time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.allocator.used_blocks == before, "disconnect leaked blocks"


def test_ha_client_generate_failover_resumes_midstream(paged):
    """Mid-stream replica loss under HAServingClient.generate: the
    second replica (bit-identical weights, greedy decode) resumes from
    ``resume_from`` and the caller sees one gapless, duplicate-free
    token stream."""
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.server import ServingServer
    cfg, model = paged
    # two engines over the SAME model object = bit-identical weights
    # (they serialize on the model lock, like two processes on one chip)
    eng1, eng2 = LLMEngine(model).start(), LLMEngine(model).start()
    s1 = ServingServer(None, llm_engine=eng1, port=0, batch_size=2,
                       max_wait_ms=1.0).start()
    s2 = ServingServer(None, llm_engine=eng2, port=0, batch_size=2,
                       max_wait_ms=1.0).start()
    try:
        prompt = (np.arange(5) * 3 + 1) % cfg.vocab
        ref, _ = _stream_tokens(s2.host, s2.port, prompt, 8)
        cli = HAServingClient([(s1.host, s1.port), (s2.host, s2.port)],
                              hedge=False, deadline_ms=120_000)
        got = []
        for tok in cli.generate(prompt, 8):
            got.append(tok)
            if len(got) == 3:
                s1.stop()   # primary dies mid-stream
        assert got == ref, f"failover stream diverged: {got} vs {ref}"
        cli.close()
    finally:
        for srv, eng in ((s1, eng1), (s2, eng2)):
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — s1 already stopped
                pass
        assert eng1.allocator.used_blocks == 0
        assert eng2.allocator.used_blocks == 0


def test_ha_client_sampled_generate_failover_resumes_midstream(paged):
    """Seeded sampling across an HA failover-with-resume: the PRNG key
    is fold_in(seed, token index) and the seed rides the stream, so the
    surviving replica regenerates the exact suffix — one gapless,
    duplicate-free SAMPLED stream across a replica loss."""
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.server import ServingServer
    cfg, model = paged
    kw = dict(temperature=0.9, top_k=16, top_p=0.95, seed=99)
    eng1, eng2 = LLMEngine(model).start(), LLMEngine(model).start()
    s1 = ServingServer(None, llm_engine=eng1, port=0, batch_size=2,
                       max_wait_ms=1.0).start()
    s2 = ServingServer(None, llm_engine=eng2, port=0, batch_size=2,
                       max_wait_ms=1.0).start()
    try:
        prompt = (np.arange(5) * 5 + 2) % cfg.vocab
        ref, _ = _stream_tokens(s2.host, s2.port, prompt, 8, **kw)
        cli = HAServingClient([(s1.host, s1.port), (s2.host, s2.port)],
                              hedge=False, deadline_ms=120_000)
        got = []
        for tok in cli.generate(prompt, 8, **kw):
            got.append(tok)
            if len(got) == 3:
                s1.stop()   # primary dies mid-stream
        assert got == ref, f"sampled failover diverged: {got} vs {ref}"
        cli.close()
    finally:
        for srv in (s1, s2):
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — s1 already stopped
                pass
        assert eng1.allocator.used_blocks == 0
        assert eng2.allocator.used_blocks == 0


# ------------------- prefix caching + quantized KV cache (real model)

def _engine_tokens(model, prompts, max_new=6, prefix_cache=False,
                   sampling=None):
    """Run ``prompts`` sequentially (each waits for the previous, so
    registration is deterministic) and return their token streams plus
    the engine stats."""
    eng = LLMEngine(model, prefix_cache=prefix_cache).start()
    try:
        outs = []
        for i, p in enumerate(prompts):
            h = eng.submit(np.asarray(p, np.int32), max_new,
                           rid=f"px-{i}", sampling=sampling)
            _drain([h], budget=120.0)
            assert h.outcome == "ok", (h.outcome, h.error)
            outs.append(list(h.tokens))
        return outs, eng.stats()
    finally:
        eng.stop()


def _px_model(kv_dtype="f32", chunk=0, impl="dense"):
    from zoo_tpu.models.llm.llama import tiny_llama_config
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    return PagedLlamaModel(tiny_llama_config(), seed=0, num_slots=2,
                           block_size=4, num_blocks=48,
                           max_blocks_per_seq=8, prefill_buckets=(8, 32),
                           kv_dtype=kv_dtype, prefill_chunk=chunk,
                           decode_impl=impl)


_PX_SHARED = list(range(1, 17))     # 16 tokens = 4 full blocks, aligned
_PX_PROMPTS = [_PX_SHARED, _PX_SHARED + [99, 98, 97],
               _PX_SHARED + [50], _PX_SHARED]


@pytest.mark.parametrize("chunk", [0, 4])
def test_prefix_cache_byte_identical_real_model(chunk):
    """Acceptance: greedy streams byte-identical with prefix caching on
    vs off — bucketed (chunk=0: novel suffix fed through the ONE chunk
    executable) AND chunked prefill — with real hits, a real CoW fork
    on the aligned repeat, and the executable census intact."""
    m_off = _px_model(chunk=chunk)
    off, _ = _engine_tokens(m_off, _PX_PROMPTS)
    m_on = _px_model(chunk=chunk)
    on, st = _engine_tokens(m_on, _PX_PROMPTS, prefix_cache=True)
    assert on == off
    assert st["prefix_hit_tokens"] > 0
    assert st["blocks_used"] == 0          # zero leaks
    counts = m_on.compile_counts()
    assert counts["decode"] == 1
    assert counts["prefill_chunk"] <= 1    # suffix feed is ONE exec
    if chunk:
        assert counts["prefill"] == 0      # bucket path never compiled


def test_prefix_cache_sampled_streams_identical_real_model():
    sampling = dict(temperature=0.8, top_k=12, top_p=0.9, seed=77)
    off, _ = _engine_tokens(_px_model(), _PX_PROMPTS, sampling=sampling)
    on, st = _engine_tokens(_px_model(), _PX_PROMPTS, sampling=sampling,
                            prefix_cache=True)
    assert on == off and st["prefix_hit_tokens"] > 0


def test_int8_cache_flash_dense_token_identity():
    """Acceptance: with the int8 KV cache, the paged flash kernel
    (interpreter = the exact kernel TPU compiles) and the dense-gather
    fallback agree token-for-token — and at test scale the quantized
    streams match the f32 reference ids outright."""
    ref, _ = _engine_tokens(_px_model("f32"), _PX_PROMPTS, max_new=8)
    dense, st = _engine_tokens(_px_model("int8", impl="dense"),
                               _PX_PROMPTS, max_new=8)
    flash, _ = _engine_tokens(_px_model("int8", impl="flash"),
                              _PX_PROMPTS, max_new=8)
    assert dense == flash                  # the hard contract
    assert dense == ref                    # tiny-scale quality parity
    assert st["kv_cache_dtype"] == "int8"


def test_int8_cache_with_prefix_cache_and_census():
    """Both features on at once: byte-identity to int8-without-cache,
    decode-compiles==1, chunk census unchanged, zero leaked blocks."""
    off, _ = _engine_tokens(_px_model("int8", chunk=4), _PX_PROMPTS)
    m = _px_model("int8", chunk=4)
    on, st = _engine_tokens(m, _PX_PROMPTS, prefix_cache=True)
    assert on == off
    counts = m.compile_counts()
    assert counts["decode"] == 1 and counts["prefill_chunk"] == 1
    assert st["blocks_used"] == 0
    assert st["kv_bytes_per_token"] < _px_model("bf16")\
        .kv_bytes_per_token


def test_kv_dtype_resolution_and_bytes_model():
    """auto records its selection (CPU -> f32, never silent), bad
    values are loud, and the bytes-per-token model halves bf16 -> int8
    modulo the scale rows."""
    from zoo_tpu.serving.llm.model import resolve_kv_dtype
    assert resolve_kv_dtype("int8") == "int8"
    assert resolve_kv_dtype("bf16") == "bf16"
    assert resolve_kv_dtype("auto") in ("int8", "f32")  # TPU vs CPU
    with pytest.raises(ValueError):
        resolve_kv_dtype("fp4")
    f32 = _px_model("f32")
    bf16 = _px_model("bf16")
    i8 = _px_model("int8")
    assert bf16.kv_bytes_per_token * 2 == f32.kv_bytes_per_token
    # int8 payload is half of bf16; the absmax scale rows ride on top
    c = f32.cfg
    scale_bytes = 2 * c.n_block * c.n_kv_head * 4
    assert i8.kv_bytes_per_token == \
        bf16.kv_bytes_per_token // 2 + scale_bytes
    assert i8.kv_cache_dtype_requested == "int8"
    import jax.numpy as jnp
    assert i8._kc.dtype == jnp.int8
    assert bf16._kc.dtype == jnp.bfloat16
    # a block's scales are one head-major row (what the TPU holds
    # as declared once it is 128 wide)
    assert i8._cache["ks"].shape == (c.n_block, i8.num_blocks, 1,
                                     c.n_kv_head * i8.block_size)


# ----------------------- weights held in the dtype the dot reads (PR 26)

_NARROW_KW = dict(num_slots=2, block_size=4, num_blocks=24,
                  max_blocks_per_seq=6, prefill_buckets=(8, 16))


def _bf16_exact(params):
    """The same tree, every value one that bf16 holds exactly (what a
    bf16 checkpoint loaded as f32 is): narrowing it loses nothing, so
    only the activations' rounding separates the two models."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


@pytest.mark.parametrize("case", [
    "tpu_f32", "tpu_bf16_tree", "tpu_tied", "cpu", "gpu",
    "precision_highest", "precision_float32"])
def test_narrow_dot_weights_rule(paged, case):
    """The rule of docs/llm_serving.md "Weights": f32 dot leaves go
    bf16 on a TPU at the platform's own matmul precision; norms,
    ``embed`` (tied: the head too), an already-narrow leaf, every other
    platform and a raised precision come back as the SAME objects."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from zoo_tpu.serving.llm.model import (
        DOT_BLOCK_LEAVES,
        narrow_dot_weights,
    )
    _, model = paged
    params = model.params
    assert model.weight_dtype == "float32"      # CPU: held as handed in
    assert set(DOT_BLOCK_LEAVES) < set(params["blocks"])

    def same(a, b):
        la, lb = (jax.tree_util.tree_leaves(t) for t in (a, b))
        return len(la) == len(lb) and all(x is y for x, y in zip(la, lb))

    platform, precision = "tpu", contextlib.nullcontext()
    if case in ("cpu", "gpu"):
        platform = case
    elif case.startswith("precision_"):
        precision = jax.default_matmul_precision(case.split("_", 1)[1])
    elif case == "tpu_tied":
        params = {k: v for k, v in params.items() if k != "head"}
    elif case == "tpu_bf16_tree":
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
    with precision:
        out = narrow_dot_weights(params, platform)
    if case not in ("tpu_f32", "tpu_tied"):
        assert same(out, params)
        return
    for name, leaf in params["blocks"].items():
        got = out["blocks"][name]
        if name in DOT_BLOCK_LEAVES:
            assert got.dtype == jnp.bfloat16 and got.shape == leaf.shape
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(leaf.astype(jnp.bfloat16)))
        else:
            assert got is leaf                   # the two norm gains
    assert out["embed"] is params["embed"]
    assert out["final_norm"] is params["final_norm"]
    if case == "tpu_tied":
        assert "head" not in out
    else:
        assert out["head"].dtype == jnp.bfloat16
    # the caller's tree is left as it was
    assert all(leaf.dtype == jnp.float32 and not leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(params))
    # with bf16 the platform's stated default, the rule still engages
    with jax.default_matmul_precision("bfloat16"):
        assert not same(narrow_dot_weights(params, "tpu"), params)


@pytest.fixture(scope="module")
def narrowed(paged):
    """The shared geometry twice over bf16-exact weights: held f32 (as
    every platform but the TPU holds them) and narrowed as the TPU
    rule narrows them — the CPU then runs the helper's narrow branch."""
    from zoo_tpu.serving.llm.model import (
        PagedLlamaModel,
        narrow_dot_weights,
    )
    cfg, model = paged
    exact = _bf16_exact(model.params)
    wide = PagedLlamaModel(cfg, params=exact, **_NARROW_KW)
    narrow = PagedLlamaModel(cfg, params=narrow_dot_weights(exact, "tpu"),
                             **_NARROW_KW)
    assert (wide.weight_dtype, narrow.weight_dtype) == \
        ("float32", "bfloat16")
    return cfg, wide, narrow


@pytest.mark.parametrize("variant", ["chunked", "flash", "spec"])
def test_narrowed_executables_agree(narrowed, variant):
    """On narrowed leaves the four executables still agree with one
    another as their f32 parity tests demand: chunk prefill == bucket
    prefill, flash decode == dense decode, verify == plain decode —
    byte-identical streams, greedy and seeded."""
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg, _, narrow = narrowed
    rs = np.random.RandomState(11)
    motif = rs.randint(0, cfg.vocab, (4,))
    prompts = [rs.randint(0, cfg.vocab, (3,)), np.tile(motif, 3),
               rs.randint(0, cfg.vocab, (14,))]
    rids = [f"nw-{i}" for i in range(len(prompts))]
    samp = dict(temperature=0.9, top_k=16, top_p=0.95)
    kw = dict(_NARROW_KW, **{
        "chunked": dict(prefill_chunk=4),
        "flash": dict(decode_impl="flash"),
        "spec": dict(spec_k=2)}[variant])
    other = PagedLlamaModel(cfg, params=narrow.params, **kw)
    assert other.params["blocks"]["wq"] is narrow.params["blocks"]["wq"]
    for sampling in (None, samp):
        want = _generate_all(narrow, prompts, 7, sampling=sampling,
                             rids=rids)
        assert _generate_all(other, prompts, 7, sampling=sampling,
                             rids=rids) == want
    counts = other.compile_counts()
    assert counts[{"chunked": "prefill_chunk", "flash": "decode",
                   "spec": "verify"}[variant]] == 1, counts


@pytest.mark.parametrize("which", ["prefill", "prefill_chunk", "decode"])
def test_narrowed_logits_within_bf16_activation_rounding(
        narrowed, which, monkeypatch):
    """What narrowing costs where the device does NOT already round
    (this CPU): the activations entering each weight dot drop to bf16
    — 8 significant bits, a relative step of 2**-8 — and nothing else
    moves, the weights being bf16-exact. A dot averages its terms'
    errors and this geometry has 2 x 4 dots in series plus the head:
    the logits stay within four steps (2**-6) of the f32 model's,
    relative to the widest logit (they read 0.85-0.96 of ONE step)."""
    import jax.numpy as jnp
    from zoo_tpu.serving.llm import model as M
    cfg, wide, narrow = narrowed
    # the executables end in a sampler; hand the logits out instead
    monkeypatch.setattr(M, "_sample_row", lambda last, *a: last)
    monkeypatch.setattr(M, "_sample_tokens", lambda logits, *a: logits)
    prompt = np.random.RandomState(5).randint(0, cfg.vocab, (8,))
    row = np.array([1, 2, 3, 0, 0, 0], np.int32)

    def logits(m):
        cache = {k: jnp.zeros_like(v) for k, v in m._cache.items()}
        if which == "prefill_chunk":
            lay = m._layouts["prefill_chunk"]
            for start in (0, 4):       # two chunks of 4 through the cache
                ids = np.zeros(lay.fields[0][1], np.int32)
                ids[0, :4] = prompt[start:start + 4]
                # (a chunk narrower than the executable is a prompt's
                # last: the first is fed as a prompt of 4)
                out, cache = m._prefill_chunk_fn(
                    m.params, cache, lay.pack(
                        ids=ids, start=start, **m._row_operands(
                            start + 4, row, None, None)))
            return np.asarray(out)
        out, cache = m._prefill_fn(
            m.params, cache, m._prefill_layouts[8].pack(
                ids=prompt[None], **m._row_operands(8, row, None, None)))
        if which == "prefill":
            return np.asarray(out)
        S = m.num_slots
        tables = np.zeros((S, m.max_blocks_per_seq), np.int32)
        tables[0] = row
        out, _ = m._decode_fn(
            m.params, cache, jnp.zeros(S, jnp.int32),
            m._layouts["decode"].pack(
                host_tokens=np.full(S, 7), use_host=np.ones(S, bool),
                block_tables=tables, positions=[8, 0],
                temps=np.zeros(S), topks=np.zeros(S), topps=np.ones(S),
                seeds=np.zeros(S)))
        return np.asarray(out)[0]

    ref, got = logits(wide), logits(narrow)
    assert ref.shape == got.shape == (cfg.vocab,)
    assert got.dtype == np.float32          # accumulated and kept in f32
    gap = np.abs(got - ref).max() / np.abs(ref).max()
    assert 0.0 < gap < 2.0 ** -6, gap       # rounded, and only that


@pytest.mark.parametrize("held", ["as_built", "narrowed"])
def test_weight_dtype_and_bytes_published(paged, narrowed, held):
    """``weight_dtype`` / ``weight_bytes`` in ``stats()`` and the
    ``zoo_llm_weight_bytes`` gauge are the resident tree's own dtype
    and summed ``nbytes`` — on this CPU the tree a model builds is
    untouched f32, the narrowed one half of that in its dot leaves."""
    import jax
    from zoo_tpu.obs.metrics import get_registry
    model = paged[1] if held == "as_built" else narrowed[2]
    leaves = jax.tree_util.tree_leaves(model.params)
    resident = sum(leaf.nbytes for leaf in leaves)
    eng = LLMEngine(model)
    st = eng.stats()
    assert st["weight_bytes"] == resident == model.weight_bytes
    assert st["weight_dtype"] == {"as_built": "float32",
                                  "narrowed": "bfloat16"}[held]
    assert st["kv_cache_dtype"] == "f32"

    def gauge():
        return {g["name"]: g["value"] for g in
                get_registry().snapshot()["gauges"]}["zoo_llm_weight_bytes"]

    # the gauge is process-global and every engine republishes it each
    # scheduler pass (``llm_server``'s idles in this process, one pass
    # every 5 ms): read it right after THIS engine published
    def published():
        eng._publish()
        return gauge() == resident

    assert any(published() for _ in range(50)), gauge()
    if held == "narrowed":
        f32 = sum(leaf.nbytes for leaf in
                  jax.tree_util.tree_leaves(paged[1].params))
        kept = sum(model.params[k].nbytes for k in
                   ("embed", "final_norm")) + sum(
            model.params["blocks"][k].nbytes for k in
            ("attn_norm", "mlp_norm"))
        assert resident - kept == (f32 - kept) // 2


def test_spec_parses_kv_and_prefix_cache():
    from zoo_tpu.serving.llm.spec import build_llm_engine
    eng = build_llm_engine(
        "llama:tiny:slots=2,block=4,blocks=16,tables=4,buckets=8,"
        "kv=int8,prefix_cache=1", start=False)
    try:
        assert eng.prefix_cache is True
        assert eng.allocator.prefix_cache is True
        assert eng.model.kv_cache_dtype == "int8"
    finally:
        eng.stop()
    with pytest.raises(ValueError):
        build_llm_engine("llama:tiny:kv=fp4", start=False)


# ------------------------------------------------------------ chaos smoke

@pytest.mark.perf
def test_check_llm_decode_script_runs():
    """The decode hot-path smoke (scripts/check_llm_decode.py): a
    2-replica chunked-prefill group under concurrent mixed
    prefill/decode load — chunked streams byte-identical to the
    unchunked reference, decode-compiles==1, zero leaked KV blocks,
    and the overlapped tick pipeline's device-busy ratio above the CPU
    floor."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join("scripts", "check_llm_decode.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LLM DECODE OK" in proc.stdout


@pytest.mark.perf
def test_check_prefix_cache_script_runs():
    """The prefix-cache chaos smoke (scripts/check_prefix_cache.py): a
    2-replica group with prefix caching on, concurrent streams sharing
    a 400-token prefix — byte-identical to the no-cache reference
    across a mid-storm SIGKILL, hit-rate above the floor, zero leaked
    blocks, and the respawned replica re-warms."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join("scripts", "check_prefix_cache.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PREFIX CACHE OK" in proc.stdout


@pytest.mark.chaos
def test_check_llm_serving_script_runs():
    """The 2-replica SIGKILL smoke (scripts/check_llm_serving.py): a
    real supervised llama:tiny replica group streams concurrent
    mixed-length generations, loses one replica mid-stream, and the HA
    client contract holds — zero client-visible failures, token streams
    byte-identical to the reference, zero leaked KV blocks."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join("scripts", "check_llm_serving.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LLM SERVING OK" in proc.stdout


# -------------------------------------------------- tensor-parallel (mesh)

class TestTensorParallel:
    """mesh= support on PagedLlamaModel (docs/multichip.md): one set of
    weights + one paged KV cache span the mesh's model axis. The full
    token-identity acceptance check runs in scripts/check_multichip.py
    (multichip marker); these are the cheap unit guarantees."""

    def test_spec_parses_tp_knob(self):
        _, eng = parse_llm_spec("llama:tiny:tp=2,slots=4")
        assert eng["tp"] == 2 and eng["num_slots"] == 4

    def test_env_tp_knob(self, monkeypatch):
        from zoo_tpu.serving.llm.spec import _env_engine_defaults
        monkeypatch.setenv("ZOO_LLM_TP", "2")
        assert _env_engine_defaults()["tp"] == 2

    def test_kv_head_divisibility_enforced(self):
        """tiny config has n_kv_head=2: tp=3 cannot shard the KV cache
        on the heads axis and must refuse loudly at construction (not
        at first decode)."""
        import jax

        from zoo_tpu.models.llm.llama import tiny_llama_config
        from zoo_tpu.parallel import build_mesh
        from zoo_tpu.serving.llm.model import PagedLlamaModel

        if len(jax.devices()) < 3:
            pytest.skip("needs >= 3 devices")
        mesh = build_mesh(jax.devices()[:3], axis_sizes={"model": 3})
        with pytest.raises(ValueError, match="n_kv_head"):
            PagedLlamaModel(tiny_llama_config(), mesh=mesh)

    def test_tp_spec_needs_enough_devices(self, monkeypatch):
        import jax

        from zoo_tpu.serving.llm.spec import build_llm_engine
        n = len(jax.devices())
        with pytest.raises(ValueError, match="only"):
            build_llm_engine(f"llama:tiny:tp={n * 2}", start=False)

    def test_single_device_mesh_is_ignored(self):
        """mesh over one device (or size-1 model axis) degrades to the
        plain single-device layout — tp reported as 1."""
        import jax

        from zoo_tpu.models.llm.llama import tiny_llama_config
        from zoo_tpu.parallel import build_mesh
        from zoo_tpu.serving.llm.model import PagedLlamaModel

        mesh = build_mesh(jax.devices()[:1], axis_sizes={"data": 1})
        m = PagedLlamaModel(tiny_llama_config(), num_blocks=8, mesh=mesh)
        assert m.mesh is None and m.tp == 1
