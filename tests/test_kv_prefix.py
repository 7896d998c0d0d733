"""Prefix-cached KV block allocator (docs/llm_serving.md): rolling
content hashes, refcounted sharing, copy-on-write forks, LRU eviction
over refcount-0 blocks only, and the engine-level admission contract —
all against pure-python fakes, so the whole file is tier-1 cheap.

The property test drives random alloc/share/write-fork/free
interleavings against a shadow model and asserts the pool never leaks
a block, never double-hands one out, and never evicts a block a live
sequence still references.
"""

import time

import numpy as np
import pytest

from llm_tick import HostStepped, tick
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.kv_cache import (
    BlockAllocator,
    prefix_block_hashes,
)


# ----------------------------------------------------------- rolling hash

def test_rolling_hash_full_blocks_only():
    assert prefix_block_hashes([1, 2, 3], 4) == []
    assert len(prefix_block_hashes([1, 2, 3, 4], 4)) == 1
    assert len(prefix_block_hashes(list(range(11)), 4)) == 2


def test_rolling_hash_binds_the_whole_prefix():
    """Block 1's key must differ when block 0 differs, even though
    block 1's own tokens are identical — a hash hit implies the entire
    prefix matches, which is what makes aliasing its KV safe."""
    a = prefix_block_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    b = prefix_block_hashes([5, 6, 7, 8, 9, 9, 9, 9], 4)
    assert a[0] != b[0]
    assert a[1] != b[1]          # same block tokens, different prefix
    c = prefix_block_hashes([1, 2, 3, 4, 9, 9, 9, 9, 1], 4)
    assert c[:2] == a[:2]        # longer prompt, same leading blocks


# ------------------------------------------------- share / fork / evict

def _alloc(n=16, bs=4):
    return BlockAllocator(num_blocks=n, block_size=bs,
                          prefix_cache=True)


def test_acquire_bumps_refs_and_counts_blocks_once():
    a = _alloc()
    h = prefix_block_hashes(list(range(8)), 4)
    a.allocate("w", 2)
    a.register_blocks("w", h)
    got = a.acquire_prefix("r", h)
    assert got == a.blocks_of("w")
    # pool pressure counts the shared blocks ONCE
    assert a.used_blocks == 2
    assert a.shared_blocks == 2
    assert a.stats()["blocks_shared"] == 2
    a.free("r")
    assert a.shared_blocks == 0
    assert a.used_blocks == 2     # writer still owns them


def test_free_parks_registered_blocks_for_reuse():
    a = _alloc()
    h = prefix_block_hashes(list(range(8)), 4)
    a.allocate("w", 2)
    a.register_blocks("w", h)
    assert a.free("w") == 2
    assert a.used_blocks == 0
    assert a.cached_blocks == 2   # matchable, not leaked
    # a later stream re-binds them without any writer alive
    got = a.acquire_prefix("r", h)
    assert len(got) == 2 and a.cached_blocks == 0
    assert a.used_blocks == 2


def test_match_stops_at_first_miss():
    a = _alloc()
    h = prefix_block_hashes(list(range(12)), 4)
    a.allocate("w", 3)
    a.register_blocks("w", h[:2])      # only two of three published
    assert a.match_prefix(h) == 2
    got = a.acquire_prefix("r", h)
    assert len(got) == 2


def test_eviction_is_lru_and_never_touches_refcounted_blocks():
    a = BlockAllocator(num_blocks=6, block_size=4, prefix_cache=True)
    h1 = prefix_block_hashes([1, 2, 3, 4], 4)
    h2 = prefix_block_hashes([5, 6, 7, 8], 4)
    h3 = prefix_block_hashes([9, 10, 11, 12], 4)
    for seq, h in (("a", h1), ("b", h2), ("c", h3)):
        a.allocate(seq, 1)
        a.register_blocks(seq, h)
    a.free("a")                   # LRU
    time.sleep(0)                  # order is insertion, not wall clock
    a.free("b")                   # MRU
    keep = a.acquire_prefix("r", h3)   # c's block: refcounted, live
    assert len(keep) == 1
    # pool: 5 usable, 1 held by r (shared w/ nothing), a+b cached, 2 free
    got = a.allocate("x", 4)      # needs both free + both cached
    assert got is not None
    # the refcounted block was NOT evicted and survives intact
    assert a.blocks_of("r") == keep
    assert a.match_prefix(h3) == 1
    # the parked ones were deregistered when reclaimed
    assert a.match_prefix(h1) == 0 and a.match_prefix(h2) == 0


def test_cow_forks_shared_and_writes_private_in_place():
    a = _alloc()
    h = prefix_block_hashes(list(range(8)), 4)
    a.allocate("w", 2)
    a.register_blocks("w", h)
    a.acquire_prefix("r", h)
    before = a.blocks_of("r")
    fork = a.make_writable("r", 1)
    assert fork is not None
    src, dst = fork
    assert src == before[1] and dst not in before
    assert a.blocks_of("r")[1] == dst
    assert a.blocks_of("w") == before          # writer untouched
    assert a.shared_blocks == 1                # only block 0 still shared
    # private block: no fork needed
    assert a.make_writable("r", 1) is None
    a.free("r")
    a.free("w")
    # zero leaks: everything is free or parked-cached
    st = a.stats()
    assert st["blocks_used"] == 0
    assert st["blocks_free"] + st["blocks_cached"] == a.num_blocks - 1


def test_cow_raises_when_pool_exhausted():
    a = BlockAllocator(num_blocks=3, block_size=4, prefix_cache=True)
    h = prefix_block_hashes([1, 2, 3, 4], 4)
    a.allocate("w", 1)
    a.register_blocks("w", h)
    a.acquire_prefix("r", h)
    a.allocate("w", 1)            # last free block
    with pytest.raises(MemoryError):
        a.make_writable("r", 0)


def test_register_first_writer_wins():
    a = _alloc()
    h = prefix_block_hashes(list(range(4)), 4)
    a.allocate("w1", 1)
    a.register_blocks("w1", h)
    a.allocate("w2", 1)
    a.register_blocks("w2", h)    # duplicate content: ignored
    assert a.match_prefix(h) == 1
    assert a.acquire_prefix("r", h) == a.blocks_of("w1")


def test_aux_is_per_sequence_never_per_shared_block():
    """The sampling-seed checkpoint must survive refcounted sharing:
    two streams on the same blocks keep distinct aux, and freeing one
    never clears the other's."""
    a = _alloc()
    h = prefix_block_hashes(list(range(8)), 4)
    a.allocate("w", 2)
    a.register_blocks("w", h)
    a.acquire_prefix("r", h)
    a.set_aux("w", seed=111)
    a.set_aux("r", seed=222)
    assert a.get_aux("w")["seed"] == 111
    assert a.get_aux("r")["seed"] == 222
    a.free("w")
    assert a.get_aux("w") is None
    assert a.get_aux("r")["seed"] == 222       # untouched by the free


def test_can_admit_is_conservative():
    """Whenever can_admit says yes with an expected prefix hit, the
    acquire+allocate(+CoW) that follows immediately must succeed."""
    rs = np.random.RandomState(7)
    for trial in range(50):
        bs = int(rs.randint(2, 6))
        a = BlockAllocator(num_blocks=int(rs.randint(4, 12)),
                           block_size=bs, prefix_cache=True)
        base = [int(t) for t in rs.randint(0, 50, bs * 3)]
        h = prefix_block_hashes(base, bs)
        if a.allocate("w", 3) is not None:
            a.register_blocks("w", h)
            if rs.rand() < 0.5:
                a.free("w")
        plen = int(rs.randint(1, 4 * bs))
        prompt = base[:plen] if rs.rand() < 0.7 else \
            [int(t) for t in rs.randint(50, 99, plen)]
        hashes = prefix_block_hashes(prompt, bs)
        matched = a.match_prefix(hashes)
        start = min(matched * bs, plen - 1)
        cow = matched * bs > start
        if not a.can_admit(plen, cached_blocks=matched, needs_cow=cow):
            continue
        got = a.acquire_prefix("r", hashes)
        need = a.blocks_for_tokens(plen) - len(got)
        if need > 0:
            assert a.allocate("r", need) is not None, \
                f"trial {trial}: can_admit lied on allocate"
        if len(got) * bs > min(len(got) * bs, plen - 1):
            a.make_writable("r", len(got) - 1)  # must not raise


def test_property_random_interleavings_never_leak():
    """alloc -> share -> write-fork -> free in random order against a
    shadow model: every block is free, parked-cached, or owned by at
    least one live sequence; the three partitions always sum to the
    pool; eviction never reclaims a refcount>0 block; free stays
    idempotent."""
    rs = np.random.RandomState(0)
    for trial in range(20):
        bs = 4
        a = BlockAllocator(num_blocks=int(rs.randint(6, 20)),
                           block_size=bs, prefix_cache=True)
        prompts = {f"p{i}": [int(t) for t in
                             rs.randint(0, 30, int(rs.randint(4, 17)))]
                   for i in range(4)}
        live = {}
        for step in range(120):
            op = rs.randint(0, 5)
            if op == 0 and len(live) < 6:          # admit
                sid = f"s{trial}-{step}"
                tokens = prompts[f"p{rs.randint(0, 4)}"]
                hashes = prefix_block_hashes(tokens, bs)
                matched = a.match_prefix(hashes)
                start = min(matched * bs, len(tokens) - 1)
                cow = matched * bs > start
                if a.can_admit(len(tokens), cached_blocks=matched,
                               needs_cow=cow):
                    got = a.acquire_prefix(sid, hashes)
                    need = a.blocks_for_tokens(len(tokens)) - len(got)
                    if need > 0:
                        assert a.allocate(sid, need) is not None
                    if len(got) * bs > start and got:
                        a.make_writable(sid, len(got) - 1)
                    live[sid] = hashes
            elif op == 1 and live:                 # register
                sid = list(live)[rs.randint(0, len(live))]
                a.register_blocks(sid, live[sid])
            elif op == 2 and live:                 # free (idempotent)
                sid = list(live)[rs.randint(0, len(live))]
                a.free(sid)
                assert a.free(sid) == 0
                del live[sid]
            elif op == 3 and live:                 # decode growth
                sid = list(live)[rs.randint(0, len(live))]
                a.allocate(sid, 1)                 # may refuse: fine
            else:                                  # fork a random row
                if live:
                    sid = list(live)[rs.randint(0, len(live))]
                    blocks = a.blocks_of(sid)
                    if blocks:
                        try:
                            a.make_writable(
                                sid, int(rs.randint(0, len(blocks))))
                        except MemoryError:
                            pass
            # -- invariants, every step --
            st = a.stats()
            owned = set()
            for sid in live:
                blks = a.blocks_of(sid)
                assert 0 not in blks              # trash block reserved
                owned.update(blks)
            assert len(owned) == st["blocks_used"], \
                "shared blocks must be counted once"
            assert st["blocks_used"] + st["blocks_free"] + \
                st["blocks_cached"] == a.num_blocks - 1, "leak"
        for sid in list(live):
            a.free(sid)
        st = a.stats()
        assert st["blocks_used"] == 0 and st["live_sequences"] == 0


def test_drop_cached_reclaims_only_parked_blocks():
    a = _alloc()
    h = prefix_block_hashes(list(range(8)), 4)
    a.allocate("w", 2)
    a.register_blocks("w", h)
    a.acquire_prefix("r", h)
    a.free("w")                    # blocks stay refcounted via r
    assert a.drop_cached() == 0
    a.free("r")
    assert a.cached_blocks == 2
    assert a.drop_cached() == 2
    assert a.free_blocks == a.num_blocks - 1
    assert a.match_prefix(h) == 0


# ------------------------------------------ engine admission (fake model)

class _PrefixFakeModel(HostStepped):
    """Deterministic jax-free model with the PagedLlamaModel surface:
    next token is a pure function of (last token, position[, seed]) —
    so streams are byte-comparable across prefix-cache on/off and
    across preempt-resume, exactly like the real model's greedy/seeded
    decode. Tracks prefill token counts so tests can assert the
    cache-hit skip actually happened."""

    def __init__(self, num_slots=2, block_size=4, num_blocks=32,
                 max_blocks_per_seq=8, max_prompt_len=24,
                 prefill_chunk=0):
        self.num_slots = num_slots
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_context = block_size * max_blocks_per_seq
        self.max_prompt_len = max_prompt_len
        self.prefill_chunk_size = prefill_chunk
        self.suffix_chunk_size = prefill_chunk or block_size
        self.eos_id = None
        self.prefilled_tokens = 0
        self.copied = []          # (src, dst) CoW device copies

    @staticmethod
    def _next(tok, pos, temp=0.0, seed=0):
        if temp > 0:
            return (31 * int(seed) + 7 * int(pos) + 3 * int(tok)) % 97
        return (2 * int(tok) + int(pos)) % 97

    def prefill(self, prompt, row, sampling=None):
        self.prefilled_tokens += len(prompt)
        t, _, _, s = sampling or (0.0, 0, 1.0, 0)
        return self._next(prompt[-1], len(prompt), t, s)

    def prefill_chunk(self, chunk, start, total_len, row,
                      sampling=None):
        self.prefilled_tokens += len(chunk)
        t, _, _, s = sampling or (0.0, 0, 1.0, 0)
        return self._next(chunk[-1], total_len, t, s)

    def copy_block(self, src, dst):
        self.copied.append((int(src), int(dst)))

    def decode(self, tokens, block_tables, positions, sampling=None):
        if sampling is None:
            temps = seeds = [0] * len(tokens)
        else:
            temps, _, _, seeds = sampling
        return np.array([self._next(t, p + 1, tt, s)
                         for t, p, tt, s in zip(tokens, positions,
                                                temps, seeds)],
                        np.int32)


def _drain(handles, budget=60.0):
    deadline = time.monotonic() + budget
    while not all(h.done for h in handles):
        assert time.monotonic() < deadline, \
            [(h.outcome, h.error, h.tokens) for h in handles]
        time.sleep(0.002)
    return [list(h.tokens) for h in handles]


def _run_streams(prefix_cache, prompts, max_new=8, sampling=None,
                 sequential=True, **model_kw):
    m = _PrefixFakeModel(**model_kw)
    eng = LLMEngine(m, prefix_cache=prefix_cache).start()
    try:
        outs = []
        if sequential:
            for i, p in enumerate(prompts):
                h = eng.submit(p, max_new, rid=f"r{i}",
                               sampling=sampling)
                outs.extend(_drain([h]))
        else:
            hs = [eng.submit(p, max_new, rid=f"r{i}", sampling=sampling)
                  for i, p in enumerate(prompts)]
            outs = _drain(hs)
        return outs, eng.stats(), m
    finally:
        eng.stop()


SHARED = list(range(1, 13))       # 12 tokens = 3 full blocks, aligned


@pytest.mark.parametrize("chunk", [0, 4])
def test_engine_prefix_cache_streams_byte_identical(chunk):
    """The acceptance bit: greedy streams byte-identical with prefix
    caching on vs off, bucketed (chunk=0: suffix fed through the chunk
    path) AND chunked prefill — and the hit actually skipped prefill
    work."""
    prompts = [SHARED, SHARED + [77, 78], SHARED + [79], SHARED]
    off, _, m_off = _run_streams(False, prompts, prefill_chunk=chunk)
    on, st, m_on = _run_streams(True, prompts, prefill_chunk=chunk)
    assert on == off
    assert st["prefix_hit_tokens"] > 0
    assert st["prefix_miss_tokens"] < sum(len(p) for p in prompts)
    # cache hits -> strictly fewer prompt tokens through the device
    assert m_on.prefilled_tokens < m_off.prefilled_tokens
    # zero leaks: every stream done, blocks free or parked-cached
    assert st["blocks_used"] == 0
    assert st["blocks_free"] + st["blocks_cached"] == \
        st["num_blocks"] - 1


def test_engine_cow_fork_copies_device_block():
    """Two LIVE streams on the same aligned prompt: the second must
    fork the final shared block (ref 2) and the engine must issue the
    device copy BEFORE the recompute write. White-box manual ticks so
    both streams are provably concurrent."""
    m = _PrefixFakeModel()
    eng = LLMEngine(m, prefix_cache=True)   # not started: manual ticks
    h1 = eng.submit(SHARED, 10, rid="a")
    for _ in range(3):                      # a prefilled + decoding
        tick(eng)
    assert not h1.done and len(h1.tokens) >= 1
    h2 = eng.submit(SHARED, 4, rid="b")
    for _ in range(20):
        tick(eng)
        if h1.done and h2.done:
            break
    assert h1.outcome == "ok" and h2.outcome == "ok"
    assert len(m.copied) == 1     # exactly one CoW device copy
    st = eng.stats()
    assert st["prefix_hit_tokens"] == len(SHARED) - 1
    eng.stop()
    # the no-cache reference agrees byte for byte
    ref, _, _ = _run_streams(False, [SHARED, SHARED], max_new=10)
    assert list(h1.tokens) == ref[0]
    assert list(h2.tokens) == ref[1][:4]


def test_cow_without_copy_block_fails_stream_loudly():
    """A model that cannot execute the CoW device copy must end the
    forked stream with an ERROR — never silently decode over a block
    whose prefix bytes were never copied."""

    class _NoCopy(_PrefixFakeModel):
        copy_block = None

    eng = LLMEngine(_NoCopy(), prefix_cache=True)
    h1 = eng.submit(SHARED, 10, rid="a")
    for _ in range(3):
        tick(eng)
    assert not h1.done
    h2 = eng.submit(SHARED, 4, rid="b")   # aligned hit -> fork owed
    for _ in range(20):
        tick(eng)
        if h1.done and h2.done:
            break
    assert h1.outcome == "ok"             # the writer is untouched
    assert h2.outcome == "error" and "copy_block" in h2.error
    assert eng.allocator.stats()["blocks_used"] == 0 or not h1.done
    eng.stop()
    assert eng.allocator.stats()["blocks_used"] == 0


def test_seed_replay_across_preempt_resume_on_cache_hit():
    """Satellite regression: a SAMPLED stream preempted mid-decode and
    resumed onto a prefix-cache hit must replay byte-identically (the
    seed checkpoint is per-sequence aux, never per-shared-block)."""
    sampling = dict(temperature=0.9, top_k=8, top_p=0.95, seed=1234)
    # reference: roomy pool, no preemption, no cache
    ref, _, _ = _run_streams(False, [SHARED], max_new=12,
                             sampling=sampling, num_blocks=32)
    # tight pool + a competing stream forces preemption; prefix cache
    # on means the resume re-matches its own re-registered prefix
    m = _PrefixFakeModel(num_blocks=10, num_slots=2)
    eng = LLMEngine(m, prefix_cache=True).start()
    try:
        h1 = eng.submit(SHARED, 12, rid="victim", sampling=sampling)
        h2 = eng.submit(list(range(20, 28)), 16, rid="hog",
                        sampling=sampling)
        outs = _drain([h1, h2])
        assert h1.outcome == "ok", (h1.outcome, h1.error)
        assert outs[0] == ref[0]
        st = eng.stats()
        assert st["blocks_used"] == 0
    finally:
        eng.stop()


def test_resumed_stream_rematches_prefix_cache():
    """A preempted stream's freed prefix stays registered (parked on
    the cached-free LRU), so its own resume admission lands on a cache
    hit — the same property an HA failover resume leans on
    replica-side. White-box ticks: the hog is admitted FIRST, so KV
    pressure always evicts the younger victim."""
    from zoo_tpu.obs.metrics import counter
    m = _PrefixFakeModel(num_blocks=9, num_slots=2, max_prompt_len=40,
                         max_blocks_per_seq=12)
    eng = LLMEngine(m, prefix_cache=True)
    preempts0 = counter("zoo_llm_preempt_total").value
    hog = eng.submit(list(range(60, 68)), 20, rid="hog")
    victim = eng.submit(SHARED, 8, rid="victim")
    for _ in range(80):
        tick(eng)
        if hog.done and victim.done:
            break
    assert hog.outcome == "ok" and victim.outcome == "ok"
    assert counter("zoo_llm_preempt_total").value > preempts0, \
        "pool was not tight enough to force a preemption"
    st = eng.stats()
    assert st["prefix_hit_tokens"] > 0, \
        "the resume did not re-match the prefix cache"
    eng.stop()
    ref, _, _ = _run_streams(False, [SHARED], max_new=8,
                             num_blocks=32, max_prompt_len=40,
                             max_blocks_per_seq=12)
    assert list(victim.tokens) == ref[0]
