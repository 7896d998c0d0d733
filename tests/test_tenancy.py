"""Multi-tenant QoS (docs/multitenancy.md): tenant spec parsing,
token-bucket admission with per-tenant retry hints, the weighted-fair /
priority-class scheduler, per-tenant prefix-cache partitions, the HA
client's per-tenant A/B pins + rate backoff, and the per-tenant SLO
burn evaluator — all against jax-free fakes, so the file is tier-1
cheap.

The two acceptance bits asserted here:

* **isolation** — one greedy tenant's flood never inflates another
  tenant's retry hint, never evicts its cached prefixes while other
  supply exists, and never delays its client-side attempts;
* **bit-identity off** — with no tenant config (or all-unlabeled
  traffic) every admission, scheduling, and hashing decision is exactly
  the pre-tenancy one, asserted byte-for-byte against a disabled-QoS
  reference run.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from llm_tick import HostStepped, tick
from zoo_tpu.serving.llm.engine import (
    AdmissionError,
    LLMEngine,
    _tenant_preempted,
)
from zoo_tpu.serving.llm.kv_cache import (
    BlockAllocator,
    _cross_evictions,
    prefix_block_hashes,
)
from zoo_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    TenantRegistry,
    _TokenBucket,
    parse_tenant_spec,
    registry,
    reset_registry,
)


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    """Tenancy off by default for every test: no env config, and the
    process singleton dropped so it re-reads the (clean) environment.
    Tests that want QoS inject an explicit TenantRegistry."""
    for var in ("ZOO_TENANT_CONFIG", "ZOO_QOS", "ZOO_TENANT",
                "ZOO_TENANT_AB_PINS"):
        monkeypatch.delenv(var, raising=False)
    reset_registry(None)
    yield
    reset_registry(None)


# ----------------------------------------------------------- spec parsing

def test_parse_tenant_spec_fields():
    cfgs = parse_tenant_spec(
        "gold:weight=4,class=0,rate=50,burst=100,kv=64,slots=2;"
        "free:rate=5")
    g = cfgs["gold"]
    assert g.weight == 4.0 and g.priority == 0
    assert g.rate == 50.0 and g.burst == 100.0
    assert g.max_kv_blocks == 64 and g.max_slots == 2
    f = cfgs["free"]
    assert f.rate == 5.0
    assert f.weight == 1.0 and f.priority == 1          # defaults
    assert f.max_kv_blocks == 0 and f.max_slots == 0    # unlimited


def test_parse_tenant_spec_malformed_entries_skipped():
    cfgs = parse_tenant_spec(
        "good:rate=5;:rate=1;bad:nope=3;worse:rate=abc;also_good")
    # malformed entries warn-and-skip; the well-formed survive
    assert set(cfgs) == {"good", "also_good"}
    assert cfgs["good"].rate == 5.0
    assert cfgs["also_good"].rate == 0.0


def test_parse_tenant_spec_respects_defaults():
    cfgs = parse_tenant_spec("a;b:weight=9", default_weight=2.0,
                             default_class=3, default_rate=7.0)
    assert cfgs["a"].weight == 2.0 and cfgs["a"].priority == 3
    assert cfgs["a"].rate == 7.0
    assert cfgs["b"].weight == 9.0 and cfgs["b"].priority == 3


# ----------------------------------------------------------- token bucket

def test_token_bucket_admission_and_hint():
    b = _TokenBucket(rate=10.0, burst=2.0)
    assert b.try_acquire() and b.try_acquire()
    assert not b.try_acquire()               # burst spent
    hint = b.retry_after_ms()
    assert 0 < hint <= 200                   # ~100ms to refill 1 @ 10/s
    # unlimited bucket: always admits, zero hint
    u = _TokenBucket(rate=0.0)
    for _ in range(100):
        assert u.try_acquire()
    assert u.retry_after_ms() == 0


def test_token_bucket_refills():
    b = _TokenBucket(rate=200.0, burst=1.0)
    assert b.try_acquire() and not b.try_acquire()
    time.sleep(0.02)                         # 200/s -> ~4 tokens, cap 1
    assert b.try_acquire()


# ----------------------------------------------- registry enable / salt

def test_registry_disabled_without_config():
    assert registry().enabled is False       # clean env singleton
    assert TenantRegistry(spec="", qos=True).enabled is False
    assert TenantRegistry(spec="a:rate=1", qos=False).enabled is False
    assert TenantRegistry(spec="a:rate=1", qos=True).enabled is True


def test_registry_disabled_is_inert():
    reg = TenantRegistry(spec="", qos=True)
    assert reg.admit("anyone") == (True, 0)
    assert reg.salt("anyone") == b""
    # unknown tenants map to the default config
    assert reg.config("nobody").name == DEFAULT_TENANT


def test_registry_salt_partitions_prefix_hashes():
    reg = TenantRegistry(spec="a:rate=0;b:rate=0", qos=True)
    tokens = list(range(8))
    ha = prefix_block_hashes(tokens, 4, salt=reg.salt("a"))
    hb = prefix_block_hashes(tokens, 4, salt=reg.salt("b"))
    h0 = prefix_block_hashes(tokens, 4, salt=reg.salt(None))
    # distinct tenants can never collide; unlabeled == pre-tenancy
    assert ha != hb and ha != h0 and hb != h0
    assert h0 == prefix_block_hashes(tokens, 4)
    assert reg.salt(DEFAULT_TENANT) == b""


def test_retry_hint_is_per_tenant():
    """Satellite regression: a shed for tenant A is hinted from A's
    OWN bucket refill — B's hint stays its own (fundable) clock."""
    reg = TenantRegistry(spec="greedy:rate=0.001,burst=1;victim:rate=1000",
                         qos=True)
    ok, hint = reg.admit("greedy")
    assert ok and hint == 0
    ok, hint = reg.admit("greedy")           # burst of 1 is spent
    assert not ok and hint > 100_000         # ~1000s at 0.001/s
    # the flood changed NOTHING for the victim
    ok, hint = reg.admit("victim")
    assert ok and hint == 0
    assert reg.bucket("victim").retry_after_ms() == 1


# --------------------------------------------------- fake engine harness

class _FakeModel(HostStepped):
    """Deterministic jax-free model with the PagedLlamaModel surface
    (same contract as test_kv_prefix's): the next token is a pure
    function of (last token, position), so streams are byte-comparable
    across QoS on/off and across preempt-resume."""

    def __init__(self, num_slots=2, block_size=4, num_blocks=32,
                 max_blocks_per_seq=8, max_prompt_len=24):
        self.num_slots = num_slots
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_context = block_size * max_blocks_per_seq
        self.max_prompt_len = max_prompt_len
        self.prefill_chunk_size = 0
        self.suffix_chunk_size = block_size
        self.eos_id = None

    @staticmethod
    def _next(tok, pos):
        return (2 * int(tok) + int(pos)) % 97

    def prefill(self, prompt, row, sampling=None):
        return self._next(prompt[-1], len(prompt))

    def prefill_chunk(self, chunk, start, total_len, row, sampling=None):
        return self._next(chunk[-1], total_len)

    def copy_block(self, src, dst):
        pass

    def decode(self, tokens, block_tables, positions, sampling=None):
        return np.array([self._next(t, p + 1)
                         for t, p in zip(tokens, positions)], np.int32)


def _run_to_completion(eng, handles, ticks=400):
    for _ in range(ticks):
        tick(eng)
        if all(h.done for h in handles):
            return
    raise AssertionError(
        [(h.outcome, h.error, list(h.tokens)) for h in handles])


def _reference(prompt, max_new):
    """Solo greedy run on a roomy single-tenant engine — the byte
    oracle every QoS-scheduled stream must still match."""
    eng = LLMEngine(_FakeModel(num_blocks=64, num_slots=1),
                    tenancy=TenantRegistry(spec="", qos=False))
    h = eng.submit(prompt, max_new, rid="ref")
    _run_to_completion(eng, [h])
    assert h.outcome == "ok"
    return list(h.tokens)


# -------------------------------------------------- engine admission QoS

def test_engine_rate_shed_and_queue_hint_isolation():
    """Satellite regression at the engine door: the greedy tenant's
    rate shed carries ITS refill hint; a victim shed on queue depth a
    moment later gets the generic backlog hint, not greedy's."""
    reg = TenantRegistry(spec="greedy:rate=0.001,burst=1;victim:rate=0",
                         qos=True)
    eng = LLMEngine(_FakeModel(), max_waiting=2, tenancy=reg)
    eng.submit([1, 2, 3], 4, rid="g1", tenant="greedy")
    with pytest.raises(AdmissionError) as ei:
        eng.submit([1, 2, 3], 4, rid="g2", tenant="greedy")
    assert ei.value.reason == "rate"
    assert ei.value.tenant == "greedy"
    assert ei.value.retry_after_ms > 100_000
    # a duplicate id joins the live stream — never re-billed, so the
    # HA client's retries / failover resumes can't drain the bucket
    assert eng.submit([1, 2, 3], 4, rid="g1", tenant="greedy") is \
        eng.get("g1")
    # victim admits freely...
    eng.submit([4, 5, 6], 4, rid="v1", tenant="victim")
    # ...until the queue bound, where its hint is the generic backlog
    # figure — NOT the greedy tenant's ~1000s refill
    with pytest.raises(AdmissionError) as ei2:
        eng.submit([7, 8, 9], 4, rid="v2", tenant="victim")
    assert ei2.value.retry_after_ms == 200


def test_engine_unlabeled_traffic_bit_identical():
    """The acceptance bit: with tenancy disabled — or enabled with all
    traffic unlabeled — admission order is plain FIFO and every stream
    is byte-identical to the pre-tenancy engine."""
    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5], [2, 4]]

    def run(reg):
        eng = LLMEngine(_FakeModel(num_slots=1), tenancy=reg)
        hs = [eng.submit(p, 5, rid=f"r{i}")
              for i, p in enumerate(prompts)]
        _run_to_completion(eng, hs)
        return [list(h.tokens) for h in hs], \
            [h.admit_seq for h in hs], eng.stats()

    off_toks, off_order, off_st = run(TenantRegistry(spec="", qos=True))
    on_toks, on_order, on_st = run(
        TenantRegistry(spec="gold:weight=4,class=0,rate=50", qos=True))
    assert off_st["qos"] is False and on_st["qos"] is True
    assert off_order == [1, 2, 3, 4] == on_order     # FIFO both ways
    assert on_toks == off_toks
    assert off_toks == [_reference(p, 5) for p in prompts]


# ------------------------------------------------- weighted-fair picking

def test_pop_next_waiter_priority_then_deficit_then_fifo():
    reg = TenantRegistry(
        spec="paid:class=0,weight=1;a:weight=3;b:weight=1", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=4), tenancy=reg)
    ha1 = eng.submit([1, 2], 4, rid="a1", tenant="a")
    ha2 = eng.submit([1, 2], 4, rid="a2", tenant="a")
    hb = eng.submit([3, 4], 4, rid="b1", tenant="b")
    hp = eng.submit([5, 6], 4, rid="p1", tenant="paid")
    # lowest priority class wins outright, whatever the deficit says
    eng._tenant_served = {"paid": 10_000, "a": 0, "b": 0}
    with eng._lock:
        assert eng._pop_next_waiter() is hp
    # equal class: lowest served/weight — a at 29/3 beats b at 11/1
    eng._tenant_served = {"a": 29, "b": 11}
    with eng._lock:
        assert eng._pop_next_waiter() is ha1     # FIFO within tenant
    eng._tenant_served = {"a": 34, "b": 11}      # now a at 11.3 loses
    with eng._lock:
        assert eng._pop_next_waiter() is hb
    with eng._lock:
        assert eng._pop_next_waiter() is ha2
    with eng._lock:
        assert eng._pop_next_waiter() is None


def test_slot_quota_skips_tenant_without_blocking_queue():
    """A tenant at its slot cap is skipped IN PLACE: its second stream
    waits, but the tenant behind it admits immediately — no
    head-of-line blocking."""
    reg = TenantRegistry(spec="capped:slots=1;other:rate=0", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=2), tenancy=reg)
    c1 = eng.submit([1, 2, 3], 6, rid="c1", tenant="capped")
    c2 = eng.submit([1, 2, 3], 6, rid="c2", tenant="capped")
    o1 = eng.submit([4, 5, 6], 3, rid="o1", tenant="other")
    tick(eng)
    live = {s.handle.id for s in eng._slots if s.handle is not None}
    assert live == {"c1", "o1"}
    assert eng.stats()["tenants"]["capped"]["waiting"] == 1
    # the cap is a cap, not a wedge: c2 runs once c1's slot frees
    _run_to_completion(eng, [c1, c2, o1])
    assert [h.outcome for h in (c1, c2, o1)] == ["ok"] * 3
    assert c2.admit_seq > o1.admit_seq


def test_kv_quota_skips_tenant_without_blocking_queue():
    reg = TenantRegistry(spec="capped:kv=2;other:rate=0", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=2, block_size=4),
                    tenancy=reg)
    # 9 prompt tokens + 1 decode token -> 3 blocks > the kv=2 cap
    big = eng.submit(list(range(1, 10)), 2, rid="big", tenant="capped")
    ok = eng.submit([4, 5, 6], 3, rid="ok", tenant="other")
    small = eng.submit([7, 8], 3, rid="small", tenant="capped")
    for _ in range(200):
        tick(eng)
        if ok.done and small.done:
            break
    # over-quota stream parks; within-quota traffic flows around it
    assert ok.outcome == "ok" and small.outcome == "ok"
    assert not big.done
    assert eng.stats()["tenants"]["capped"]["waiting"] == 1


def test_weighted_fair_victim_jumps_greedy_backlog():
    """num_slots=1 and a greedy tenant's 3-deep backlog ahead of the
    victim in the queue: the deficit scheduler admits the victim right
    after greedy's FIRST stream (served/weight resets the race), and
    the victim's bytes are untouched by the reordering."""
    reg = TenantRegistry(spec="greedy:rate=0;victim:rate=0", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=1), tenancy=reg)
    gs = [eng.submit([10 + i, 11 + i], 4, rid=f"g{i}", tenant="greedy")
          for i in range(3)]
    v = eng.submit([1, 2, 3], 4, rid="v", tenant="victim")
    _run_to_completion(eng, gs + [v])
    assert v.admit_seq == 2                  # not 4 (the FIFO slot)
    assert list(v.tokens) == _reference([1, 2, 3], 4)


# -------------------------------------------------- class-based preempts

def test_class_preemption_resumes_victim_byte_identical():
    """Both slots held by best-effort streams; a paid (class 0) stream
    arrives. The youngest best-effort stream is preempted for it, then
    resumes via re-prefill — all three streams byte-identical to solo
    references, and the preemption is attributed to the tenant with
    reason=\"class\"."""
    reg = TenantRegistry(spec="paid:class=0;free:class=1", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=2, num_blocks=32),
                    tenancy=reg)
    before = _tenant_preempted.labels(tenant="free",
                                      reason="class").value
    f1 = eng.submit([1, 2, 3, 4], 8, rid="f1", tenant="free")
    f2 = eng.submit([5, 6, 7, 8], 8, rid="f2", tenant="free")
    for _ in range(3):
        tick(eng)
    assert not f1.done and not f2.done       # both decoding
    p = eng.submit([9, 10, 11], 6, rid="p", tenant="paid")
    tick(eng)                               # preempts f2 at admit end
    tick(eng)                               # the freed slot admits p
    # the YOUNGEST best-effort stream lost its slot to the paid class
    live = {s.handle.id for s in eng._slots if s.handle is not None}
    assert live == {"p", "f1"}
    assert _tenant_preempted.labels(tenant="free",
                                    reason="class").value == before + 1
    _run_to_completion(eng, [f1, f2, p])
    assert [h.outcome for h in (f1, f2, p)] == ["ok"] * 3
    assert f2.preempts >= 1
    assert list(f1.tokens) == _reference([1, 2, 3, 4], 8)
    assert list(f2.tokens) == _reference([5, 6, 7, 8], 8)
    assert list(p.tokens) == _reference([9, 10, 11], 6)


def test_class_preemption_never_evicts_a_peer():
    """Single class: a full house of equals is NEVER churned by a
    same-class waiter — preemption only crosses class boundaries."""
    reg = TenantRegistry(spec="a:class=1;b:class=1", qos=True)
    eng = LLMEngine(_FakeModel(num_slots=1, num_blocks=32),
                    tenancy=reg)
    a = eng.submit([1, 2, 3], 6, rid="a", tenant="a")
    for _ in range(2):
        tick(eng)
    b = eng.submit([4, 5, 6], 6, rid="b", tenant="b")
    tick(eng)
    assert eng._slots[0].handle is not None
    assert eng._slots[0].handle.id == "a"    # undisturbed
    _run_to_completion(eng, [a, b])
    assert a.preempts == 0


# ------------------------------------------- prefix-cache partitioning

def test_partition_eviction_prefers_own_then_shared():
    """A greedy tenant under KV pressure evicts its OWN parked blocks
    first, then the shared partition — the victim's cached prefix
    survives until there is literally nothing else, and the final
    cross-tenant resort is counted."""
    a = BlockAllocator(num_blocks=10, block_size=4, prefix_cache=True)
    hv = prefix_block_hashes(list(range(12)), 4,
                             salt=b"tenant:victim")
    hg = prefix_block_hashes(list(range(100, 116)), 4,
                             salt=b"tenant:greedy")
    a.set_tenant("v1", "victim")
    assert a.allocate("v1", 3) is not None
    a.register_blocks("v1", hv)
    a.free("v1")                             # 3 parked in victim's part
    a.set_tenant("g1", "greedy")
    assert a.allocate("g1", 4) is not None
    a.register_blocks("g1", hg)
    a.free("g1")                             # 4 parked in greedy's part
    cross0 = _cross_evictions.labels(tenant="greedy").value
    # greedy churn: needs 5 = 2 free + 3 evictions, all from its OWN
    # partition even though the victim's blocks are older (global LRU)
    a.set_tenant("g2", "greedy")
    assert a.allocate("g2", 5) is not None
    assert a.match_prefix(hv) == 3           # victim's cache intact
    assert _cross_evictions.labels(tenant="greedy").value == cross0
    # exhaustion: own partition has 1 left, shared has none -> the
    # remaining 2 come cross-tenant, and the counter says so
    a.set_tenant("g3", "greedy")
    assert a.allocate("g3", 3) is not None
    assert _cross_evictions.labels(tenant="greedy").value == cross0 + 2
    assert a.match_prefix(hv) < 3


def test_untagged_eviction_is_plain_lru():
    """No tenant tags: eviction pops the global LRU head, exactly the
    pre-tenancy order (the bit-identity contract for the off path)."""
    a = BlockAllocator(num_blocks=4, block_size=4, prefix_cache=True)
    h1 = prefix_block_hashes([1, 2, 3, 4], 4)
    h2 = prefix_block_hashes([5, 6, 7, 8], 4)
    for seq, h in (("x", h1), ("y", h2)):
        a.allocate(seq, 1)
        a.register_blocks(seq, h)
    a.free("x")                              # LRU
    a.free("y")                              # MRU
    a.allocate("z", 2)                       # 1 free + 1 eviction
    assert a.match_prefix(h1) == 0           # the LRU one went
    assert a.match_prefix(h2) == 1


def test_partition_property_random_churn_matches_shadow():
    """Random tagged alloc/park/grow churn vs a shadow model of the
    partitioned LRU: per-partition cached counts and the cross-tenant
    eviction counters track exactly, and the pool never leaks."""
    rs = np.random.RandomState(42)
    tenants = ["", "a", "b"]
    for trial in range(15):
        nb = int(rs.randint(8, 24))
        a = BlockAllocator(num_blocks=nb, block_size=4,
                           prefix_cache=True)
        # shadow: the _cached LRU as an ordered list of partition tags
        shadow_lru = []
        shadow_free = nb - 1
        shadow_cross = {t: 0 for t in tenants}
        cross0 = {t: _cross_evictions.labels(tenant=t).value
                  for t in ("a", "b")}
        live = {}                            # seq -> (tenant, nblocks)
        serial = 0

        def shadow_evict(t):
            idx = None
            if t:
                for i, tag in enumerate(shadow_lru):
                    if tag == t:
                        idx = i
                        break
                if idx is None:
                    for i, tag in enumerate(shadow_lru):
                        if not tag:
                            idx = i
                            break
                if idx is None:
                    idx = 0
                    shadow_cross[t] += 1
            else:
                idx = 0
            shadow_lru.pop(idx)

        def shadow_take(n, t):
            nonlocal shadow_free
            while shadow_free < n and shadow_lru:
                shadow_evict(t)
                shadow_free += 1
            if shadow_free < n:
                return False
            shadow_free -= n
            return True

        for _ in range(80):
            op = rs.randint(0, 3)
            if op == 0 and len(live) < 5:            # new tagged seq
                t = tenants[rs.randint(0, 3)]
                n = int(rs.randint(1, 4))
                sid = f"s{trial}-{serial}"
                serial += 1
                a.set_tenant(sid, t)
                got = a.allocate(sid, n)
                ok = shadow_take(n, t)
                assert (got is not None) == ok
                if got is not None:
                    live[sid] = (t, n)
            elif op == 1 and live:                   # register + park
                sid = list(live)[rs.randint(0, len(live))]
                t, n = live.pop(sid)
                # unique per-seq tokens: hashes never collide/share
                tokens = [1000 * serial + i for i in range(4 * n)]
                serial += 1
                a.register_blocks(
                    sid, prefix_block_hashes(
                        tokens, 4, salt=b"t:" + t.encode()))
                a.free(sid)
                shadow_lru.extend([t] * n)
            elif op == 2 and live:                   # decode growth
                sid = list(live)[rs.randint(0, len(live))]
                t, n = live[sid]
                if a.allocate(sid, 1) is not None:
                    live[sid] = (t, n + 1)
                    assert shadow_take(1, t)
                else:
                    assert not shadow_take(1, t)
            # -- invariants, every step --
            st = a.stats()
            assert st["blocks_free"] == shadow_free
            assert st["blocks_cached"] == len(shadow_lru)
            assert st["blocks_used"] + st["blocks_free"] + \
                st["blocks_cached"] == nb - 1, "leak"
            by_part = {}
            for blk, tag in a._part_of.items():
                by_part[tag] = by_part.get(tag, 0) + 1
            want = {}
            for tag in shadow_lru:
                if tag:
                    want[tag] = want.get(tag, 0) + 1
            assert by_part == want
            for t in ("a", "b"):
                assert _cross_evictions.labels(tenant=t).value == \
                    cross0[t] + shadow_cross[t]


# ------------------------------------------------ HA client tenant bits

def _client(**kw):
    from zoo_tpu.serving.ha_client import HAServingClient
    return HAServingClient([("127.0.0.1", 1)], deadline_ms=0,
                           hedge=False, **kw)


def test_parse_tenant_pins():
    from zoo_tpu.serving.ha_client import _parse_tenant_pins
    assert _parse_tenant_pins("gold=v2, free=v1") == \
        {"gold": "v2", "free": "v1"}
    assert _parse_tenant_pins("") == {}
    with pytest.raises(ValueError):
        _parse_tenant_pins("gold")
    with pytest.raises(ValueError):
        _parse_tenant_pins("=v2")


def test_client_tenant_pin_overrides_split():
    c = _client(tenant_pins={"gold": "v2"})
    c.pin_version("v1")                      # 100% fractional split
    assert c._draw_version("free") == "v1"
    assert c._draw_version(None) == "v1"
    assert c._draw_version("gold") == "v2"   # pin beats the split
    c.pin_version("v3", tenant="gold")
    assert c._draw_version("gold") == "v3"
    c.pin_version(None, tenant="gold")       # unpin -> back to split
    assert c._draw_version("gold") == "v1"


def test_client_tenant_backoff_is_isolated_and_capped():
    c = _client()
    # only a RATE shed arms the clock — queue sheds fail over instead
    c._note_tenant_backoff("victim", {"retry_after_ms": 5000})
    c._note_tenant_backoff("victim", {"reason": "queue_full",
                                      "retry_after_ms": 5000})
    assert "victim" not in c._tenant_retry_at
    c._note_tenant_backoff("greedy", {"reason": "rate",
                                      "retry_after_ms": 60_000})
    until = c._tenant_retry_at["greedy"]
    # capped by ZOO_TENANT_BACKOFF_CAP_MS (default 2000ms), not 60s
    assert 0 < until - time.monotonic() <= 2.05
    # the victim's attempts are never delayed by greedy's clock
    t0 = time.monotonic()
    c._tenant_backoff_wait("victim", None)
    c._tenant_backoff_wait(None, None)
    assert time.monotonic() - t0 < 0.05


def test_client_tenant_backoff_waits_out_the_hint():
    c = _client()
    c._note_tenant_backoff("g", {"reason": "rate",
                                 "retry_after_ms": 120})
    t0 = time.monotonic()
    c._tenant_backoff_wait("g", None)
    waited = time.monotonic() - t0
    assert 0.08 <= waited <= 1.0
    # the clock is spent: a second wait is a no-op
    t0 = time.monotonic()
    c._tenant_backoff_wait("g", None)
    assert time.monotonic() - t0 < 0.05


# ------------------------------------------------- per-tenant SLO burn

def test_slo_per_tenant_burn_and_breach(monkeypatch):
    monkeypatch.setenv("ZOO_SLO_TENANT_SHED_RATE", "0.1")
    import zoo_tpu.obs.slo as slo_mod
    from zoo_tpu.obs.slo import SLOWatchdog
    # evaluate() publishes the process's last verdict; the breach made
    # here must not outlive the test (the promotion gate of a later
    # test file on the same worker reads it as a burning fleet)
    monkeypatch.setattr(slo_mod, "_last_status", slo_mod._last_status)
    from zoo_tpu.obs.metrics import counter, gauge
    shed = counter("zoo_tenant_shed_total",
                   "Requests shed per tenant",
                   labels=("tenant", "reason"))
    adm = counter("zoo_tenant_admitted_total",
                  "Requests admitted per tenant", labels=("tenant",))
    w = SLOWatchdog(rules=[])
    w.evaluate()                             # baseline snapshot
    for _ in range(5):
        shed.labels(tenant="slo-greedy", reason="rate").inc()
        adm.labels(tenant="slo-greedy").inc()
    for _ in range(10):
        adm.labels(tenant="slo-victim").inc()
    status = w.evaluate()
    g = status["tenants"]["slo-greedy"]
    assert g["breached"] and g["shed_rate"] == pytest.approx(0.5)
    assert g["burn_rate"] == pytest.approx(5.0)
    v = status["tenants"]["slo-victim"]
    assert not v["breached"] and v["shed_rate"] == 0.0
    assert "tenant_shed_rate[slo-greedy]" in status["breaches"]
    assert status["ok"] is False
    burn = gauge("zoo_tenant_burn_rate",
                 "Per-tenant burn rate", labels=("tenant", "slo"))
    assert burn.labels(tenant="slo-greedy",
                       slo="shed_rate").value == pytest.approx(5.0)


def test_slo_tenant_objective_arms_the_watchdog(monkeypatch):
    from zoo_tpu.obs.slo import SLOWatchdog
    assert SLOWatchdog(rules=[]).start()._thread is None
    monkeypatch.setenv("ZOO_SLO_TENANT_SHED_RATE", "0.05")
    w = SLOWatchdog(rules=[]).start()
    try:
        assert w._thread is not None
    finally:
        w.stop()


# ------------------------------------------------------------ chaos smoke

@pytest.mark.chaos
def test_check_tenancy_script_runs():
    """The adversarial-mix smoke (scripts/check_tenancy.py): a greedy
    tenant floods a 3-replica group with a mid-storm SIGKILL while a
    paced victim streams on — victims byte-identical, zero victim
    sheds, the greedy tenant visibly throttled — as a subprocess, the
    operator invocation."""
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "check_tenancy.py"),
         "--duration", "8"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TENANCY OK" in proc.stdout
