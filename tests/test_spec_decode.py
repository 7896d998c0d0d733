"""Speculative decoding (docs/llm_serving.md): the n-gram
prompt-lookup drafter, the multi-token paged VERIFY executable, and the
engine's accept/rollback scheduling.

The load-bearing contract is the classic spec-decode guarantee made
byte-exact: every emitted token is the CANONICAL per-position sample
(same logits row, same stateless PRNG key non-speculative decode would
use), so a speculative stream is byte-identical to plain decode —
greedy and seeded sampling alike, across preemption, chunked prefill,
prefix caching, int8 KV, and tensor parallelism. Drafter/scheduler
tests run jax-free against a deterministic fake model; the interaction
matrix runs the real ``PagedLlamaModel``. The 2-replica SIGKILL smoke
(scripts/check_spec_decode.py) runs as a subprocess under the ``perf``
marker like its siblings.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from llm_tick import tick
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.speculative import (
    PromptLookup,
    accept_length,
    propose_tokens,
)


# ------------------------------------------------------------- drafter

class TestDrafter:
    def test_periodic_prompt_proposes_continuation(self):
        # suffix [3,1,2] re-occurs; the period-3 cycle extrapolates
        assert list(propose_tokens([1, 2, 3, 1, 2, 3, 1, 2], 7)) == \
            [3, 1, 2, 3, 1, 2, 3]

    def test_non_repeating_context_proposes_nothing(self):
        assert propose_tokens([5, 6, 7, 8], 4).size == 0

    def test_longest_ngram_wins(self):
        # 1-gram [2] matches at idx 1 (cont 9) but the 2-gram [5, 2]
        # match at idx 3 is more reliable and must win
        ctx = [7, 2, 9, 5, 2, 8, 5, 2]
        assert list(propose_tokens(ctx, 1, ngram_max=2)) == [8]

    def test_k_zero_and_tiny_context(self):
        assert propose_tokens([1, 2, 1, 2], 0).size == 0
        assert propose_tokens([1], 4).size == 0
        assert propose_tokens([], 4).size == 0

    def test_accept_length(self):
        assert accept_length([3, 4, 9], [3, 4, 1, 2]) == 2
        assert accept_length([], [7]) == 0
        assert accept_length([5], [5, 6]) == 1
        assert accept_length([9], [5, 6]) == 0

    def test_prompt_lookup_matches_reference_drafter(self):
        """The incremental index and the rescanning reference must be
        behaviorally identical — random contexts, random splits."""
        rs = np.random.RandomState(7)
        for _ in range(300):
            L = rs.randint(2, 40)
            ctx = rs.randint(0, 5, (L,)).astype(np.int32)
            k = int(rs.randint(1, 8))
            n = int(rs.randint(1, 5))
            split = int(rs.randint(1, L)) if L > 1 else 1
            lk = PromptLookup(ctx[:split], n)
            lk.extend(ctx[split:])
            assert list(lk.propose(k)) == \
                list(propose_tokens(ctx, k, n)), (ctx, k, n, split)


# ------------------------------------------- scheduler over a fake model

class _SpecFake:
    """Deterministic jax-free model: the canonical next token after x
    is (x + 1) % mod, for decode AND verify alike — so a cyclic prompt
    0..mod-1 makes prompt-lookup drafts fully acceptable, and a
    non-repeating prompt yields no proposals."""

    def __init__(self, num_slots=2, spec_k=3, mod=4):
        self.num_slots, self.spec_k, self.mod = num_slots, spec_k, mod
        self.block_size, self.num_blocks = 4, 64
        self.max_blocks_per_seq, self.max_prompt_len = 8, 30
        self.max_context, self.prefill_chunk_size = 32, 0
        self.eos_id = None
        self.suffix_chunk_size = 4
        self.verify_calls = 0
        self.verify_widths = set()

    def prefill(self, prompt, row, sampling=None):
        return (int(prompt[-1]) + 1) % self.mod

    def decode_step(self, prev, host, use, tables, pos, lanes):
        return (np.where(np.asarray(use), host, prev if prev
                         is not None else 0) + 1) % self.mod

    def verify_step(self, tokens, tables, positions, lanes):
        tokens = np.asarray(tokens)
        assert tokens.shape == (self.num_slots, self.spec_k + 1), \
            tokens.shape
        self.verify_calls += 1
        self.verify_widths.add(tokens.shape)
        return (tokens + 1) % self.mod

    def read_tokens(self, batch):
        return np.asarray(batch)


def _drain(handles, budget=60.0):
    end = time.monotonic() + budget
    while not all(h.done for h in handles):
        assert time.monotonic() < end, \
            [(h.outcome, h.error) for h in handles]
        time.sleep(0.002)
    assert all(h.outcome == "ok" for h in handles), \
        [(h.outcome, h.error) for h in handles]
    return [list(h.tokens) for h in handles]


CYCLIC = np.array([0, 1, 2, 3, 0, 1, 2, 3], np.int32)
NOISE = np.array([9, 17, 23], np.int32)


class TestEngineSpecFake:
    def test_spec_stream_identical_to_plain(self):
        plain = LLMEngine(_SpecFake(spec_k=0)).start()
        try:
            ref = _drain([plain.submit(CYCLIC, 10, rid="p")])
        finally:
            # a scheduler left running idles in 5 ms spans for the rest
            # of the worker's life, and whatever counts the ring's
            # spans next (tests/test_obs_spans.py) counts them too
            plain.stop()
        fake = _SpecFake(spec_k=3)
        eng = LLMEngine(fake).start()
        try:
            got = _drain([eng.submit(CYCLIC, 10, rid="s")])
            assert got == ref
            st = eng.stats()
            # the cyclic prompt drafts perfectly: every proposal is
            # the canonical (x+1)%4 continuation
            assert st["spec_accepted_tokens"] > 0
            assert st["spec_accept_rate"] == 1.0
            assert fake.verify_calls < 10, (
                "full acceptance should need far fewer passes than "
                "tokens")
        finally:
            eng.stop()

    def test_hand_stepped_verify_pass_matches_the_running_engine(self):
        """``tick(eng)`` on a ``spec_k > 0`` engine is one pass that
        dispatches a VERIFY batch and its landing: the stream it emits
        is the running engine's, token for token."""
        eng = LLMEngine(_SpecFake(spec_k=3)).start()
        try:
            running = _drain([eng.submit(CYCLIC, 10, rid="r"),
                              eng.submit(NOISE, 6, rid="n")])
        finally:
            eng.stop()
        fake = _SpecFake(spec_k=3)
        eng = LLMEngine(fake)
        hs = [eng.submit(CYCLIC, 10, rid="r"),
              eng.submit(NOISE, 6, rid="n")]
        assert tick(eng), "the first pass admits, prefills and verifies"
        assert fake.verify_calls == 1
        assert eng.stats()["decode_steps"] == 1      # landed inline
        assert not any(s.spec_inflight for s in eng._slots)
        for _ in range(50):
            if all(h.done for h in hs):
                break
            tick(eng)
        assert [list(h.tokens) for h in hs] == running
        assert eng.stats()["spec_accepted_tokens"] > 0
        assert eng.allocator.used_blocks == 0
        eng.stop()

    def test_acyclic_prompt_degenerates_to_plain_decode(self):
        fake = _SpecFake(spec_k=3, mod=50)
        eng = LLMEngine(fake).start()
        try:
            _drain([eng.submit(NOISE, 6, rid="n")])
            st = eng.stats()
            assert st["spec_proposed_tokens"] == 0
            assert st["spec_draft_hit_rate"] < 1.0
        finally:
            eng.stop()

    def test_fixed_verify_census_shape(self):
        """Every verify batch is the ONE (slots, k+1) shape regardless
        of how many lanes drafted — the compile-census contract."""
        fake = _SpecFake(num_slots=2, spec_k=3)
        eng = LLMEngine(fake).start()
        try:
            _drain([eng.submit(CYCLIC, 8, rid="a"),
                    eng.submit(NOISE, 4, rid="b")])
            assert fake.verify_widths == {(2, 4)}
        finally:
            eng.stop()

    def test_per_request_spec_cap(self):
        fake = _SpecFake(spec_k=3)
        eng = LLMEngine(fake).start()
        try:
            ref = _drain([eng.submit(CYCLIC, 8, rid="full")])
            got = _drain([eng.submit(CYCLIC, 8, rid="capped",
                                     spec_k=0)])
            assert got == ref  # identity holds with drafting off
        finally:
            eng.stop()
        with pytest.raises(ValueError):
            LLMEngine(_SpecFake()).submit(CYCLIC, 4, spec_k=-1)

    def test_engine_budget_clamped_to_model_width(self):
        """An engine cannot speculate wider than the model's fixed
        verify executable; spec_k=0 disables cleanly (the A/B rig)."""
        assert LLMEngine(_SpecFake(spec_k=3), spec_k=99).spec_k == 3
        eng = LLMEngine(_SpecFake(spec_k=3), spec_k=0)
        assert eng.spec_k == 0 and not eng._spec

    def test_eos_inside_accepted_run_stops_stream(self):
        fake = _SpecFake(spec_k=3)
        fake.eos_id = 2
        eng = LLMEngine(fake).start()
        try:
            toks = _drain([eng.submit(CYCLIC, 10, rid="e")])[0]
            assert toks[-1] == 2 and 2 not in toks[:-1]
            assert eng.stats()["blocks_used"] == 0
        finally:
            eng.stop()

    def test_max_new_respected_mid_batch(self):
        """A verify pass can accept past max_new; emission must stop
        exactly at the budget."""
        fake = _SpecFake(spec_k=3)
        eng = LLMEngine(fake).start()
        try:
            for n in (1, 2, 5):
                toks = _drain([eng.submit(CYCLIC, n, rid=f"m{n}")])[0]
                assert len(toks) == n
            assert eng.stats()["blocks_used"] == 0
        finally:
            eng.stop()


# ---------------------------------------------------- allocator support

class TestGrowTo:
    def test_grow_to_funds_without_preemption(self):
        from zoo_tpu.serving.llm.kv_cache import BlockAllocator
        alloc = BlockAllocator(num_blocks=8, block_size=4)
        alloc.allocate("s", 1)
        assert alloc.grow_to("s", 10) == 12      # 3 blocks x 4
        assert alloc.grow_to("s", 100) == 28     # pool-capped: 7 blocks
        assert alloc.free_blocks == 0
        assert alloc.grow_to("ghost", 8) == 0    # unknown sequence
        alloc.free("s")
        assert alloc.free_blocks == 7

    def test_grow_to_never_steals_referenced_blocks(self):
        from zoo_tpu.serving.llm.kv_cache import BlockAllocator
        alloc = BlockAllocator(num_blocks=6, block_size=4)
        alloc.allocate("a", 3)
        alloc.allocate("b", 1)
        assert alloc.grow_to("b", 40) == 8       # only the free block
        assert len(alloc.blocks_of("a")) == 3


# -------------------------------------------------- real-model identity

@pytest.fixture(scope="module")
def tiny_cfg():
    from zoo_tpu.models.llm.llama import tiny_llama_config
    return tiny_llama_config(vocab=64)


def _generate(model, prompts, n, engine_kw=None, sampling=None,
              budget=300.0):
    eng = LLMEngine(model, **(engine_kw or {})).start()
    try:
        hs = [eng.submit(p, n, rid=f"g{i}",
                         sampling=(sampling[i] if sampling else None))
              for i, p in enumerate(prompts)]
        toks = _drain(hs, budget=budget)
        return toks, eng.stats()
    finally:
        eng.stop()


class TestRealModelMatrix:
    """The interaction matrix: speculative decode x prefix-cache x
    int8 KV x chunked prefill, all token-identical to the f32 dense
    non-speculative reference (the tp=2 leg runs under the multichip
    marker below)."""

    PROMPTS = None
    SAMPLING = None
    REF = None

    @pytest.fixture(scope="class")
    def reference(self, tiny_cfg):
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        cls = TestRealModelMatrix
        if cls.REF is None:
            rs = np.random.RandomState(3)
            motif = rs.randint(0, 64, (5,))
            cls.PROMPTS = [
                np.tile(motif, 4).astype(np.int32),       # repetitive
                rs.randint(0, 64, (9,)).astype(np.int32),  # noise
                np.tile(motif, 4).astype(np.int32),       # shared prefix
            ]
            cls.SAMPLING = [None,
                            dict(temperature=0.9, top_k=16,
                                 top_p=0.95, seed=11),
                            dict(temperature=1.1, seed=5)]
            base = PagedLlamaModel(
                tiny_cfg, seed=0, num_slots=2, block_size=4,
                num_blocks=48, max_blocks_per_seq=10,
                prefill_buckets=(8, 32))
            assert base.kv_cache_dtype == "f32"
            cls.REF, st = _generate(base, cls.PROMPTS, 12,
                                    sampling=cls.SAMPLING)
            assert st["spec_k"] == 0
        return cls.REF

    @pytest.mark.parametrize("variant", [
        "spec", "spec_chunk", "spec_int8", "spec_prefix",
        "spec_int8_prefix_chunk"])
    def test_variant_token_identical(self, tiny_cfg, reference,
                                     variant):
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        kw = dict(seed=0, num_slots=2, block_size=4, num_blocks=48,
                  max_blocks_per_seq=10, prefill_buckets=(8, 32),
                  spec_k=3)
        ekw = {}
        if "chunk" in variant:
            kw["prefill_chunk"] = 4
        if "int8" in variant:
            kw["kv_dtype"] = "int8"
        if "prefix" in variant:
            ekw["prefix_cache"] = True
        model = PagedLlamaModel(tiny_cfg, **kw)
        got, st = _generate(model, self.PROMPTS, 12, engine_kw=ekw,
                            sampling=self.SAMPLING)
        assert got == reference, f"{variant} diverged"
        c = st["compiles"]
        assert c["verify"] == 1 and c["decode"] == 0, c
        assert c["prefill_chunk"] <= 1, c
        assert st["blocks_used"] == 0, st
        if variant == "spec":
            # compiled-artifact contracts on the ONE verify
            # executable: donated cache aliased, outfeed stays
            # slots x (k+1) int32 rows, never logits (zoo-lint
            # HLO-DONATION / HLO-HOST-TRANSFER); one variant is
            # enough — the census asserts the others share it
            from zoo_tpu.analysis.hlo import assert_llm_executable
            assert_llm_executable(model, "verify")
        assert st["spec_accepted_tokens"] > 0, (
            "the repetitive streams should accept some drafts")
        if "prefix" in variant:
            assert st["prefix_hit_tokens"] > 0, st

    def test_seeded_sampling_deterministic_across_runs(self, tiny_cfg,
                                                       reference):
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        model = PagedLlamaModel(
            tiny_cfg, seed=0, num_slots=2, block_size=4,
            num_blocks=48, max_blocks_per_seq=10,
            prefill_buckets=(8, 32), spec_k=3)
        a, _ = _generate(model, self.PROMPTS, 12,
                         sampling=self.SAMPLING)
        b, _ = _generate(model, self.PROMPTS, 12,
                         sampling=self.SAMPLING)
        assert a == b == reference

    def test_spec_across_real_preemption(self, tiny_cfg):
        """A pool sized to force eviction mid-stream: the speculative
        engine preempts, resumes by re-prefill, and stays
        byte-identical to the non-speculative reference."""
        from zoo_tpu.models.llm.llama import LlamaConfig
        from zoo_tpu.obs.metrics import counter
        from zoo_tpu.serving.llm.model import PagedLlamaModel

        cfg = LlamaConfig(vocab=64, hidden=32, n_block=2, n_head=4,
                          n_kv_head=2, intermediate=64,
                          rope_theta=10000.0)
        kw = dict(seed=0, num_slots=2, block_size=4, num_blocks=8,
                  max_blocks_per_seq=8, prefill_buckets=(8, 32))
        prompts = [np.arange(2, 8) % 64, np.arange(3, 9) % 64]
        ref, _ = _generate(PagedLlamaModel(cfg, **kw), prompts, 14)
        p0 = counter("zoo_llm_preempt_total").value
        got, st = _generate(PagedLlamaModel(cfg, spec_k=3, **kw),
                            prompts, 14)
        assert counter("zoo_llm_preempt_total").value > p0, \
            "pool sizing failed to force a preemption"
        assert got == ref
        assert st["blocks_used"] == 0

    def test_verify_step_enforces_census_shape(self, tiny_cfg):
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        m = PagedLlamaModel(tiny_cfg, seed=0, num_slots=2,
                            block_size=4, num_blocks=16,
                            max_blocks_per_seq=4,
                            prefill_buckets=(8,), spec_k=2)
        lanes = (np.zeros(2, np.float32), np.zeros(2, np.int32),
                 np.ones(2, np.float32), np.zeros(2, np.uint32))
        with pytest.raises(ValueError, match="census"):
            m.verify_step(np.zeros((2, 5), np.int32),
                          np.zeros((2, 4), np.int32),
                          np.zeros(2, np.int32), lanes)
        m0 = PagedLlamaModel(tiny_cfg, seed=0, num_slots=2,
                             block_size=4, num_blocks=16,
                             max_blocks_per_seq=4,
                             prefill_buckets=(8,))
        with pytest.raises(RuntimeError, match="spec_k"):
            m0.verify_step(np.zeros((2, 1), np.int32),
                           np.zeros((2, 4), np.int32),
                           np.zeros(2, np.int32), lanes)


# --------------------------------------------------------- spec grammar

class TestSpecGrammar:
    def test_parse_spec_knobs(self):
        from zoo_tpu.serving.llm.spec import parse_llm_spec
        _, eng = parse_llm_spec(
            "llama:tiny:spec_k=4,spec_ngram=2,prefill_impl=dense")
        assert eng["spec_k"] == 4 and eng["spec_ngram"] == 2
        assert eng["prefill_impl"] == "dense"

    def test_build_engine_spec_on_off(self):
        from zoo_tpu.serving.llm.spec import build_llm_engine
        e = build_llm_engine(
            "llama:tiny:spec_k=3,slots=2,block=4,blocks=16,tables=4,"
            "buckets=8", start=False)
        assert e.spec_k == 3 and e.model.spec_k == 3 and e._spec
        e2 = build_llm_engine(
            "llama:tiny:slots=2,block=4,blocks=16,tables=4,buckets=8",
            start=False)
        assert e2.spec_k == 0 and not e2._spec

    def test_env_spec_k(self, monkeypatch):
        from zoo_tpu.models.llm.llama import tiny_llama_config
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        monkeypatch.setenv("ZOO_LLM_SPEC_K", "2")
        m = PagedLlamaModel(tiny_llama_config(), num_blocks=8,
                            prefill_buckets=(8,))
        assert m.spec_k == 2

    def test_negative_spec_k_refused(self):
        from zoo_tpu.models.llm.llama import tiny_llama_config
        from zoo_tpu.serving.llm.model import PagedLlamaModel
        with pytest.raises(ValueError, match="spec_k"):
            PagedLlamaModel(tiny_llama_config(), num_blocks=8,
                            prefill_buckets=(8,), spec_k=-1)


# --------------------------------------------------- tensor parallelism

@pytest.mark.multichip
def test_spec_tp2_token_identical():
    """tp=2 verify (docs/multichip.md): the verify executable jitted
    with explicit shardings over the model axis emits the same streams
    as the single-device non-speculative reference."""
    import jax

    from zoo_tpu.models.llm.llama import tiny_llama_config
    from zoo_tpu.parallel import build_mesh
    from zoo_tpu.serving.llm.model import PagedLlamaModel

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    cfg = tiny_llama_config(vocab=64)
    kw = dict(seed=0, num_slots=2, block_size=4, num_blocks=24,
              max_blocks_per_seq=6, prefill_buckets=(8, 16))
    rs = np.random.RandomState(5)
    motif = rs.randint(0, 64, (4,))
    prompts = [np.tile(motif, 3).astype(np.int32),
               rs.randint(0, 64, (9,)).astype(np.int32)]
    ref, _ = _generate(PagedLlamaModel(cfg, **kw), prompts, 6)
    mesh = build_mesh(jax.devices()[:2], axis_sizes={"model": 2})
    tp = PagedLlamaModel(cfg, mesh=mesh, spec_k=3, **kw)
    assert tp.tp == 2
    got, st = _generate(tp, prompts, 6)
    assert got == ref
    assert st["compiles"]["verify"] == 1
    assert st["blocks_used"] == 0


# ------------------------------------------------------------ chaos smoke

@pytest.mark.perf
def test_check_spec_decode_script_runs():
    """The spec-decode chaos smoke (scripts/check_spec_decode.py): a
    2-replica spec_k=4 group under a mixed repetitive/noise storm —
    byte-identical to the non-speculative reference across a mid-storm
    SIGKILL, accepted-draft floor, zero leaked KV blocks,
    verify-compiles==1."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join("scripts", "check_spec_decode.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SPEC DECODE OK" in proc.stdout
