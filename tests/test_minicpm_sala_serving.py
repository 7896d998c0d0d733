"""The third served architecture (``minicpm_sala``: MiniCPM-SALA) at toy
widths on the CPU: block-sparse attention that selects pages inside the
paged cache, Lightning layers whose per-slot state lives beside it —
each held to the plain reference the benchmark brings
(``benchmarks/reference/minicpm_sala.py``, loaded by its path: one
reference, not two). The selection's sizes are scaled so that it really
cuts: windows of 4 every 2 tokens, pages of 8, 6 pages attended of up to
20, the last 16 tokens and page 0 forced, dense under 32 tokens.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_tick import tick
from zoo_tpu.models.llm.llama import LlamaConfig
from zoo_tpu.models.llm.minicpm_sala import (
    MiniCpmSalaConfig,
    tiny_minicpm_sala_config,
)
from zoo_tpu.obs.metrics import counter
from zoo_tpu.ops.pallas.lightning import (
    lightning_chunk,
    lightning_decode,
    lightning_decode_reference,
)
from zoo_tpu.ops.pallas.sparse_decode import (
    sparse_decode_reference,
    sparse_paged_decode,
)
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.model import PagedLlamaModel
from zoo_tpu.serving.llm.model_sala import PagedMiniCpmSalaModel
from zoo_tpu.serving.llm.spec import build_llm_engine, is_llm_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmarks/reference/minicpm_sala.py", "ref_minicpm_sala")
ADAPTER = _load("benchmarks/adapters/minicpm_sala_paged.py",
                "adapter_minicpm_sala")

SPARSE_SIZES = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                "init_blocks": 1, "window_size": 16, "topk": 6,
                "dense_len": 32}
# the published keys at toy widths: a sparse layer first and last, two
# Lightning layers between, 4 of a published depth of 8
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
       "num_hidden_layers": 4,
       "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                       "minicpm4"],
       "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
       "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 32,
       "qk_norm": True, "use_output_gate": True, "use_output_norm": True,
       "attn_use_output_gate": True, "attn_use_rope": False,
       "lightning_use_rope": True, "tie_word_embeddings": False,
       "published": {"num_hidden_layers": 8},
       "sparse_config": SPARSE_SIZES}
CFG = MiniCpmSalaConfig.from_published(TOY)


def _program_tree(ref_params):
    """The reference's weights under the program's names, widened (the
    CPU multiplies no bfloat16 pair into float32)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), ADAPTER.to_program_tree(ref_params))


def _model(ref_params, cls=PagedMiniCpmSalaModel, **kw):
    args = dict(num_slots=3, block_size=8, num_blocks=64,
                max_blocks_per_seq=24, prefill_buckets=(16,),
                prefill_chunk=12, kv_dtype="f32", spec_k=0)
    args.update(kw)
    return cls(CFG, params=_program_tree(ref_params), **args)


@pytest.fixture(scope="module")
def ref_params():
    return REF.make_params(2**31 + 5, TOY)


def _run(eng, handles, passes=600):
    for _ in range(passes):
        tick(eng)
        if all(h.done for h in handles):
            break
    assert [h.outcome for h in handles] == ["ok"] * len(handles)


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


# ------------------------------------------- (1) the engine against the reference

@pytest.mark.parametrize("kv,prefill,limit", [
    ("f32", "chunked", 1e-3), ("f32", "bucket", 1e-3),
    ("bf16", "chunked", 0.02)])
def test_served_tokens_follow_the_reference(ref_params, kv, prefill, limit):
    """Prefill (chunks of 12, not a multiple of the page of 8; or one
    bucket), then decode through ``LLMEngine``, three requests sharing
    ticks: one stays under ``dense_len``, one crosses it while decoding,
    one is prefilled past it. Every served token's reference logit lies
    within ``limit`` of the reference's best. The program runs float32
    here, so what is left is the order of sums (the chunked scan and the
    recurrence against the O(n^2) form, tiled online softmax against one
    softmax): 1e-5 of logits of size 1, and 1e-3 leaves two decades and
    is an order under the float8 control's smallest reading (0.016).
    A bfloat16 cache rounds K, V and the compressed keys to 3 digits:
    its limit is the control's reading."""
    kw = dict(kv_dtype=kv)
    if prefill == "bucket":
        kw.update(prefill_chunk=0, prefill_buckets=(32, 160))
    model = _model(ref_params, **kw)
    eng = LLMEngine(model)
    prompts = _prompts(3, (9, 21, 150))
    handles = [eng.submit(p, n) for p, n in zip(prompts, (12, 40, 30))]
    _run(eng, handles)
    control = 0.0
    for prompt, h in zip(prompts, handles):
        gaps, low = REF.served_gaps(ref_params, TOY, prompt, h.tokens,
                                    pad_to=32, lower_too=True)
        assert len(gaps) == len(h.tokens)
        assert float(gaps.max()) <= limit, gaps
        control = max(control, float(low.max()))
    if kv == "f32":
        assert control > 10 * limit, control
    st = eng.stats()
    assert st["state_resets"] == 3          # one a request: its start
    assert st["state_steps"] == 2 * (11 + 39 + 29)
    assert 0 < st["sparse_pages_attended"] < st["sparse_pages_resident"]
    assert st["compiles"]["decode"] == 1
    assert eng.allocator.used_blocks == 0
    eng.stop()


FAULTS = _load("scripts/check_sala_faults.py", "check_sala_faults")


@pytest.mark.parametrize("fault", ["forced_only", "sparse_zero",
                                   "stale_state"])
def test_the_comparison_sees_a_planted_fault(ref_params, fault):
    """The comparison that decides ``correct`` must see the layers this
    architecture exists for: with the selection keeping its forced pages
    alone, a tick's sparse mixers zeroed, or a tick's Lightning state
    not written back (``scripts/check_sala_faults.py``, which reads the
    same at the cell's size on the chip), a served token lies further
    below the reference's best than a hundred times the limit the sound
    program is held to (1e-3, above; they read 0.22, 0.27, 0.44). It
    does because the weights are made so that the mixers carry the
    logits (embedding std 1 / ``scale_emb``, a peaked sparse attention:
    the reference's ``make_params``): at an embedding of std 1 and a
    q gain of 1 the stream is the last token's embedding and the same
    faults read 0.002 to 0.004."""
    cls, code = FAULTS.faulty_class()
    model = _model(ref_params, cls)
    prompt = _prompts(3, (150,))[0]

    def widest_gap(name):
        FAULTS.set_fault(model, code[name])
        eng = LLMEngine(model)
        h = eng.submit(prompt, 30)
        _run(eng, [h])
        eng.stop()
        gaps, _ = REF.served_gaps(ref_params, TOY, prompt, h.tokens,
                                  pad_to=32)
        return float(gaps.max())

    assert widest_gap("none") <= 1e-3
    assert widest_gap(fault) > 0.1


def _decode_logits(model, tokens, tables, positions):
    """The skeleton's decode body up to the head: a tick's logits."""
    bs = model.block_size
    positions = jnp.asarray(positions, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    at = {"cos": jnp.take(model._cos, positions, axis=0),
          "sin": jnp.take(model._sin, positions, axis=0),
          "blk": jnp.take_along_axis(
              tables, (positions // bs)[:, None], axis=1)[:, 0],
          "off": positions % bs, "tables": tables, "pos": positions,
          "real": None, "slot": None}
    h = jnp.take(model.params["embed"], jnp.asarray(tokens), axis=0)
    h, cache, _ = model._layers(model.params, model._cache, h,
                                model._attend_decode, at)
    return model._lm_head(model.params, h), cache


def _table(blocks, width=24):
    row = np.zeros((width,), np.int32)
    row[:len(blocks)] = blocks
    return row


def _prefill(model, slot, tokens, row, chunk=12):
    for s0 in range(0, len(tokens), chunk):
        model.prefill_chunk(tokens[s0:s0 + chunk], s0, len(tokens), row,
                            slot=slot)


def test_a_ticks_logits_are_the_references(ref_params):
    """Three slots at contexts of 20 (dense), 31 -> 32 (the token that
    crosses ``dense_len``) and 101 (sparse), prefilled in chunks and
    stepped twice: the tick's LOGITS against the reference's full
    forward pass, 2e-4 (float32 on both sides; logits of size 1)."""
    model = _model(ref_params)
    seqs = _prompts(11, (22, 33, 103))
    rows = [_table(range(1, 4)), _table(range(4, 9)),
            _table(range(9, 22))]
    for slot, (seq, row) in enumerate(zip(seqs, rows)):
        _prefill(model, slot, seq[:-2], row)
    for step in (2, 1):
        pos = [len(s) - step for s in seqs]
        logits, model._cache = _decode_logits(
            model, [s[p] for s, p in zip(seqs, pos)], np.stack(rows), pos)
        for slot, seq in enumerate(seqs):
            want = REF.logits_at(ref_params, TOY,
                                 np.pad(seq, (0, 128 - len(seq))),
                                 [pos[slot]])
            np.testing.assert_allclose(logits[slot], want[0], atol=2e-4)


# --------------------------------------------------------- (2) Lightning

@pytest.mark.parametrize("chunks", [(40,), (16, 24), (7, 13, 20), (1,) * 9])
def test_lightning_recurrence_scan_and_quadratic_form_agree(chunks):
    """The token recurrence (the decode step, kernel and twin), the
    chunked scan with its state carried (pad rows and all) and the
    reference's O(n^2) decay-masked form are one function; the state
    runs across chunks and into decode."""
    rng = np.random.default_rng(5)
    H, D, T = 4, 16, sum(chunks) + 3
    q, k, v = (jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
               for _ in range(3))
    slopes = REF.lightning_slopes(H)
    want = REF.lightning_attention(q, k, v, slopes)
    state, at, outs = jnp.zeros((H, D, D)), 0, []
    for n in chunks:
        pad = 5                                  # rows past the real ones
        rows = slice(at, at + n + pad)
        o, state = lightning_chunk(
            *(jnp.pad(x[rows], ((0, n + pad - x[rows].shape[0]), (0, 0),
                                (0, 0))) for x in (q, k, v)),
            slopes, state, n)
        outs.append(o[:n])
        at += n
    # the last three tokens one at a time: slot 1 of 2 is live
    leaf = jnp.zeros((2, 2, H, D, D)).at[1, 1].set(state)
    live = jnp.asarray([False, True])
    for t in range(at, T):
        step = lightning_decode if t % 2 else lightning_decode_reference
        two = [jnp.stack([x[t] * 0 + 7.0, x[t]]) for x in (q, k, v)]
        o, leaf = step(leaf, 1, *two, jnp.exp(-slopes), live)
        outs.append(o[1][None])
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=2e-5)
    assert not np.asarray(leaf[0]).any() and not np.asarray(leaf[1, 0]).any()


# --------------------------------------------------------- (3) the selection

def _sparse_inputs(model, ref_params, seq):
    """Layer 0's normed q and k of ``seq`` as the reference makes them
    (float32), and the reference's compressed keys."""
    p = ref_params["layers"][0]
    x = REF._rms_norm(TOY["scale_emb"] * jnp.take(
        ref_params["embed"], jnp.asarray(seq), axis=0).astype(jnp.float32),
        p["input_norm"], 1e-6)
    t = len(seq)
    q = REF._rms_norm(REF._mm(x, p["q"], False).reshape(t, 2, 2, 16),
                      p["q_norm"], 1e-6)
    k = REF._rms_norm(REF._mm(x, p["k"], False).reshape(t, 2, 16),
                      p["k_norm"], 1e-6)
    return q, k, REF.compressed_keys(k, SPARSE_SIZES)


def test_selection_is_the_references(ref_params):
    """A chunk's per-row page masks and a tick's page tables equal the
    reference's block sets wherever its margin is no tie (1e-5); forced
    pages are always in; one set a K/V head; never a page in the causal
    future; a tick's table is live-first with the query's own page cut
    at the query."""
    model = _model(ref_params, prefill_chunk=50, prefill_buckets=(50,))
    seq = _prompts(13, (150,))[0]
    row = _table(range(2, 21))
    _prefill(model, 1, seq, row, chunk=50)
    q, k, kc = _sparse_inputs(model, ref_params, seq)
    pos = jnp.arange(len(seq))
    n_blocks = 19
    want, margin = REF.select_blocks(
        REF.block_scores(q, kc, pos, SPARSE_SIZES, n_blocks), SPARSE_SIZES)
    want, margin = np.asarray(want), np.asarray(margin)
    got = np.asarray(model._select_mask(
        q, model._cache["ck"], 0, jnp.asarray(row), pos))[..., :n_blocks]
    sparse = np.arange(len(seq)) + 1 >= 32
    sure = sparse[:, None] & (margin > 1e-5)
    assert sure.mean() > 0.6
    np.testing.assert_array_equal(got[sure], want[sure])
    own = np.arange(len(seq)) // 8
    for t in (40, 77, 149):
        for g in range(2):
            assert got[t, g, 0] and got[t, g, (t - 15) // 8:own[t] + 1].all()
            assert not got[t, g, own[t] + 1:].any()
            assert got[t, g].sum() == 6
    dense = ~sparse
    assert all(got[t, g, :own[t] + 1].all() and not got[t, g, own[t] + 1:]
               .any() for t in np.flatnonzero(dense) for g in range(2))
    # a tick: slot 1 at the sequence's last position, slots 0 and 2 idle
    t = len(seq) - 1
    tables = np.stack([_table([]), row, _table([])])
    live = jnp.asarray([False, True, False])
    phys, lens, n_live = model._select_pages(
        jnp.stack([q[t]] * 3), model._cache["ck"], 0, jnp.asarray(tables),
        jnp.asarray([0, t, 0]), live)
    assert np.asarray(n_live).tolist() == [[0, 0], [6, 6], [0, 0]]
    for g in range(2):
        pages = np.asarray(phys[1, g, :6])
        assert sorted(pages) == sorted(row[np.flatnonzero(want[t, g])])
        cut = np.asarray(lens[1, g, :6])
        assert sorted(cut) == [t % 8 + 1] + [8] * 5
        assert cut[list(pages).index(row[own[t]])] == t % 8 + 1
        assert not np.asarray(lens[1, g, 6:]).any()
    assert not np.asarray(lens[0]).any() and not np.asarray(lens[2]).any()


def test_compressed_keys_appended_a_window_at_a_time(ref_params):
    """The compressed keys a prefill in chunks of 12 and then 20 decode
    ticks leave in the pages equal those computed at once from the whole
    sequence's keys (the reference's), window by window, across page and
    chunk boundaries; a window that is not whole yet is not written."""
    model = _model(ref_params)
    seq = _prompts(17, (90,))[0]
    row = _table(range(5, 17))
    _prefill(model, 0, seq[:70], row)
    tables = np.stack([row, _table([]), _table([])])
    for t in range(70, 90):
        _, model._cache = _decode_logits(
            model, [seq[t], 0, 0], tables, [t, 0, 0])
    _, _, kc = _sparse_inputs(model, ref_params, seq)
    ck = np.asarray(model._cache["ck"][0])        # (blocks, 2 * 4, 16)
    got = ck[row[:12]].reshape(12, 2, 4, 16).transpose(0, 2, 1, 3).reshape(
        48, 2, 16)
    n = kc.shape[0]
    assert n == (90 - 4) // 2 + 1
    np.testing.assert_allclose(got[:n], kc, atol=1e-6)
    assert not got[n:].any()


# ----------------------------------------------- (4) the kernels, interpreted

@pytest.mark.parametrize("entries", [None, 4, 1])
def test_sparse_decode_kernel_is_its_twin(entries):
    rng = np.random.default_rng(0)
    S, G, Hg, D, bs, NB, E, L = 3, 2, 4, 16, 8, 20, 6, 2
    q = jnp.asarray(rng.standard_normal((S, G, Hg, D)), jnp.float32)
    kc, vc = (jnp.asarray(rng.standard_normal((L, NB, G, bs, D)),
                          jnp.float32) for _ in range(2))
    n_live = np.asarray([[6, 4], [1, 0], [3, 5]], np.int32)
    lens = np.full((S, G, E), bs, np.int32)
    for s in range(S):
        for g in range(G):
            lens[s, g, n_live[s, g]:] = 0
            if n_live[s, g]:
                lens[s, g, rng.integers(0, n_live[s, g])] = \
                    rng.integers(1, bs + 1)
    tables = np.where(lens > 0, rng.integers(1, NB, (S, G, E)), 0)
    args = (q, kc, vc, jnp.asarray(tables), jnp.asarray(lens))
    got = sparse_paged_decode(*args, jnp.asarray(n_live), layer=1,
                              entries=entries, interpret=True)
    want = sparse_decode_reference(*args, layer=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert not np.asarray(got[1, 1]).any()          # no live entry: 0


def test_flash_decode_serves_the_dense_tokens(ref_params):
    """The engine over the two kernels (interpreted) serves the tokens
    of the engine over their twins."""
    prompts = _prompts(19, (40, 12))
    served = []
    for impl in ("dense", "flash"):
        eng = LLMEngine(_model(ref_params, decode_impl=impl))
        handles = [eng.submit(p, 10) for p in prompts]
        _run(eng, handles)
        served.append([h.tokens for h in handles])
        eng.stop()
    assert served[0] == served[1]


# ---------------------------------- (5) a slot's second life, and preemption

def test_slot_reuse_and_preemption_give_a_fresh_engines_tokens(ref_params):
    """Four requests through two slots on a pool that cannot hold two
    long ones to their end: slots are reused (a state left by the
    sequence before), one request is preempted and re-prefilled from 0,
    and an empty slot sits beside live ones at the start and the end.
    Every request's tokens are those of an engine that serves it
    alone."""
    prompts = _prompts(23, (45, 30, 50, 20))
    new = (25, 30, 20, 12)
    alone = []
    for p, n in zip(prompts, new):
        eng = LLMEngine(_model(ref_params))
        h = eng.submit(p, n)
        _run(eng, [h])
        alone.append(h.tokens)
        eng.stop()
    preempts0 = counter("zoo_llm_preempt_total").value
    eng = LLMEngine(_model(ref_params, num_slots=2, num_blocks=14))
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    _run(eng, handles, passes=1500)
    assert counter("zoo_llm_preempt_total").value > preempts0
    assert [h.tokens for h in handles] == alone
    assert eng.stats()["state_resets"] > 4      # the re-prefill's among them
    eng.stop()


# ------------------------------------------------------- (6) what is refused

@pytest.mark.parametrize("kw,engine_kw,match", [
    (dict(spec_k=2), {}, "speculative decoding"),
    (dict(kv_dtype="int8"), {}, "int8"),
    (dict(mesh="two"), {}, "mesh"),
    (dict(block_size=16), {}, "selection block"),
    ({}, dict(prefix_cache=True), "prefix cache"),
    ({}, dict(role="prefill"), "kv_migrate"),
    ({}, dict(role="decode"), "kv_migrate"),
])
def test_what_is_not_built_is_refused_at_construction(ref_params, kw,
                                                      engine_kw, match):
    if "mesh" in kw:
        class Two:
            size = 2
            shape = {"model": 2}
        kw = dict(mesh=Two())
    with pytest.raises(ValueError, match=match):
        LLMEngine(_model(ref_params, **kw), **engine_kw)


def test_migration_of_a_stateful_sequence_is_refused(ref_params):
    eng = LLMEngine(_model(ref_params))
    prompt = np.arange(1, 9, dtype=np.int32)
    with pytest.raises(ValueError, match="kv_migrate"):
        eng.submit(prompt, 4, handoff=True)
    with pytest.raises(ValueError, match="kv_migrate"):
        eng.submit(prompt, 4, adopt={"rid": "x"})
    assert eng.offer_adopted({"rid": "x", "block_size": 8,
                              "kv": {}}) is False
    with pytest.raises(ValueError, match="slot"):
        eng.model.prefill_chunk(prompt, 0, 8, _table([1]))
    eng.stop()


# ------------------------------------------------------- (7) the spec string

def test_spec_builds_the_architecture():
    spec = ("minicpm_sala:tiny:slots=2,block=8,blocks=40,tables=12,"
            "chunk=8,buckets=16/32")
    assert is_llm_spec(spec)
    attended0 = counter("zoo_llm_sparse_pages_attended_total").value
    eng = build_llm_engine(spec)
    try:
        model = eng.model
        assert isinstance(model, PagedMiniCpmSalaModel)
        assert model.cfg == tiny_minicpm_sala_config()
        h = eng.submit(np.arange(1, 41, dtype=np.int32), 5)
        deadline = time.monotonic() + 120
        while not h.done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h.outcome == "ok" and len(h.tokens) == 5
        st = eng.stats()
        # 4 ticks of one live slot: 2 Lightning layers; contexts of 41
        # to 44 tokens are 6 pages, all 6 attended, in 2 sparse layers
        # of 2 K/V heads
        assert st["state_steps"] == 4 * 2
        assert st["sparse_pages_attended"] == 4 * 6 * 2 * 2
        assert st["sparse_pages_resident"] == 4 * 6 * 2 * 2
        assert st["state_resets"] == 1
        assert st["state_bytes_per_slot"] == 2 * 4 * 16 * 16 * 4
        assert st["state_bytes"] == 2 * st["state_bytes_per_slot"]
        assert st["kv_bytes_per_token"] == 2 * (2 * 2 * 16 * 4) \
            + 2 * 2 * 16 * 4 // 2
        assert st["compiles"]["decode"] == 1
        assert counter("zoo_llm_sparse_pages_attended_total").value \
            - attended0 == st["sparse_pages_attended"]
    finally:
        eng.stop()
    other = build_llm_engine(
        "minicpm_sala:tiny:mixers=sllls,topk=4,slots=1,block=8,blocks=8,"
        "tables=4,buckets=16", start=False)
    assert other.model.cfg.mixer_types == (
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn",
        "minicpm4") and other.model.cfg.topk == 4
    for bad, match in ((",kv=int8", "int8"), (",spec_k=2", "speculative"),
                       (",prefix_cache=1", "prefix cache"),
                       (",role=decode", "kv_migrate"),
                       (",block=16", "selection block")):
        with pytest.raises(ValueError, match=match):
            build_llm_engine(spec + bad, start=False)
    with pytest.raises(ValueError, match="one letter a layer"):
        build_llm_engine("minicpm_sala:tiny:mixers=sxl", start=False)


# ------------------- the skeleton's small repair: leaves that are not paged

def test_a_model_with_an_unpaged_leaf_copies_and_exports_its_paged_ones(
        ref_params):
    """``copy_block`` / ``export_kv_blocks`` / ``import_kv_blocks`` act
    on every PAGED leaf and leave the per-slot state alone; a model
    without one (Llama) is as it was: every leaf paged."""
    a = _model(ref_params, num_blocks=12, max_blocks_per_seq=8)
    assert a.UNPAGED_LEAVES == ("state",)
    assert set(a._cache) == {"k", "v", "ck", "state"}
    assert a._cache["k"].shape == (2, 12, 2, 8, 16)
    assert a._cache["ck"].shape == (2, 12, 2 * 4, 16)
    assert a._cache["state"].shape == (2, 3, 4, 16, 16)
    assert a.donated_cache_leaves() == 4
    row = _table((3, 5), width=8)
    a.prefill_chunk(np.arange(1, 13, dtype=np.int32), 0, 12, row, slot=2)
    state = np.asarray(a._cache["state"])
    assert np.abs(state[:, 2]).max() > 0 and not state[:, :2].any()
    a.copy_block(3, 7)
    np.testing.assert_array_equal(np.asarray(a._cache["state"]), state)
    out = a.export_kv_blocks([3, 7, 5])
    assert set(out) == {"k", "v", "ck"}
    for name in out:
        assert out[name].shape[:2] == (2, 3)
        assert np.abs(out[name][:, 0]).max() > 0
        np.testing.assert_array_equal(out[name][:, 0], out[name][:, 1])
    b = _model(ref_params, num_blocks=12, max_blocks_per_seq=8)
    b.import_kv_blocks([2, 9], out, start=1)
    back = b.export_kv_blocks([2, 9])
    for name in out:
        np.testing.assert_array_equal(back[name], out[name][:, 1:])
    assert not np.asarray(b._cache["state"]).any()
    with pytest.raises(ValueError, match="missing cache planes"):
        b.import_kv_blocks([2], {"k": out["k"], "v": out["v"]})
    llama = PagedLlamaModel(
        LlamaConfig(vocab=64, hidden=32, n_block=1, n_head=2, n_kv_head=1,
                    intermediate=64), num_slots=2, block_size=4,
        num_blocks=8, max_blocks_per_seq=4, prefill_buckets=(8,))
    assert llama.UNPAGED_LEAVES == () and set(llama._paged()) == {"k", "v"}
    llama.prefill(np.arange(1, 7, dtype=np.int32), _table((2, 3), width=4))
    llama.copy_block(2, 6)
    got = llama.export_kv_blocks([2, 6])
    assert set(got) == {"k", "v"}
    np.testing.assert_array_equal(got["k"][:, 0], got["k"][:, 1])
