"""The fourth served architecture (Keye-VL-2.0's language model: a
learned indexer that picks the cached tokens each query attends, and
softmax-routed experts) at toy widths on the CPU, held to the plain
reference the benchmark brings (``benchmarks/reference/keye_vl2.py``,
loaded by its path: one reference, not two). The selection is scaled so
that it really cuts: 16 tokens a query of up to 110 resident, pages of 8.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_tick import tick
from zoo_tpu.obs.metrics import counter
from zoo_tpu.ops import moe
from zoo_tpu.ops.pallas.dsa_decode import (
    dsa_decode_reference,
    dsa_paged_decode,
    kv_row_shape,
)
from zoo_tpu.serving.llm import model_keye
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.model_keye import (
    KeyeConfig,
    PagedKeyeModel,
    kth_largest,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmarks/reference/keye_vl2.py", "ref_keye_vl2")
ADAPTER = _load("benchmarks/adapters/keye_vl2_paged.py",
                "adapter_keye_vl2")
FAULTS = _load("scripts/check_keye_faults.py", "check_keye_faults")

# the published keys at toy widths; 2 of a published depth of 48
TOY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "num_experts": 8, "moe_intermediate_size": 32,
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
       "rope_theta": 10000.0, "decoder_sparse_step": 1,
       "mlp_only_layers": [], "tie_word_embeddings": False,
       "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                     "indexer_num_kv_heads": 1, "topk": 16,
                     "kv_chunk_size": 16, "q_chunk_size": 16}}
CFG = KeyeConfig.from_published(TOY)


def _program_tree(ref_params):
    """The reference's weights under the program's names, widened (the
    CPU multiplies no bfloat16 pair into float32)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), ADAPTER.to_program_tree(ref_params))


def _model(ref_params, cls=PagedKeyeModel, **kw):
    args = dict(num_slots=3, block_size=8, num_blocks=64,
                max_blocks_per_seq=16, prefill_buckets=(16,),
                prefill_chunk=12, kv_dtype="f32", spec_k=0)
    args.update(kw)
    return cls(CFG, params=_program_tree(ref_params), **args)


@pytest.fixture(scope="module")
def ref_params():
    return REF.make_params(2**31 + 9, TOY)


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


def _serve(model, prompts, new):
    eng = LLMEngine(model)
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    _run(eng, handles)
    eng.stop()
    return [list(h.tokens) for h in handles]


def _widest(ref_params, prompts, served):
    return max(float(REF.served_gaps(ref_params, TOY, p, s,
                                     pad_to=32)[0].max())
               for p, s in zip(prompts, served))


# ------------------------------------------- (1) the engine against the reference

@pytest.mark.parametrize("prefill", ["chunked", "bucket"])
def test_served_tokens_follow_the_reference(ref_params, prefill):
    """Prefill (chunks of 12, not a multiple of the page of 8, so the
    60-token prompt is five of them; or one bucket), then decode through
    ``LLMEngine``, three requests sharing ticks: one stays under the
    selection's 16 tokens (every token), one crosses 16 while decoding,
    one is prefilled past it (chunks whose rows each pick their own 16).
    Every served token's reference logit lies within 1e-3 of the
    reference's best: the program runs float32 here, so what is left is
    the order of sums (tiled online softmax against one softmax, the
    grouped product against a mask over every expert), 1e-5 of logits of
    size 1."""
    kw = {} if prefill == "chunked" else dict(
        prefill_chunk=0, prefill_buckets=(16, 64))
    prompts = _prompts(3, (9, 12, 60))
    served = _serve(_model(ref_params, **kw), prompts, (6, 20, 30))
    assert _widest(ref_params, prompts, served) <= 1e-3


def test_a_ticks_logits_are_the_references(ref_params):
    """Three slots at contexts of 10 (every token), 16 -> 17 (the tick
    that crosses the selection's size) and 95 (16 of 95), prefilled in
    chunks and stepped twice: the tick's LOGITS against the reference's
    full forward pass, 2e-4 (float32 on both sides)."""
    model = _model(ref_params)
    seqs = _prompts(11, (12, 18, 97))
    rows = [np.pad(np.arange(1 + 16 * i, 1 + 16 * i + 13), (0, 3))
            for i in range(3)]
    rows = [np.where(np.arange(16) < -(-len(s) // 8), r, 0).astype(np.int32)
            for s, r in zip(seqs, rows)]
    for slot, (seq, row) in enumerate(zip(seqs, rows)):
        for s0 in range(0, len(seq) - 2, 12):
            model.prefill_chunk(seq[s0:min(s0 + 12, len(seq) - 2)], s0,
                                len(seq) - 2, row)
    bs = model.block_size
    for step in (2, 1):
        pos = jnp.asarray([len(s) - step for s in seqs], jnp.int32)
        tables = jnp.asarray(np.stack(rows))
        at = {"cos": jnp.take(model._cos, pos, axis=0),
              "sin": jnp.take(model._sin, pos, axis=0),
              "blk": jnp.take_along_axis(tables, (pos // bs)[:, None],
                                         axis=1)[:, 0],
              "off": pos % bs, "tables": tables, "pos": pos, "real": None,
              "slot": None}
        h = jnp.take(model.params["embed"],
                     jnp.asarray([s[p] for s, p in zip(seqs, pos)]), axis=0)
        h, model._cache, _ = model._layers(model.params, model._cache, h,
                                           model._attend_decode, at)
        logits = model._lm_head(model.params, h)
        for slot, seq in enumerate(seqs):
            want, _, _ = REF.logits_at(ref_params, TOY,
                                       np.pad(seq, (0, 128 - len(seq))),
                                       [int(pos[slot])])
            np.testing.assert_allclose(logits[slot], want[0], atol=2e-4)


@pytest.mark.parametrize("fault", ["dense", "no_relu", "no_w", "sigmoid"])
def test_the_comparison_sees_a_planted_fault(ref_params, fault):
    """The comparison that decides ``correct`` must see the layers this
    architecture exists for: dense attention in place of the selection,
    the indexer's score without its ReLU or without its heads' weights,
    sigmoid routing in place of softmax (``scripts/check_keye_faults.py``,
    which reads the same at the cell's size on the chip): a served token
    lies further below the reference's best than fifty times the limit
    the sound program is held to (1e-3, above)."""
    cls, code = FAULTS.faulty_class()
    model = _model(ref_params, cls)
    prompt = _prompts(5, (90,))[0]

    def widest_gap(name):
        FAULTS.set_fault(model, code[name])
        return _widest(ref_params, [prompt], _serve(model, [prompt], (20,)))

    assert widest_gap("none") <= 1e-3
    assert widest_gap(fault) > 0.05


def test_every_token_below_the_selection_is_the_scored_selection(
        ref_params, monkeypatch):
    """Under ``topk`` resident tokens the selection is every token and
    the indexer is skipped; scoring them and taking the ``topk`` best
    (which is every one of them) serves the same tokens. ``lax.cond``
    forced to its second branch scores every tick and every chunk (the
    sampling's greedy branch is its second as well)."""
    prompts = _prompts(7, (5, 9))
    sound = _serve(_model(ref_params), prompts, (10, 6))
    monkeypatch.setattr(model_keye.jax.lax, "cond",
                        lambda pred, yes, no, op: no(op))
    scored = _serve(_model(ref_params), prompts, (10, 6))
    assert scored == sound


# -------------------------------------------------------------- (2) pieces

@pytest.mark.parametrize("heads,dim,step,K,live", [
    pytest.param(2, 16, 8, 40, (40, 17, 0), id="2-16-8"),
    pytest.param(4, 128, 16, 40, (40, 17, 0), id="4-128-16"),
    pytest.param(4, 128, 128, 40, (40, 17, 0), id="4-128-128"),
    # every list ends on a step's last token
    pytest.param(4, 128, 16, 48, (48, 32, 0), id="on-a-step-boundary"),
    # two steps of 256, the second padded; a list of exactly one step
    pytest.param(2, 16, 256, 300, (300, 256, 0), id="T256-K300"),
    # a step that does not divide the list: 24 of a last step live
    pytest.param(4, 128, 48, 120, (120, 119, 0), id="T48-K120"),
])
def test_dsa_decode_kernel_is_its_twin(heads, dim, step, K, live):
    """``zoo_dsa_decode`` (interpreted) against its plain gather: lists
    of any length, a slot with none, a list that ends inside a step or
    on its last token, a step of 256 tokens, a list its step does not
    divide, rows of bfloat16 (the kernel rounds its probabilities to the
    cache's dtype before the second product, as the twin does). The
    interpreter completes a copy where it starts, so this holds the
    issue loop's arithmetic and the slab's rows, not the wait."""
    S, H, L, nb, bs = 3, 2 * heads, 2, 10, 16
    rs = np.random.RandomState(heads + step)
    q = rs.randn(S, H, dim).astype(np.float32)
    kv = jnp.asarray(rs.randn(L, nb, bs, *kv_row_shape(heads, dim)),
                     jnp.bfloat16)
    rows = rs.randint(0, nb * bs, (S, K)).astype(np.int32)
    n = np.array(live, np.int32)
    got = dsa_paged_decode(q, kv, rows, n, layer=1, tokens_per_step=step,
                           interpret=True)
    want = dsa_decode_reference(jnp.asarray(q), kv, jnp.asarray(rows),
                                jnp.asarray(n), layer=1)
    np.testing.assert_allclose(got, want, atol=1e-2)
    assert np.all(np.asarray(got)[2] == 0.0)


def test_dsa_decode_refuses_a_page_of_no_power_of_two():
    """The issue loop splits a row into page and offset by a shift and a
    mask: a page of 24 tokens is refused, not misread."""
    kv = jnp.zeros((1, 4, 24) + kv_row_shape(2, 16), jnp.bfloat16)
    with pytest.raises(ValueError, match="power of two"):
        dsa_paged_decode(jnp.zeros((1, 4, 16)), kv,
                         jnp.zeros((1, 8), jnp.int32),
                         jnp.ones((1,), jnp.int32), layer=0, interpret=True)


def test_kth_largest_is_exact():
    """The chunk's threshold is the exact k-th largest of every row,
    negative, positive and ``-inf`` entries alike, and ``-inf`` where a
    row has fewer than k finite ones."""
    rs = np.random.RandomState(0)
    x = rs.randn(6, 300).astype(np.float32) * np.array(
        [1e-3, 1, 1e3, 1, 1, 1], np.float32)[:, None]
    x[3, 100:] = -np.inf
    x[4, 5:] = -np.inf
    x[5] = np.round(x[5])                       # ties
    got = np.asarray(kth_largest(jnp.asarray(x), 16))
    want = -np.sort(-x, axis=1)[:, 15]
    np.testing.assert_array_equal(got, want)
    assert got[4] == -np.inf


def _route_before(x, router, bias, top_k, scale=1.0, norm_topk=True):
    """``route_topk`` as it was before it took ``routing``."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def test_softmax_routing_and_the_sigmoid_path_unchanged():
    """Softmax routing is softmax over every expert, the top 8, weights
    renormalised; the sigmoid path with its selection bias gives the
    same bits as before and lowers to the same program."""
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(20, 64), jnp.float32)
    router = jnp.asarray(rs.randn(64, 128) * 0.125, jnp.float32)
    bias = jnp.asarray(rs.randn(128) * 0.02, jnp.float32)
    idx, w = moe.route_topk(x, router, None, 8, routing="softmax")
    p = np.asarray(jax.nn.softmax(jnp.matmul(
        x, router, precision=jax.lax.Precision.HIGHEST), axis=-1))
    want_idx = np.argsort(-p, axis=1)[:, :8]
    want_w = np.take_along_axis(p, want_idx, axis=1)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(
        np.asarray(w), want_w / want_w.sum(1, keepdims=True), rtol=1e-6)
    for kw in ({}, dict(scale=1.8, norm_topk=False)):
        now = jax.jit(lambda a, b, c: moe.route_topk(a, b, c, 4, **kw))
        before = jax.jit(lambda a, b, c: _route_before(a, b, c, 4, **kw))
        for got, want in zip(now(x, router, bias), before(x, router, bias)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert now.lower(x, router, bias).as_text() \
            == before.lower(x, router, bias).as_text()


# --------------------------------------------- (3) preemption, the cache

def test_preemption_and_slot_reuse_give_a_fresh_engines_tokens(ref_params):
    """Four requests through two slots on a pool that cannot hold two
    long ones to their end: slots are reused, one request is preempted
    and re-prefilled from 0 (its K/V and index keys written again), and
    every request's tokens are those of an engine that serves it
    alone, and follow the reference."""
    prompts = _prompts(23, (45, 30, 50, 20))
    new = (25, 30, 20, 12)
    alone = [_serve(_model(ref_params), [p], [n])[0]
             for p, n in zip(prompts, new)]
    preempts0 = counter("zoo_llm_preempt_total").value
    eng = LLMEngine(_model(ref_params, num_slots=2, num_blocks=14))
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    _run(eng, handles, passes=1500)
    eng.stop()
    assert counter("zoo_llm_preempt_total").value > preempts0
    assert [list(h.tokens) for h in handles] == alone
    assert _widest(ref_params, prompts, alone) <= 1e-3


def test_copy_block_and_export_carry_the_index_keys(ref_params):
    """Both paged leaves, K/V and the index keys, move with a block."""
    model = _model(ref_params)
    model.prefill_chunk(_prompts(1, (12,))[0], 0, 12,
                        np.pad(np.array([3, 4], np.int32), (0, 14)))
    assert set(model._cache) == {"kv", "ki"}
    model.copy_block(3, 9)
    for name, arr in model._cache.items():
        np.testing.assert_array_equal(arr[:, 9], arr[:, 3])
        assert np.any(np.asarray(arr[:, 3]) != 0)
    out = model.export_kv_blocks([4])
    assert set(out) == {"kv", "ki"}


def test_counters_and_stats(ref_params):
    """A tick's scored and selected tokens ride back with its tokens:
    ``llm_stats`` and the two counters agree, and a slot over the
    selection's size attends exactly ``topk`` tokens a layer."""
    scored0 = counter("zoo_llm_dsa_tokens_scored_total").value
    selected0 = counter("zoo_llm_dsa_tokens_selected_total").value
    eng = LLMEngine(_model(ref_params))
    h = eng.submit(_prompts(9, (40,))[0], 5)
    _run(eng, [h])
    st = eng.stats()
    eng.stop()
    layers = CFG.n_block
    # four ticks over contexts of 41..44 (the first token comes from the
    # prefill): all scored, 16 selected in each layer
    assert st["dsa_tokens_selected"] == 4 * 16 * layers
    assert st["dsa_tokens_scored"] == (41 + 42 + 43 + 44) * layers
    assert counter("zoo_llm_dsa_tokens_scored_total").value - scored0 \
        == st["dsa_tokens_scored"]
    assert counter("zoo_llm_dsa_tokens_selected_total").value - selected0 \
        == st["dsa_tokens_selected"]
    # the experts a tick visited: 2 choices of one live token a layer
    assert st["moe_rows"] == 4 * 2 * layers
    assert 4 * layers <= st["moe_expert_visits"] <= st["moe_rows"]


# ------------------------------------------------------- (4) what is refused

@pytest.mark.parametrize("kw,engine_kw,match", [
    (dict(spec_k=2), {}, "speculative decoding"),
    (dict(kv_dtype="int8"), {}, "int8"),
    (dict(mesh="two"), {}, "mesh"),
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


def test_migration_is_refused(ref_params):
    eng = LLMEngine(_model(ref_params))
    prompt = np.arange(1, 9, dtype=np.int32)
    with pytest.raises(ValueError, match="kv_migrate"):
        eng.submit(prompt, 4, handoff=True)
    with pytest.raises(ValueError, match="kv_migrate"):
        eng.submit(prompt, 4, adopt={"rid": "x"})
    eng.stop()


def test_the_published_keys_and_the_formulas_count_the_same():
    """The benchmark's configuration builds this config, and the
    reference's parameter count is the formulas' matrices plus gains."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-d4.json")) as f:
        cfg = json.load(f)
    c = KeyeConfig.from_published(cfg)
    assert (c.n_block, c.n_head, c.n_kv_head, c.head_dim, c.n_experts,
            c.experts_per_tok, c.index_heads, c.index_head_dim,
            c.index_topk) == (4, 32, 4, 128, 128, 8, 16, 64, 2048)
    with pytest.raises(ValueError, match="dense feed-forward"):
        KeyeConfig.from_published(dict(cfg, mlp_only_layers=[0]))
