"""The second served architecture (``glm4_moe_lite``: GLM-4.7-Flash) at
toy widths on the CPU: latent paged cache, absorbed decode, expanded
chunk prefill, the dropless expert layer — each held to the plain
reference the benchmark brings (``benchmarks/reference/glm4_moe_lite.py``,
loaded by its path: one reference, not two) — and the Llama-shaped model
as it was before the skeleton was factored out of it.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_tick import tick
from zoo_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig
from zoo_tpu.obs.metrics import counter
from zoo_tpu.ops.moe import moe_ffn_dropless, route_topk
from zoo_tpu.ops.pallas.mla_decode import (
    mla_decode_reference,
    mla_paged_decode,
)
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.model_mla import PagedGlmMoeLiteModel
from zoo_tpu.serving.llm.spec import build_llm_engine, is_llm_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmarks/reference/glm4_moe_lite.py", "ref_glm4_moe_lite")
ADAPTER = _load("benchmarks/adapters/glm4_moe_lite_paged.py",
                "adapter_glm4_moe_lite")

# the published keys at toy widths (two expert layers after the dense one)
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_attention_heads": 4,
       "num_hidden_layers": 3, "vocab_size": 512, "q_lora_rank": 32,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 1,
       "num_experts_per_tok": 2, "first_k_dense_replace": 1,
       "routed_scaling_factor": 1.8, "norm_topk_prob": True,
       "n_group": 1, "topk_group": 1, "rms_norm_eps": 1e-5,
       "rope_theta": 10000.0, "rope_scaling": None,
       "partial_rotary_factor": 1, "tie_word_embeddings": False}


def _program_tree(ref_params):
    """The reference's weights under the program's names, widened (the
    CPU multiplies no bfloat16 pair into float32)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ADAPTER.to_program_tree(ref_params, TOY))


def _model(ref_params, **kw):
    args = dict(num_slots=2, block_size=4, num_blocks=10,
                max_blocks_per_seq=8, prefill_buckets=(8,),
                prefill_chunk=8, kv_dtype="f32", spec_k=0)
    args.update(kw)
    return PagedGlmMoeLiteModel(GlmMoeLiteConfig.from_published(TOY),
                                params=_program_tree(ref_params), **args)


@pytest.fixture(scope="module")
def ref_params():
    return REF.make_params(2**31 + 5, TOY)


# ------------------------------------------- the engine against the reference

def test_served_tokens_follow_the_reference(ref_params):
    """Chunked prefill, then decode through the latent cache, two
    requests sharing ticks on a pool that cannot hold both to their
    end, so one is preempted and re-prefilled: every served token's
    reference logit lies within 1e-3 of the reference's best. The
    program runs float32 here, so what is left is the order of sums
    (absorbed against expanded attention, the sorted grouped product
    against the masked sum over all experts): 1e-5 of logits of size 1;
    1e-3 leaves two decades and is fifty times under the float8
    control's smallest reading."""
    model = _model(ref_params)
    eng = LLMEngine(model)
    preempts0 = counter("zoo_llm_preempt_total").value
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
               for n in (11, 10)]
    handles = [eng.submit(p, 14) for p in prompts]
    for _ in range(200):
        tick(eng)
        if all(h.done for h in handles):
            break
    assert [h.outcome for h in handles] == ["ok", "ok"]
    assert counter("zoo_llm_preempt_total").value > preempts0
    assert eng.allocator.used_blocks == 0
    for prompt, h in zip(prompts, handles):
        gaps, low = REF.served_gaps(ref_params, TOY, prompt, h.tokens,
                                    pad_to=32, lower_too=True)
        assert len(gaps) == 14
        assert float(gaps.max()) <= 1e-3, gaps
        assert float(low.max()) > 0.05, low
    assert model.moe_rows > 0 and model.moe_expert_visits > 0
    assert model.compile_counts()["decode"] == 1
    eng.stop()


def test_absorbed_decode_and_expanded_rows_are_one_function(ref_params):
    """Decode attends the latent rows themselves (absorbed), a chunk
    re-makes K and V from them (expanded): the same query over the same
    cache gives the same output through either."""
    a = _model(ref_params)
    c = a.cfg
    rng = np.random.default_rng(3)
    lat = jnp.asarray(rng.normal(size=a._cache["lat"].shape), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(2, c.n_head,
                                          c.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, c.n_head,
                                          c.qk_rope_head_dim)), jnp.float32)
    tables = jnp.asarray([[3, 1, 4, 0, 0, 0, 0, 0],
                          [2, 5, 6, 7, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([8, 13], jnp.int32)
    p = a.params["lead"][0]
    q_lat = jnp.einsum("shd,hcd->shc", q_nope, p["w_uk"])
    absorbed = a._out_proj(p, mla_decode_reference(
        q_lat, q_rope, lat, 1, tables, pos,
        scale=float(c.qk_head_dim) ** -0.5))
    expanded = a._rows_attend(p, q_nope[:, None], q_rope[:, None], lat, 1,
                              tables, pos[:, None])
    assert expanded.shape == (2, 1, c.hidden)
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded[:, 0]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("blocks_per_step,splits", [(4, 2), (3, 1),
                                                    (16, 4)])
def test_mla_decode_kernel_is_the_dense_gather(blocks_per_step, splits):
    """``zoo_mla_decode`` under the Pallas interpreter against the
    dense gather, with an idle slot (position 0 on the trash block), a
    position in the middle of a step and one at the table's end."""
    rng = np.random.default_rng(0)
    S, H, rank, rope, L, nb, bs, W = 3, 4, 32, 8, 2, 40, 8, 10
    cache = jnp.asarray(rng.normal(size=(L, nb, bs, 128)), jnp.float32)
    ql = jnp.asarray(rng.normal(size=(S, H, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(S, H, rope)), jnp.float32)
    bt = rng.permutation(np.arange(1, nb))[:S * W].reshape(S, W)
    bt[0] = 0
    pos = jnp.asarray([0, 37, 79], jnp.int32)
    out = mla_paged_decode(ql, qr, cache, 1, jnp.asarray(bt, jnp.int32),
                           pos, scale=0.2, blocks_per_step=blocks_per_step,
                           num_splits=splits, interpret=True)
    ref = mla_decode_reference(ql, qr, cache, 1,
                               jnp.asarray(bt, jnp.int32), pos, scale=0.2)
    assert out.shape == (S, H, rank)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_serves_the_dense_tokens(ref_params):
    """The decode executable with the kernel (interpreted) and with the
    dense gather serve the same tokens."""
    toks = {}
    for impl in ("dense", "flash"):
        eng = LLMEngine(_model(ref_params, decode_impl=impl,
                               num_blocks=24)).start()
        h = eng.submit(np.arange(3, 16, dtype=np.int32), 6)
        deadline = time.monotonic() + 120
        while not h.done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h.outcome == "ok", h.error
        toks[impl] = h.tokens
        assert eng.stats()["decode_attention_impl"] == impl
        eng.stop()
    assert toks["dense"] == toks["flash"]


# ------------------------------------------------------- the dropless layer

def _moe_params(rng, h=16, f=8, e=8, shared=True):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * shape[-2] ** -0.5,
                           jnp.float32)
    p = {"router": w(h, e), "bias": jnp.zeros((e,), jnp.float32),
         "w_gate": w(e, h, f), "w_up": w(e, h, f), "w_down": w(e, f, h)}
    if shared:
        p.update(ws_gate=w(h, f), ws_up=w(h, f), ws_down=w(f, h))
    return p


def _masked(p, x, top_k, scale):
    """Every expert applied to every token, a mask keeping the chosen:
    the reference file's own routing and SwiGLU."""
    cfg = {"num_experts_per_tok": top_k, "norm_topk_prob": True,
           "routed_scaling_factor": scale}
    _, w, _ = REF.route(cfg, x, {"router": p["router"],
                                 "e_score_correction_bias": p["bias"]})
    y = sum(w[:, e:e + 1] * REF._swiglu(x, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e], False)
            for e in range(p["router"].shape[1]))
    if "ws_gate" in p:
        y = y + REF._swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"],
                            False)
    return y


@pytest.mark.parametrize("case", ["even", "one_expert_pair", "bias",
                                  "scale_and_shared"])
def test_dropless_layer_is_the_masked_reference(case):
    rng = np.random.default_rng(11)
    p = _moe_params(rng)
    x = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    e = p["router"].shape[1]
    if case == "one_expert_pair":
        # every token picks experts 0 and 1: 48 rows on two experts,
        # three times what a capacity of 1.25 would have let through
        p["bias"] = jnp.zeros((e,)).at[:2].set(10.0)
    if case == "bias":
        p["bias"] = jnp.asarray(rng.normal(size=(e,)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts = moe_ffn_dropless(p, x, top_k=2, scale=1.8)
        want = _masked(p, x, 2, 1.8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(counts[1]) == 24 * 2               # nothing dropped
    if case == "one_expert_pair":
        assert int(counts[0]) == 2
    if case == "bias":
        # the bias changes the choice and never the weight
        plain = dict(p, bias=jnp.zeros((e,), jnp.float32))
        idx_b, w_b = route_topk(x, p["router"], p["bias"], 2, 1.8)
        idx_0, _ = route_topk(x, plain["router"], plain["bias"], 2, 1.8)
        assert (np.sort(idx_b, -1) != np.sort(idx_0, -1)).any()
        s = jax.nn.sigmoid(jnp.matmul(
            x, p["router"], precision=jax.lax.Precision.HIGHEST))
        chosen = jnp.take_along_axis(s, idx_b, axis=-1)
        np.testing.assert_allclose(
            np.asarray(w_b),
            np.asarray(chosen / chosen.sum(-1, keepdims=True) * 1.8),
            rtol=1e-6)
    if case == "scale_and_shared":
        bare = {k: v for k, v in p.items() if not k.startswith("ws_")}
        with jax.default_matmul_precision("highest"):
            y1, _ = moe_ffn_dropless(bare, x, top_k=2, scale=1.0)
            y18, _ = moe_ffn_dropless(bare, x, top_k=2, scale=1.8)
            shared = REF._swiglu(x, p["ws_gate"], p["ws_up"],
                                 p["ws_down"], False)
        np.testing.assert_allclose(np.asarray(y18), 1.8 * np.asarray(y1),
                                   rtol=1e-5, atol=1e-6)
        # the shared expert is added once, outside the scale
        np.testing.assert_allclose(np.asarray(y - y18),
                                   np.asarray(shared), rtol=1e-4,
                                   atol=1e-5)
    # idle lanes are not counted and change no output
    valid = jnp.arange(24) < 5
    y_v, c_v = moe_ffn_dropless(p, x, top_k=2, scale=1.8, valid=valid)
    assert int(c_v[1]) == 10 and int(c_v[0]) <= 10
    np.testing.assert_array_equal(np.asarray(y_v), np.asarray(
        moe_ffn_dropless(p, x, top_k=2, scale=1.8)[0]))


def test_the_router_control_narrows_the_router_alone(ref_params):
    """The second control of the near-tie rule (``route(bf16=True)``):
    the same routing with the router one precision down. Every token
    still has its two experts; where the choice is the float32 one the
    weights lie within two bfloat16 roundings of a score (2^-8 each)
    times the scale; the float32 path is untouched by the flag."""
    rng = np.random.default_rng(17)
    p = ref_params["moe"][0]
    x = jnp.asarray(rng.normal(size=(256, TOY["hidden_size"])), jnp.float32)
    chosen, w, margin = REF.route(TOY, x, p)
    chosen_b, w_b, _ = REF.route(TOY, x, p, bf16=True)
    assert (np.asarray(chosen).sum(-1) == 2).all()
    assert (np.asarray(chosen_b).sum(-1) == 2).all()
    same = (np.asarray(chosen) == np.asarray(chosen_b)).all(-1)
    assert same.mean() > 0.9
    diff = np.abs(np.asarray(w) - np.asarray(w_b))[same]
    assert 0 < diff.max() < 1.8 * 2 * 2.0 ** -8
    # a choice that differs is a near-tie of the float32 scores
    assert (np.asarray(margin)[~same] < 2 * 2.0 ** -8).all()


@pytest.mark.parametrize("sizes,tiling", [
    ([5, 0, 20, 7], (8, 8, 8)),            # an empty expert, shared tiles
    ([0, 0, 32, 0], (8, 16, 24)),          # every row on one expert
    ([1, 9, 0, 20, 0], (8, 8, 8)),         # rows padded to whole tiles
    ([2] * 8, (16, 8, 8)),                 # eight experts in one tile
])
def test_moe_gmm_kernel_is_ragged_dot(sizes, tiling):
    """``zoo_moe_gmm`` under the Pallas interpreter against
    ``jax.lax.ragged_dot`` on the same sorted rows."""
    from zoo_tpu.ops.pallas.moe_gmm import group_tiles, moe_gmm
    rng = np.random.default_rng(5)
    m, g = sum(sizes), len(sizes)
    lhs = jnp.asarray(rng.normal(size=(m, 16)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(g, 16, 24)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = moe_gmm(lhs, rhs, gs, tiling=tiling, interpret=True)
        want = jax.lax.ragged_dot(lhs, rhs, gs,
                                  preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # an expert without rows is in no (tile, expert) pair: its weights
    # are never fetched
    tm = tiling[0]
    m_pad = -(-m // tm) * tm
    _, _, gid, _, n_pairs = group_tiles(gs, m_pad, tm)
    visited = set(np.asarray(gid)[:int(n_pairs[0])].tolist())
    assert visited == {i for i, n in enumerate(sizes) if n}


def test_dropless_layer_with_the_kernel_is_the_same_layer(monkeypatch):
    """The layer picks the grouped product by platform; handed the
    kernel (interpreted) in place of the CPU's ``ragged_dot`` it is
    the same layer."""
    from zoo_tpu.ops import moe
    from zoo_tpu.ops.pallas.moe_gmm import moe_gmm
    rng = np.random.default_rng(13)
    p = _moe_params(rng, h=16, f=8)
    x = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_r, c_r = moe_ffn_dropless(p, x, top_k=2, scale=1.8)
        monkeypatch.setattr(moe, "_grouped_dot", moe_gmm)
        y_k, c_k = moe_ffn_dropless(p, x, top_k=2, scale=1.8)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


# --------------------------------------------------------- the latent cache

def test_latent_blocks_copy_export_and_import(ref_params):
    a = _model(ref_params, num_blocks=12)
    c = a.cfg
    assert set(a._cache) == {"lat"}
    assert a.latent_row == 128            # 32 + 8 values, one lane tile
    assert a._cache["lat"].shape == (c.n_block, 12, 4, 128)
    assert a.kv_bytes_per_token == c.n_block * 128 * 4
    row = np.zeros((8,), np.int32)
    row[:2] = (3, 5)
    a.prefill_chunk(np.arange(1, 9, dtype=np.int32), 0, 8, row)
    lat = np.asarray(a._cache["lat"])
    assert np.abs(lat[:, 3]).max() > 0 and np.abs(lat[:, 5]).max() > 0
    assert not lat[:, 3, :, c.latent_dim:].any()       # the lane padding
    assert not lat[:, 7].any()
    a.copy_block(3, 7)
    out = a.export_kv_blocks([3, 7, 5])
    assert out["lat"].shape == (c.n_block, 3, 4, 128)
    np.testing.assert_array_equal(out["lat"][:, 0], out["lat"][:, 1])
    b = _model(ref_params, num_blocks=12)
    b.import_kv_blocks([2, 9], out, start=1)
    back = b.export_kv_blocks([2, 9])
    np.testing.assert_array_equal(back["lat"], out["lat"][:, 1:])
    assert a.donated_cache_leaves() == 1


def test_spec_builds_the_architecture():
    spec = ("glm_moe_lite:tiny:slots=2,block=8,blocks=32,tables=6,"
            "chunk=8,buckets=16/32")
    assert is_llm_spec(spec)
    visits0 = counter("zoo_llm_moe_expert_visits_total").value
    eng = build_llm_engine(spec)
    try:
        assert isinstance(eng.model, PagedGlmMoeLiteModel)
        h = eng.submit(np.arange(1, 12, dtype=np.int32), 5)
        deadline = time.monotonic() + 120
        while not h.done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h.outcome == "ok" and len(h.tokens) == 5
        st = eng.stats()
        # 4 decode ticks of one live lane, 2 choices, 2 expert layers
        assert st["moe_rows"] == 4 * 2 * 2
        assert 4 * 2 <= st["moe_expert_visits"] <= st["moe_rows"]
        assert st["kv_bytes_per_token"] == 3 * 128 * 4
        assert st["compiles"]["decode"] == 1
        assert counter("zoo_llm_moe_expert_visits_total").value \
            - visits0 == st["moe_expert_visits"]
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="not built"):
        build_llm_engine(spec + ",kv=int8", start=False)


# --------------------- the Llama-shaped model, as before the refactoring

# tokens, bytes a token and cache layout of `llama:tiny:seed=3` as the
# parent commit (097c5f8) served them on this spec, before the skeleton
# was factored out of PagedLlamaModel (the int8 scale planes as PR 29
# holds them: a block's scales are one head-major row)
_BEFORE = {
    "f32": ([[162, 162, 162, 162, 162, 162, 0, 127],
             [183, 127, 207, 26, 26, 26, 133, 196],
             [251, 58, 183, 173, 157, 58, 183, 157]], 512,
            {"k": ((2, 64, 2, 8, 16), "float32"),
             "v": ((2, 64, 2, 8, 16), "float32")}),
    "bf16": ([[162, 162, 162, 162, 162, 162, 0, 127],
              [183, 127, 207, 26, 26, 26, 133, 196],
              [251, 58, 105, 85, 58, 224, 157, 58]], 256,
             {"k": ((2, 64, 2, 8, 16), "bfloat16"),
              "v": ((2, 64, 2, 8, 16), "bfloat16")}),
    "int8": ([[162, 162, 162, 162, 162, 162, 0, 127],
              [183, 127, 207, 26, 26, 26, 133, 196],
              [251, 58, 105, 234, 183, 157, 58, 183]], 160,
             {"k": ((2, 64, 2, 8, 16), "int8"),
              "ks": ((2, 64, 1, 16), "float32"),
              "v": ((2, 64, 2, 8, 16), "int8"),
              "vs": ((2, 64, 1, 16), "float32")}),
}


@pytest.mark.parametrize("kv", sorted(_BEFORE))
def test_llama_model_is_as_before_the_refactoring(kv):
    tokens, per_token, layout = _BEFORE[kv]
    eng = build_llm_engine(
        "llama:tiny:seed=3,slots=3,block=8,blocks=64,tables=8,chunk=8,"
        f"buckets=16/32,kv={kv}")
    try:
        hs = [eng.submit(np.arange(1 + i, 20 + 3 * i, dtype=np.int32), 8)
              for i in range(3)]
        deadline = time.monotonic() + 120
        while not all(h.done for h in hs) and time.monotonic() < deadline:
            time.sleep(0.01)
        m = eng.model
        assert m.kv_bytes_per_token == per_token
        assert {k: (v.shape, str(v.dtype))
                for k, v in m._cache.items()} == layout
        assert [h.tokens for h in hs] == tokens
        assert "moe_rows" not in eng.stats()
    finally:
        eng.stop()
