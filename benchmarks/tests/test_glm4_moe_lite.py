"""``glm-4.7-flash-d7``: its formulas against counts made by hand (the
arithmetic of ISSUE 28), its file against the catalog's keys, the
adapter's tree against the reference's, and the manifest with it in."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import formulas_glm4_moe_lite as fg  # noqa: E402
from harness import manifest  # noqa: E402

CELL = "glm-4.7-flash.longctx-closed"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "glm-4.7-flash-d7.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues(cfg):
    attn = (2048 * 768 + 768 * 20 * 256 + 2048 * 576
            + 512 * 20 * 448 + 20 * 256 * 2048)
    assert attn == 21_757_952 == fg.attention_params(cfg)
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184 == fg.expert_params(cfg)
    layer = attn + 2048 * 64 + 65 * expert
    assert layer == 635_305_984 == fg.expert_layer_params(cfg)
    dense = attn + 3 * 2048 * 10240
    assert dense == 84_672_512 == fg.dense_layer_params(cfg)
    assert 2 * fg.head_params(cfg) == 2 * 154_880 * 2048 == 634_388_480
    assert fg.matmul_params(cfg) == dense + 6 * layer + 634_388_480 \
        == 4_530_896_896                         # 9.06 GB at 2 bytes
    active = dense + 6 * (attn + 2048 * 64 + 5 * expert) + 154_880 * 2048
    assert fg.active_params(cfg) == active == 816_316_416
    assert fg.kv_bytes_per_token(cfg) == 7 * 576 * 2 == 8_064


def test_the_reference_holds_what_the_formulas_count(cfg):
    ref = manifest.reference_of(cfg)
    norms = 7 * (2 * 2048 + 768 + 512) + 2048 + 6 * 64
    assert ref.param_count(cfg) == fg.matmul_params(cfg) + norms


def test_decode_work_of_a_full_tick(cfg):
    census = {"decode_ticks": 10, "decode_tokens": 320,
              "attended_positions": 320 * 8448}
    assert fg.experts_read(cfg, 32) == pytest.approx(55.886, abs=1e-3)
    per_token = 2 * 816_316_416 + 2 * 20 * (2 * 512 + 64) * 8448 * 7
    assert fg.decode_flops(cfg, {}, census) == pytest.approx(
        320 * per_token)
    assert per_token == pytest.approx(4.206e9, rel=1e-3)
    tick = ((84_672_512 + 154_880 * 2048
             + 6 * (21_757_952 + 9_437_184 * (1 + 55.8855))) * 2
            + 6 * 2048 * 64 * 4)
    want = 10 * tick + 320 * 154_880 * 4 + 8064 * 320 * 8448
    assert fg.decode_bytes(cfg, {}, census) == pytest.approx(want,
                                                             rel=1e-6)
    assert want / 10 == pytest.approx(9.71e9, rel=2e-3)   # ~12 ms a tick
    assert fg.mla_decode_bytes(cfg, {}, census) == 8064 * 320 * 8448
    # the grouped product's floor: the visited experts of six layers
    assert fg.moe_gmm_bytes(cfg, {}, census) == pytest.approx(
        10 * 6 * 55.8855 * 9_437_184 * 2, rel=1e-6)
    assert fg.moe_gmm_bytes(cfg, {}, {"decode_ticks": 0}) == 0.0
    assert fg.decode_bytes(cfg, {}, {"decode_ticks": 0,
                                     "decode_tokens": 0,
                                     "attended_positions": 0}) == 0.0


def test_the_file_keeps_every_published_key(cfg):
    """The catalog row's ``config`` (model-configs guide) key by key;
    only ``num_hidden_layers`` is cut, and the manifest says so."""
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    differs = [k for k, v in published.items() if cfg.get(k) != v
               or k not in cfg]
    assert differs == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 47}
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == "glm-4.7-flash-d7")
    assert entry["reduced"] == ["num_hidden_layers"]
    eng = cfg["engine"]
    assert eng["num_blocks"] * eng["block_size"] == 294_912
    assert eng["max_blocks_per_seq"] * eng["block_size"] == 8192 + 512


def test_the_manifest_is_clean_with_the_cell_in(cfg):
    bench = manifest.load_benchmark()
    assert manifest.check_manifest(bench) == []
    cell = manifest.Cell(bench, CELL)
    assert cell.traffic["prompt_tokens"]["value"] == 8192
    assert cell.traffic["output_tokens"]["value"] == 512
    assert cell.traffic["clients"] * cell.traffic["start_every_tokens"] \
        == 512
    reports = {m["name"] for m in cell.per_layer()}
    assert {"decode_step_mfu.closed", "decode_step_roofline.closed",
            "decode_attend_share.closed", "decode_mlp_share.closed",
            "mla_decode_kernel_roofline.closed",
            "decode_moe_experts_share.closed",
            "decode_moe_route_share.closed", "tick_prefill_p50_ms.closed",
            "moe_expert_visits.closed"} <= reports
    assert "paged_decode_kernel_roofline.closed" not in reports
    assert {m["name"] for m in cell.end_to_end()} \
        == {"decode_tokens_per_s", "setup_s"}
    assert set(cell.limits) == {"served_logit_gap_max", "requests_failed"}


def test_the_adapter_hands_over_the_references_arrays(cfg):
    """The program's tree is the reference's under other names: the
    expert stacks are the same arrays, and ``kv_b`` split by head
    multiplies back to itself."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(BENCH, "rehearsal",
                           "config.glm-4.7-flash-d7.json")) as f:
        toy = {**cfg, **json.load(f)}
    ref_mod, adapter = manifest.reference_of(toy), manifest.adapter_of(toy)
    ref = ref_mod.make_params(2**31 + 3, toy)
    tree = adapter.to_program_tree(ref, toy)
    assert len(tree["blocks"]) == 2 and len(tree["lead"]) == 1
    assert tree["blocks"][1]["w_gate"] is ref["moe"][1]["experts_gate"]
    assert tree["blocks"][0]["router"].dtype == jnp.float32
    assert tree["blocks"][0]["w_down"].dtype == jnp.bfloat16
    nh, dn, dv = 4, 16, 16
    kv_b = np.asarray(ref["dense"][0]["kv_b"], np.float32)
    lead = tree["lead"][0]
    assert lead["w_uk"].shape == (nh, 32, dn)
    back = np.concatenate([np.asarray(lead["w_uk"], np.float32),
                           np.asarray(lead["w_uv"], np.float32)], axis=-1)
    np.testing.assert_array_equal(
        back.transpose(1, 0, 2).reshape(32, nh * (dn + dv)), kv_b)
    assert tree["blocks"][0]["w_uk"].shape == (nh, 32, dn)
