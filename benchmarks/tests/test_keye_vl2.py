"""``keye-vl-2.0-30b-a3b-d4``: its formulas against counts made by hand,
its file against the published ``config.json``, the adapter's tree
against the reference's, and the manifest with it in."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import formulas_keye_vl2 as fk  # noqa: E402
from harness import manifest  # noqa: E402

CELL = "keye-vl-2.0.doc32k-closed"
# the published config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "keye-vl-2.0-30b-a3b-d4.json")) as f:
        return json.load(f)


def test_parameter_counts_by_hand(cfg):
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    assert attn == 18_874_368 == fk.attention_params(cfg)
    index = 2048 * (16 * 64 + 64 + 16)
    assert index == 2_260_992 == fk.indexer_params(cfg)
    assert fk.router_params(cfg) == 2048 * 128 == 262_144
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 == fk.expert_params(cfg)
    layer = attn + index + 262_144 + 128 * expert
    assert layer == 625_377_280 == fk.layer_params(cfg)    # 1.251 GB
    head = 151_936 * 2048
    assert fk.matmul_params(cfg) == 4 * layer + 2 * head \
        == 3_123_838_976                           # 6.25 GB at 2 bytes
    assert fk.active_params(cfg) == 4 * (attn + index + 262_144
                                         + 8 * expert) + head


def test_the_reference_holds_what_the_formulas_count(cfg):
    ref = manifest.reference_of(cfg)
    gains = 4 * (2 * 2048 + 2 * 128 + 2 * 64) + 2048
    assert ref.param_count(cfg) == fk.matmul_params(cfg) + gains


def test_decode_work_of_a_full_tick(cfg):
    """16 live slots at 33,300 tokens: 2,048 of them attended a layer,
    all 33,300 index keys scored; 2,048 B of K and V and 128 B of index
    key a token."""
    ctx = 33_300
    census = {"decode_ticks": 10, "decode_tokens": 160,
              "attended_positions": 160 * ctx}
    assert fk.selected(cfg, ctx) == 2048
    assert fk.selected(cfg, 1000) == 1000
    assert fk.scored(cfg, ctx) == ctx
    assert fk.scored(cfg, 2048) == 0
    assert fk.kv_row_bytes(cfg) == 2048 and fk.index_key_bytes(cfg) == 128
    kv = 160 * 4 * 2048 * 2048
    assert fk.dsa_decode_bytes(cfg, {}, census) == kv
    lanes = 16
    read = 128 * (1 - (1 - 8 / 128) ** lanes)
    assert 82 < read < 83
    per_tick = 4 * ((18_874_368 + 2_260_992 + read * 4_718_592) * 2
                    + 262_144 * 4) + 151_936 * 2048 * 2
    want = (10 * per_tick + 160 * 151_936 * 4
            + 160 * 4 * ctx * 128 + kv)
    assert fk.decode_bytes(cfg, {}, census) == pytest.approx(want, rel=1e-9)
    per_token = (2 * fk.active_params(cfg)
                 + 4 * (2 * 16 * 64 * ctx + 4 * 32 * 128 * 2048))
    assert fk.decode_flops(cfg, {}, census) == pytest.approx(160 * per_token)
    empty = {"decode_ticks": 0, "decode_tokens": 0, "attended_positions": 0}
    assert fk.decode_bytes(cfg, {}, empty) == 0.0
    assert fk.decode_flops(cfg, {}, empty) == 0.0
    assert fk.dsa_decode_bytes(cfg, {}, empty) == 0.0


def test_the_file_keeps_every_published_key(cfg):
    """The published keys one by one; only the depth is cut, and the
    manifest says so."""
    differs = [k for k, v in PUBLISHED.items()
               if k not in cfg or cfg[k] != v]
    assert differs == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 4
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b-d4")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == SOURCE
    for key in ("qk_norm", "indexer", "chunks", "text_only"):
        assert key in cfg["assumed"]
    eng = cfg["engine"]
    assert eng["max_blocks_per_seq"] * eng["block_size"] == 32768 + 1024
    assert eng["num_blocks"] >= 16 * eng["max_blocks_per_seq"] + 1


def test_the_adapter_names_every_leaf_of_the_reference(cfg):
    adapter = manifest.adapter_of(cfg)
    ref = manifest.reference_of(cfg)
    layer = ref._layer.__wrapped__ if hasattr(ref._layer, "__wrapped__") \
        else ref._layer
    import jax
    items = dict(ref._cfg_items(cfg))
    shapes = jax.eval_shape(lambda k: layer(k, items),
                            jax.random.PRNGKey(0))
    assert set(shapes) == set(adapter._LAYER)


def test_the_manifest_is_clean_with_the_cell_in(cfg):
    bench = manifest.load_benchmark()
    assert manifest.check_manifest(bench) == []
    cell = manifest.Cell(bench, CELL)
    assert cell.chips == 1
    assert cell.traffic["prompt_tokens"]["value"] == 32768
    assert cell.traffic["output_tokens"]["value"] == 1024
    assert cell.traffic["clients"] == cfg["engine"]["num_slots"] == 16
    assert cell.traffic["clients"] * cell.traffic["start_every_tokens"] \
        == 1024
    names = {m["name"] for m in cell.per_layer()}
    assert {"decode_dsa_share.closed", "chunk_dsa_share.closed",
            "decode_dsa_select_share.closed",
            "dsa_decode_kernel_roofline.closed",
            "dsa_tokens_selected.closed"} <= names
    assert "moe_gmm_kernel_roofline.closed" not in names
    assert "mla_decode_kernel_roofline.closed" not in names
