"""``minicpm-sala-d12``: its formulas against counts made by hand (the
arithmetic of ISSUE 33), its file against the catalog's keys, the
adapter's tree against the reference's, and the manifest with it in."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import formulas_minicpm_sala as fs  # noqa: E402
from harness import manifest  # noqa: E402

CELL = "minicpm-sala.ctx32k-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "minicpm-sala-d12.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues(cfg):
    ffn = 3 * 4096 * 16384
    sparse = 4096 * (3 * 4096 + 2 * 256) + ffn
    assert sparse == 253_755_392 == fs.sparse_layer_params(cfg)
    light = 5 * 4096 * 4096 + ffn
    assert light == 285_212_672 == fs.lightning_layer_params(cfg)
    assert 2 * fs.head_params(cfg) == 2 * 73_448 * 4096 == 601_686_016
    assert fs.matmul_params(cfg) == 3 * sparse + 9 * light + 601_686_016 \
        == 3_929_866_240                          # 7.86 GB at 2 bytes
    assert fs.active_params(cfg) == 3_929_866_240 - 73_448 * 4096


def test_the_reference_holds_what_the_formulas_count(cfg):
    ref = manifest.reference_of(cfg)
    gains = 12 * (2 * 4096 + 2 * 128) + 9 * (128 + 32) + 4096
    assert ref.param_count(cfg) == fs.matmul_params(cfg) + gains


def test_decode_work_of_a_full_tick(cfg):
    """32 live slots at 33,800 tokens: 64 of 529 pages a head, the
    query's own half full; 2,111 compressed keys a head; nine states."""
    ctx = 33_800
    census = {"decode_ticks": 10, "decode_tokens": 320,
              "attended_positions": 320 * ctx}
    assert fs.pages_attended(cfg, ctx) == 64
    assert fs.pages_attended(cfg, 8191) == 128       # dense: every page
    assert fs.pages_attended(cfg, 8192) == 64
    rows = 63.5 * 64
    assert fs.rows_attended(cfg, ctx) == rows
    assert fs.rows_attended(cfg, 100) == 100
    assert fs.windows_scored(cfg, ctx) == (ctx - 32) // 16 + 1 == 2111
    assert fs.windows_scored(cfg, 8000) == 0
    kv = 320 * 3 * 2 * rows * 2 * 128 * 2
    assert fs.sparse_decode_bytes(cfg, {}, census) == kv
    states = 320 * 9 * 2 * 32 * 128 * 128 * 4
    assert fs.lightning_decode_bytes(cfg, {}, census) == states
    want = (10 * fs.active_params(cfg) * 2 + 320 * 73_448 * 4 + kv
            + 320 * 3 * 2 * 128 * 2 * 2111 + states)
    assert fs.decode_bytes(cfg, {}, census) == pytest.approx(want, rel=1e-9)
    assert want / 10 == pytest.approx(8.98e9, rel=2e-3)    # ~11 ms a tick
    per_token = (2 * fs.active_params(cfg)
                 + 3 * 2 * 32 * 128 * (2111 + 2 * rows)
                 + 9 * 4 * 32 * 128 * 128)
    assert fs.decode_flops(cfg, {}, census) == pytest.approx(320 * per_token)
    empty = {"decode_ticks": 0, "decode_tokens": 0, "attended_positions": 0}
    assert fs.decode_bytes(cfg, {}, empty) == 0.0
    assert fs.decode_flops(cfg, {}, empty) == 0.0
    assert fs.sparse_decode_bytes(cfg, {}, empty) == 0.0


def test_the_file_keeps_every_published_key(cfg):
    """The catalog row's ``config`` (model-configs guide) key by key;
    only the depth and the list of mixers are cut, to the published
    layers 9 to 20, and the manifest says so."""
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    published = row["config"]
    differs = [k for k, v in published.items()
               if k not in cfg or cfg[k] != v]
    assert sorted(differs) == ["mixer_types", "num_hidden_layers"]
    assert cfg["published"] == {
        "num_hidden_layers": 32, "mixer_types": published["mixer_types"]}
    assert cfg["mixer_types"] == published["mixer_types"][9:21]
    assert cfg["num_hidden_layers"] == 12 == len(cfg["mixer_types"])
    assert cfg["mixer_types"].count("minicpm4") == 3
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == "minicpm-sala-d12")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert entry["source"] == row["source_url"]
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64,
        "dense_len": 8192}
    assert "sparse_config" in cfg["assumed"]
    eng = cfg["engine"]
    assert eng["block_size"] == cfg["sparse_config"]["block_size"]
    assert eng["num_blocks"] == 32 * 552 == 17_664
    assert eng["max_blocks_per_seq"] * eng["block_size"] == 32768 + 2048


def test_the_manifest_is_clean_with_the_cell_in(cfg):
    bench = manifest.load_benchmark()
    assert manifest.check_manifest(bench) == []
    cell = manifest.Cell(bench, CELL)
    assert cell.chips == 1
    assert cell.traffic["prompt_tokens"]["value"] == 32768
    assert cell.traffic["output_tokens"]["value"] == 2048
    assert cell.traffic["clients"] == 32
    assert cell.traffic["clients"] * cell.traffic["start_every_tokens"] \
        == 2048
    assert cell.traffic["checked_requests"] == 2
    reports = {m["name"] for m in cell.per_layer()}
    assert {"decode_step_mfu.closed", "decode_step_roofline.closed",
            "decode_attend_share.closed", "decode_mlp_share.closed",
            "tick_prefill_p50_ms.closed",
            "sparse_decode_kernel_roofline.closed",
            "lightning_decode_kernel_roofline.closed",
            "decode_sparse_select_share.closed",
            "decode_lightning_share.closed",
            "sparse_pages_attended.closed"} <= reports
    assert not {"paged_decode_kernel_roofline.closed",
                "mla_decode_kernel_roofline.closed",
                "moe_expert_visits.closed"} & reports
    assert {m["name"] for m in cell.end_to_end()} \
        == {"decode_tokens_per_s", "setup_s"}
    assert set(cell.limits) == {"served_logit_gap_max", "requests_failed"}


def test_the_adapter_hands_over_the_references_arrays(cfg):
    """The program's tree is the reference's under other names: the
    same arrays, nothing copied."""
    import jax.numpy as jnp
    with open(os.path.join(BENCH, "rehearsal",
                           "config.minicpm-sala-d12.json")) as f:
        toy = {**cfg, **json.load(f)}
    ref_mod, adapter = manifest.reference_of(toy), manifest.adapter_of(toy)
    ref = ref_mod.make_params(2**31 + 3, toy)
    tree = adapter.to_program_tree(ref)
    assert len(tree["blocks"]) == 4
    assert tree["blocks"][1]["w_gate"] is ref["layers"][1]["gate"]
    assert tree["blocks"][0]["wk"].shape == (64, 2 * 16)
    assert tree["blocks"][1]["wk"].shape == (64, 4 * 16)
    assert "o_norm" in tree["blocks"][1] and "o_norm" not in tree["blocks"][0]
    assert tree["blocks"][0]["w_g"].dtype == jnp.bfloat16
    assert tree["blocks"][0]["q_norm"].dtype == jnp.float32
    assert tree["slopes"].shape == (2, 4)
    assert float(tree["slopes"][0, 3]) == 2.0 ** -8
    assert tree["head"] is ref["head"]
