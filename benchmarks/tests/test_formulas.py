"""The FLOP and byte formulas against counts made by hand, and against
the ledger's own numbers (PR 22: 565 tokens/s over 32 slots)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import formulas  # noqa: E402


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_is_67_gflop_a_sample():
    cfg = _cfg("bert-base")
    # 3 * 12 * (8*768^2 + 4*768*3072 + 4*128*768) * 128
    by_hand = 3 * 12 * (4_718_592 + 9_437_184 + 393_216) * 128
    assert by_hand == 67_041_755_136
    assert formulas.bert_train_flops_per_sample(cfg, 128) == by_hand
    assert formulas.train_flops(cfg, {"seq_len": 128},
                                {"samples": 4096}) == by_hand * 4096
    # 109.5 M parameters, 16 bytes each with gradients and Adam
    assert formulas.bert_param_count(cfg) == pytest.approx(109.5e6,
                                                           rel=5e-3)


def test_mistral_d8_multiplies_with_1_88_billion_weights():
    cfg = _cfg("mistral-7b-v0.3-d8")
    block = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert block == 218_103_808
    by_hand = 8 * block + 4096 * 32768
    assert by_hand == 1_879_048_192
    assert formulas.decoder_matmul_params(cfg) == by_hand
    # with the embedding and the norms: 2.01 B held
    assert formulas.decoder_param_count(cfg) == pytest.approx(2.013e9,
                                                              rel=1e-3)
    # int8 K and V rows of 8 layers x 8 heads x 128, and their scales
    assert formulas.kv_bytes_per_token(cfg) == 2 * 8 * 8 * (128 + 4)


def test_decode_tick_floor_and_flops_against_the_ledger():
    cfg = _cfg("mistral-7b-v0.3-d8")
    # one tick of 32 lanes whose contexts sum to 36.8 k positions
    census = {"decode_ticks": 1, "decode_tokens": 32,
              "attended_positions": 36_800}
    b = formulas.decode_bytes(cfg, {}, census)
    weights = 1_879_048_192 * 2
    kv = 36_800 * 16_896
    logits = 32 * 32768 * 4
    assert b == weights + kv + logits
    floor_s = b / 819e9
    assert floor_s == pytest.approx(5.4e-3, rel=0.03)
    # the ledger's tick: 32 tokens / 565 tokens/s = 56.6 ms of device
    tick_s = 32 / 565.08
    assert 100 * floor_s / tick_s == pytest.approx(9.5, abs=0.7)
    f = formulas.decode_flops(cfg, {}, census)
    assert f / 32 == pytest.approx(3.9e9, rel=0.02)
    assert 100 * f / tick_s / 197e12 == pytest.approx(1.1, abs=0.1)
    # nothing decoded: nothing to divide by, and no share
    assert formulas.decode_flops(cfg, {}, {"decode_tokens": 0,
                                           "attended_positions": 0}) == 0.0


def test_every_formula_a_metric_names_exists_for_each_of_its_cells():
    """A bare name resolves in the formulas module of the cell's own
    configuration, ``module:function`` where it says, for ``formula``
    (``formula_share``) and ``work`` (``op_formula_share``) alike."""
    from harness import manifest
    bench = manifest.load_benchmark()
    seen = 0
    for w in bench["workloads"]:
        cell = manifest.Cell(bench, w["name"])
        for m in cell.per_layer():
            for key in ("formula", "work"):
                name = m.get("params", {}).get(key)
                if name is not None:
                    assert callable(manifest.formula(name, cell.config)), \
                        (cell.name, m["name"], name)
                    seen += 1
    assert seen >= 7
