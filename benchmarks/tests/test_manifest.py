"""``BENCHMARK.json`` against the contract's rules on names, units and
references, and the data files each cell and metric points at."""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from harness import manifest, readers  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return manifest.load_benchmark()


def test_the_manifest_as_committed_has_no_fault(bench):
    assert manifest.check_manifest(bench) == []


def test_every_cell_finds_its_files_and_every_metric_its_reader(bench):
    for w in bench["workloads"]:
        cell = manifest.Cell(bench, w["name"])
        assert cell.config["kind"] and cell.traffic
        assert cell.limits, f"{cell.name}: nothing decides `correct`"
        for lim in cell.limits.values():
            assert set(lim) >= {"limit"}
        assert {m["name"] for m in cell.end_to_end()} >= {"setup_s"}
        layers = cell.per_layer()
        assert layers
        for m in layers:
            assert callable(readers.resolve(m["reader"], readers.READERS))
            assert cell.name in m.get("workloads", [cell.name])


def test_one_four_chip_cell_at_most_and_setup_bound(bench):
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup


@pytest.mark.parametrize("mutate, said", [
    (lambda b: b["workloads"][0].update(name="bad name"), "bad name"),
    (lambda b: b["workloads"][0].update(name="x" * 65), "bad name"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
    (lambda b: b["end_to_end"][0].update(unit="µs"), "bad unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["end_to_end"][0].update(source="program_span"), "source"),
    (lambda b: b["per_layer"][0].update(why="because"), "keys"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"), "moves"),
    (lambda b: b["per_layer"][0].update(
        workloads=["bert-base.fit-resident"]), "do not report"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="twin")),
     "listed twice"),
    (lambda b: b["workloads"][0].update(chips=2), "chips"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]],
     "four-chip"),
    (lambda b: b["workloads"][0].update(why="a\tb"), "one line"),
    (lambda b: b.update(run_seconds=52), "run_seconds"),
    (lambda b: b.update(extra=1), "top-level"),
    (lambda b: b["configs"][0].update(file="zoo_tpu/x.json"),
     "outside paths"),
])
def test_a_broken_manifest_is_found(bench, mutate, said):
    broken = copy.deepcopy(bench)
    mutate(broken)
    faults = manifest.check_manifest(broken)
    assert any(said in f for f in faults), faults


def test_config_files_keep_the_published_widths(bench):
    """No width may be reduced: the files hold the sources' numbers."""
    with open(os.path.join(BENCH, "configs", "bert-base.json")) as f:
        bert = json.load(f)
    assert (bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["vocab_size"], bert["max_position_embeddings"]) == (
                768, 12, 12, 3072, 30522, 512)
    with open(os.path.join(BENCH, "configs",
                           "mistral-7b-v0.3-d8.json")) as f:
        mis = json.load(f)
    assert (mis["hidden_size"], mis["num_attention_heads"],
            mis["num_key_value_heads"], mis["head_dim"],
            mis["intermediate_size"], mis["vocab_size"],
            mis["rope_theta"], mis["rms_norm_eps"]) == (
                4096, 32, 8, 128, 14336, 32768, 1e6, 1e-5)
    assert mis["num_hidden_layers"] == 8
    assert mis["published"]["num_hidden_layers"] == 32
    by_name = {c["name"]: c for c in bench["configs"]}
    assert by_name["mistral-7b-v0.3-d8"]["reduced"] == ["num_hidden_layers"]


def test_a_formula_resolves_in_the_configurations_own_module():
    import formulas
    import formulas_kernels
    assert manifest.formula("decode_bytes", {}) is formulas.decode_bytes
    assert manifest.formula("decode_bytes", {"formulas": "formulas"}) \
        is formulas.decode_bytes
    # another module under benchmarks/ that a configuration names: a
    # bare name is looked up there and nowhere else
    cfg = {"formulas": "formulas_kernels"}
    assert manifest.formula("paged_decode_bytes", cfg) \
        is formulas_kernels.paged_decode_bytes
    with pytest.raises(KeyError):
        manifest.formula("decode_bytes", cfg)
    # ``module:function`` is that function whatever the cell
    assert manifest.formula("formulas:kv_bytes_per_token", cfg) \
        is formulas.kv_bytes_per_token


def test_every_configuration_names_its_adapter_reference_and_formulas():
    bench = manifest.load_benchmark()
    assert {c["name"] for c in bench["configs"]} >= {
        "mistral-7b-v0.3-d8", "bert-base"}
    for c in bench["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        adapter = manifest.adapter_of(cfg)
        for duty in manifest.ADAPTER_DUTIES[cfg["kind"]]:
            assert callable(getattr(adapter, duty)), (c["name"], duty)
        ref = manifest.reference_of(cfg)
        wanted = {"serve_decoder": ("make_params", "served_gaps", "free"),
                  "train_classifier": ("make_params", "make_data",
                                       "follow")}[cfg["kind"]]
        for fn in wanted:
            assert callable(getattr(ref, fn)), (c["name"], fn)
        assert manifest.formulas_of(cfg).__name__ == cfg.get(
            "formulas", "formulas")


def test_an_adapter_that_lacks_a_duty_is_refused():
    with pytest.raises(AttributeError, match="lacks"):
        manifest.adapter_of({"kind": "train_classifier",
                             "adapter": "llama_paged"})


@pytest.mark.parametrize("path", ["harness/serve_cell.py",
                                  "harness/train_cell.py", "run.py"])
@pytest.mark.parametrize("word", ["LlamaConfig", "PagedLlamaModel", "BERT(",
                                  "head_dim", "intermediate_size",
                                  "zoo_tpu.pipeline.api.keras.layers"])
def test_the_harness_names_no_architecture(path, word):
    with open(os.path.join(BENCH, path)) as f:
        assert word not in f.read()
