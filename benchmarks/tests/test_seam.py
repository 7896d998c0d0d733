"""The seam holds: a second architecture of each kind arrives by files
alone. Its configuration, adapter, reference, formulas, rehearsal sizes
and limits live under ``tests/data/`` (the fixture
``second_architectures`` lays them over the manifest); the harness, the
drivers and ``formulas.py`` are the ones every accepted cell runs on.
And the harness's own trap is gone: a collection that begins inside the
recorder's lock no longer waits for itself."""

import gc
import json
import os
import threading

import pytest

import run as runner
from harness import manifest, readers, trace as tr
from harness.spans import GcWatch, Recorder

HERE = os.path.dirname(os.path.abspath(__file__))
SECOND = ["toy-fused.decode-closed", "toy-fused.chat-open",
          "toy-bag.fit-resident"]
LLAMA_KEYS = {"hidden_size", "head_dim", "num_key_value_heads",
              "num_attention_heads", "intermediate_size",
              "num_hidden_layers"}


def _config(name):
    with open(os.path.join(HERE, "data", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", SECOND)
def test_a_second_architecture_rehearses_from_files_of_its_own(
        capsys, second_architectures, workload, trace):
    assert workload in second_architectures
    code = runner.main(["--workload", workload, "--seed", str(2**31 + 5),
                        "--seconds", "2", "--trace", str(trace),
                        "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    cell = manifest.Cell(manifest.load_benchmark(), workload)
    assert not LLAMA_KEYS & set(cell.config)
    want = cell.per_layer() if trace else cell.end_to_end()
    assert want and set(result["metrics"]) <= {m["name"] for m in want}
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in want}
    if cell.config["kind"] == "serve_decoder":
        census = json.loads(next(ln for ln in lines
                                 if ln.startswith("census: "))[8:])
        assert census["decode_ticks"] > 0 and census["prefill_ticks"] > 0
        gap = result["compared"]["served_logit_gap_max"]
        assert gap["value"] is not None and gap["value"] <= gap["limit"]


def test_the_second_architectures_bring_their_own_modules(
        second_architectures):
    for name, kind in (("toy-fused", "serve_decoder"),
                       ("toy-bag", "train_classifier")):
        cfg = _config(name)
        assert cfg["kind"] == kind and not LLAMA_KEYS & set(cfg)
        for mod in (manifest.adapter_of(cfg), manifest.reference_of(cfg),
                    manifest.formulas_of(cfg)):
            assert os.path.dirname(mod.__file__).startswith(
                os.path.join(HERE, "data"))


def test_a_bare_formula_is_the_configurations_own(second_architectures):
    """One metric file, two architectures: ``decode_flops`` of
    ``decode_step_mfu.closed`` is ``formulas.py``'s for Mistral and
    ``formulas_fused.py``'s for the second decoder, through the reader
    that the traced run uses."""
    import formulas
    import formulas_fused
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        small = tr.Trace.from_json(json.load(f))
    bench = manifest.load_benchmark()
    census = {"decode_ticks": 2, "decode_tokens": 8,
              "attended_positions": 400}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    read = {}
    for cell_name, mod in (("mistral-7b.decode-closed", formulas),
                           ("toy-fused.decode-closed", formulas_fused)):
        cell = manifest.Cell(bench, cell_name)
        metric = next(m for m in cell.per_layer()
                      if m["name"] == "decode_step_mfu.closed")
        assert metric["params"]["formula"] == "decode_flops"
        ctx = readers.Context(
            rec=None, t0=0.0, t1=1.0, cfg=cell.config, traffic={}, chips=1,
            peaks=peaks, facts={}, counters={}, trace=small,
            traced=(0.0, 1.0), traced_census=dict(census))
        # the small trace's executables are ``jit_epoch_fn``: 2 calls,
        # 40 ms on the fuller device
        params = dict(metric["params"], module="epoch_fn")
        read[cell_name] = readers.READERS["formula_share"](ctx, params)
        by_hand = 100.0 * mod.decode_flops(cell.config, {}, census) \
            / 0.040 / 1e12
        assert read[cell_name] == pytest.approx(by_hand)
    # 4 layers of 256 x (8 + 2*2) x 32 + 8*32 x 256 + 3 x 256 x 512 and
    # the head 256 x 4096
    cfg = _config("toy-fused")
    assert formulas_fused.matmul_params(cfg) == 4 * 557_056 + 1_048_576
    assert read["toy-fused.decode-closed"] != read["mistral-7b.decode-closed"]


def test_a_collection_inside_the_recorders_lock_does_not_wait_for_itself():
    """PERF.md §7 of PR 26: ``add_span`` takes the recorder's lock, the
    ``with`` allocates, and the collection that this starts calls the
    watch's hook on the same thread; a hook that took the lock hung
    here at call 623, every time."""
    rec = Recorder()
    watch = GcWatch(rec).start()
    done = []

    def many():
        for v in range(3000):
            rec.add_span("x", 0.0, float(v))
        gc.collect()
        done.append(True)

    t = threading.Thread(target=many, daemon=True)
    try:
        t.start()
        t.join(timeout=30)
        assert done and not t.is_alive(), "add_span waits for itself"
    finally:
        watch.stop()
    assert len(rec.spans["x"]) == 3000
    said = watch.said(0.0, float("inf"))
    assert said["gen0"] + said["gen1"] + said["gen2"] >= 1
    assert "host.gc2" in rec.spans      # merged when the watch stopped
