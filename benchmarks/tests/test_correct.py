"""What decides ``correct`` is shown to fail.

Each test skips the harness's look for a chip (``--rehearse-cpu``: toy
widths on the CPU) and drives the rest of a run. The control tests put
the reference in the next precision down (float8 operands) in the
program's place; the fault tests break the timed path underneath: a
step that returns its state unchanged, half of the batch left out with
the mean taken over the rest, the exchange between chips left out (one
chip's rows alone), a token altered where it is produced. Every one
has to come out as not correct by the cell's own limits.
"""

import importlib
import json

import numpy as np
import pytest

import run as runner
from adapters import bert_keras
from harness import manifest, serve_cell, train_cell

FOUR = "bert-base.fit-4chip"      # laid over the manifest by a fixture
TRAIN = ["bert-base.fit-resident", FOUR]
SERVE = ["mistral-7b.decode-closed", "mistral-7b.chat-open"]


def _drive(capsys, workload, seed=11, seconds=1.0, control=0, trace=0):
    code = runner.main(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace),
                        "--control", str(control), "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True
    assert list(result)[-1] == "compared"
    return result


@pytest.mark.parametrize("workload", TRAIN + SERVE)
def test_a_sound_run_is_correct(capsys, training_cells, workload):
    result = _drive(capsys, workload)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    for c in result["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]


# ------------------------------------------------------------- training

def _plant_loss(monkeypatch, share):
    from zoo_tpu.pipeline.api.keras import objectives
    whole = objectives.get_loss(bert_keras.LOSS)

    def part(y_true, logits):
        keep = max(1, int(y_true.shape[0] * share))
        return whole(y_true[:keep], logits[:keep])

    part._handles_low_precision = True
    monkeypatch.setattr(bert_keras, "LOSS", part)


@pytest.mark.parametrize("workload", TRAIN)
def test_half_of_the_batch_left_out_is_not_correct(
        capsys, monkeypatch, training_cells, workload):
    _plant_loss(monkeypatch, 0.5)
    result = _drive(capsys, workload)
    assert result["correct"] is False, result["compared"]


def test_the_exchange_between_chips_left_out_is_not_correct(
        capsys, monkeypatch, training_cells):
    _plant_loss(monkeypatch, 0.25)         # one chip's rows of four
    result = _drive(capsys, FOUR)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("workload", TRAIN)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, training_cells, workload):
    import jax
    import jax.numpy as jnp
    real_fit = train_cell.TrainedClassifier.fit

    def fit(self, epochs, data=None):
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        params, opt = copy(self.model.params), self.model._opt_state
        opt = copy(opt) if opt is not None else None
        out = real_fit(self, epochs, data)
        self.model.params = params
        if opt is not None:
            self.model._opt_state = opt
        return out

    monkeypatch.setattr(train_cell.TrainedClassifier, "fit", fit)
    result = _drive(capsys, workload)
    assert result["correct"] is False
    assert result["compared"]["param_change_gap_worst"]["value"] \
        == pytest.approx(1.0, abs=0.05)


@pytest.mark.parametrize("workload", TRAIN)
def test_the_control_of_a_training_cell_is_not_correct(
        capsys, training_cells, workload):
    result = _drive(capsys, workload, control=1)
    also = result["also_read"]
    control = {k[len("control_"):]: v for k, v in also.items()
               if k.startswith("control_")}
    cell = manifest.Cell(manifest.load_benchmark(), workload)
    runner._rehearsal(cell)               # the limits the run was held to
    ok, compared, _ = runner._judge(control, cell.limits)
    assert ok is False, compared
    # float8 parts from the reference in the first gradient itself; the
    # norms after an epoch do not tell it from bfloat16 (PERF.md)
    far = compared["first_grad_distance_worst"]
    assert far["value"] > far["limit"] > \
        result["compared"]["first_grad_distance_worst"]["value"]
    for tag in ("half_batch",) + (("no_exchange",)
                                  if cell.chips > 1 else ()):
        fault = {k[len(f"fault_{tag}_"):]: v for k, v in also.items()
                 if k.startswith(f"fault_{tag}_")}
        assert runner._judge(fault, cell.limits)[0] is False, (tag, fault)


# -------------------------------------------------------------- serving

@pytest.mark.parametrize("workload", SERVE + ["toy-fused.decode-closed"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, second_architectures, workload):
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    real_read = PagedLlamaModel.read_tokens
    calls = [0]

    def read_tokens(self, batch):
        toks = np.array(real_read(self, batch))
        calls[0] += 1
        if calls[0] % 7 == 0:
            toks[...] = toks ^ 1          # stays inside the vocabulary
        return toks

    monkeypatch.setattr(PagedLlamaModel, "read_tokens", read_tokens)
    result = _drive(capsys, workload, seconds=3.0)
    assert result["correct"] is False, result["compared"]
    gap = result["compared"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", SERVE)
def test_the_control_of_a_serving_cell_is_not_correct(capsys, workload):
    result = _drive(capsys, workload, seconds=3.0, control=1)
    assert result["correct"] is True, result["compared"]
    limit = result["compared"]["served_logit_gap_max"]["limit"]
    assert result["also_read"]["control_logit_gap_max"] > limit


def test_a_failed_request_is_not_correct(capsys, monkeypatch):
    real_send = serve_cell.ServedDecoder.send

    def send(self, req):
        if req.index == 3:
            raise ConnectionError("planted")
        return real_send(self, req)

    monkeypatch.setattr(serve_cell.ServedDecoder, "send", send)
    result = _drive(capsys, "mistral-7b.chat-open", seconds=3.0)
    assert result["failed"] >= 1 and result["correct"] is False
