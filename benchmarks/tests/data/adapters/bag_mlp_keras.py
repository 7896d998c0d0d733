"""The tests' second trained architecture
(``reference/bag_classifier.py``) as a Keras ``Sequential`` of the
program: ``Embedding``, the mean over the sequence, ``Dense`` (tanh) and
``Dense``, compiled with ``AdamWeightDecay``."""

from __future__ import annotations

_TABLE, _HIDDEN, _OUT = "000_embedding", "002_dense", "003_dense"


def to_program_tree(ref: dict) -> dict:
    pair = lambda p: {"W": p["w"], "b": p["b"]}
    return {_TABLE: {"E": ref["table"]}, "001_lambda": {},
            _HIDDEN: pair(ref["hidden"]), _OUT: pair(ref["out"])}


def from_program_tree(prog: dict) -> dict:
    pair = lambda p: {"w": p["W"], "b": p["b"]}
    return {"table": prog[_TABLE]["E"], "hidden": pair(prog[_HIDDEN]),
            "out": pair(prog[_OUT])}


def model(cfg: dict, seq: int):
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import Dense, Embedding, Lambda
    from zoo_tpu.pipeline.api.keras.optimizers import AdamWeightDecay

    t = cfg["train"]
    m = Sequential()
    m.add(Embedding(cfg["tokens"], cfg["width"], input_shape=(seq,)))
    m.add(Lambda(lambda h: h.mean(axis=1), output_shape=(cfg["width"],)))
    m.add(Dense(cfg["inner"], activation="tanh"))
    m.add(Dense(cfg["classes"]))
    m.compile(optimizer=AdamWeightDecay(
        lr=t["learning_rate"], beta_1=t["beta_1"], beta_2=t["beta_2"],
        epsilon=t["epsilon"], weight_decay=t["weight_decay"]),
        loss="sparse_categorical_crossentropy_from_logits",
        dtype_policy=cfg["precision"]["policy"])
    return m
