"""BERT sequence classification (``reference/bert_classifier.py``) as
the repo trains it (``bench.py`` ``bench_bert``, ``chip_smoke.py``
``_bert_model``): a Keras ``Sequential`` of ``BERT``, token 0 and
``Dense``, compiled with ``AdamWeightDecay``, from the configuration's
file."""

from __future__ import annotations

# reference leaf -> program leaf (the Sequential's layer names)
_BERT, _HEAD = "000_bert", "002_dense"
# the loss the job compiles with (a test plants a fault here)
LOSS = "sparse_categorical_crossentropy_from_logits"


def to_program_tree(ref: dict) -> dict:
    top = {k: v for k, v in ref.items()
           if k not in ("layers", "cls_w", "cls_b")}
    top["blocks"] = ref["layers"]
    return {_BERT: top, "001_lambda": {},
            _HEAD: {"W": ref["cls_w"], "b": ref["cls_b"]}}


def from_program_tree(prog: dict) -> dict:
    out = {k: v for k, v in prog[_BERT].items() if k != "blocks"}
    out["layers"] = prog[_BERT]["blocks"]
    out["cls_w"], out["cls_b"] = prog[_HEAD]["W"], prog[_HEAD]["b"]
    return out


def model(cfg: dict, seq: int):
    """The compiled Keras model for sequences of ``seq`` tokens."""
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import BERT, Dense, Lambda
    from zoo_tpu.pipeline.api.keras.optimizers import AdamWeightDecay

    t = cfg["train"]
    hidden = cfg["hidden_size"]
    m = Sequential()
    m.add(BERT(vocab=cfg["vocab_size"], hidden_size=hidden,
               n_block=cfg["num_hidden_layers"],
               n_head=cfg["num_attention_heads"], seq_len=seq,
               intermediate_size=cfg["intermediate_size"],
               hidden_p_drop=cfg["hidden_dropout_prob"],
               attn_p_drop=cfg["attention_probs_dropout_prob"],
               remat=t["remat"],
               max_position_len=cfg["max_position_embeddings"],
               token_type_vocab=cfg["type_vocab_size"],
               initializer_range=cfg["initializer_range"],
               input_shape=(seq,)))
    m.add(Lambda(lambda h: h[:, 0], output_shape=(hidden,)))
    m.add(Dense(t["num_labels"]))
    m.compile(optimizer=AdamWeightDecay(
        lr=t["learning_rate"], beta_1=t["beta_1"], beta_2=t["beta_2"],
        epsilon=t["epsilon"], weight_decay=t["weight_decay"]),
        loss=LOSS, dtype_policy=cfg["precision"]["policy"])
    return m
