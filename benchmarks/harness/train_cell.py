"""Driving a training job: ``init_orca_context`` ->
``Estimator.from_keras`` -> ``fit`` on device-resident arrays (the entry
``chip_smoke.py``'s train leg shows), and the comparison of its first
call with the plain reference.

The timed call is the whole-epoch executable: one dispatch runs every
step of an epoch and returns the sum of the losses and the state after
the last step. There is no state after one step to read without
building a second program, so the reference follows every step of the
first call (``followed_steps``) and the two are compared after it: the
epoch's mean loss, Adam's first moment (the running mean of the
gradients as the optimizer got them) and the parameters' change, the
last two as norms by the worst leaf.
"""

from __future__ import annotations

import gc
import statistics
import time
from typing import Dict, Optional

import numpy as np

from .spans import Recorder

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


def _build_model(cfg: dict, seq: int):
    """BERT as the repo trains it (``bench.py`` ``bench_bert``,
    ``chip_smoke.py`` ``_bert_model``), from the configuration's file."""
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


def _leaf_norms(tree) -> Dict[str, float]:
    """Norm of every leaf, keyed by its path, in one jitted call."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))) for x in xs])(
            [x for _, x in flat])
    return {jax.tree_util.keystr(path): float(n)
            for (path, _), n in zip(flat, norms)}


def _diff(a, b):
    import jax
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


class TrainedClassifier:
    """The one object that set-up builds, drives through its first call
    and hands to the window: the estimator with its compiled epoch and
    its state."""

    def __init__(self, cell, seed: int, devs, rec: Recorder, ref_mod):
        import jax
        from zoo_tpu.orca import init_orca_context
        from zoo_tpu.orca.learn.keras import Estimator

        self.cell, self.seed, self.devs, self.rec = cell, seed, devs, rec
        self.ref_mod, self.cfg, self.tr = ref_mod, cell.config, cell.traffic
        self.batch = int(self.tr["batch_per_chip"]) * len(devs)
        self.steps = int(self.tr["steps_per_epoch"])
        self.rows = self.batch * self.steps
        init_orca_context("local", devices=list(devs),
                          mesh_axes=self.tr.get("mesh_axes"))
        with rec.span("setup.weights"):
            p0 = ref_mod.make_params(seed, self.cfg)
            model = _build_model(self.cfg, int(self.tr["seq_len"]))
            model.params = to_program_tree(p0)
        with rec.span("setup.data"):
            ids, y = ref_mod.make_data(seed, self.cfg, self.steps,
                                       self.batch, int(self.tr["seq_len"]))
            self.host_data = (ids, y)
            mesh = model._mesh()
            if mesh is not None and mesh.size > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P
                rep = NamedSharding(mesh, P())
                self.data = {"x": jax.device_put(ids, rep),
                             "y": jax.device_put(y, rep)}
            else:
                self.data = {"x": jax.device_put(ids, devs[0]),
                             "y": jax.device_put(y, devs[0])}
        self.model = model
        self.est = Estimator.from_keras(model)
        self.first: Optional[dict] = None

    def fit(self, epochs: int):
        with self.rec.span("fit.call"):
            return self.est.fit(self.data, epochs=epochs,
                                batch_size=self.batch,
                                shuffle=bool(self.tr["shuffle"]),
                                max_failure_retries=0)["loss"]

    def first_call(self):
        """The first epoch through the window's own call, and what the
        program's state says after it (scalars only; the arrays stay on
        the device)."""
        import jax
        losses = self.fit(1)
        params = from_program_tree(self.model.params)
        mu = from_program_tree(self.model._opt_state[0].mu)
        p0 = self.ref_mod.make_params(self.seed, self.cfg)
        self.first = {"loss": float(losses[0]),
                      "moment": _leaf_norms(mu),
                      "change": _leaf_norms(_diff(params, p0))}
        del p0
        jax.block_until_ready(self.model.params)
        return self.first

    def close(self):
        import jax
        from zoo_tpu.orca import stop_orca_context
        leaves = jax.tree_util.tree_leaves(
            (self.model.params, self.model._opt_state, self.data))
        self.model.params = self.model._opt_state = None
        self.data = None
        self.model._drop_train_caches()
        for leaf in leaves:
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()
        stop_orca_context()
        gc.collect()


# ---------------------------------------------------------------- `correct`

def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leave_out=()) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    keys = [k for k in ref if k not in leave_out]
    floor = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keys)


def reference_first_call(cell, seed: int, devs, ref_mod, host_data,
                         lower: bool = False, rows: float = 1.0) -> dict:
    """The plain reference through the same steps on the same rows."""
    import jax
    cfg, tr = cell.config, cell.traffic
    batch = int(tr["batch_per_chip"]) * len(devs)
    steps = int(tr["followed_steps"])
    p0 = ref_mod.make_params(seed, cfg)
    losses, p_end, mu, g1 = ref_mod.follow(
        p0, cfg, host_data[0], host_data[1], steps, batch, lower=lower,
        rows=rows, devices=list(devs) if len(devs) > 1 else None)
    if len(devs) > 1:
        p0 = jax.device_put(p0, jax.tree_util.tree_leaves(p_end)[0].sharding)
    out = {"loss": float(np.mean(np.asarray(losses))),
           "moment": _leaf_norms(mu),
           "change": _leaf_norms(_diff(p_end, p0)),
           "first_grad": {jax.tree_util.keystr(k): float(v) for k, v in
                          jax.tree_util.tree_flatten_with_path(g1)[0]}}
    for leaf in jax.tree_util.tree_leaves((p0, p_end, mu)):
        leaf.delete()
    return out


def compare_first_call(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct`` for a training cell."""
    g = ref["first_grad"]
    nought = statistics.median(g.values()) * 1e-3
    still = [k for k, v in g.items() if v < nought]
    return {
        "epoch1_loss_gap": abs(prog["loss"] - ref["loss"])
        / abs(ref["loss"]),
        "moment_norm_gap_worst": worst_leaf_gap(prog["moment"],
                                                ref["moment"], still),
        "param_change_gap_worst": worst_leaf_gap(prog["change"],
                                                 ref["change"], still),
    }
