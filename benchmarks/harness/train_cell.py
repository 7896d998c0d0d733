"""Driving a training job: ``init_orca_context`` ->
``Estimator.from_keras`` -> ``fit`` on device-resident arrays (the entry
``chip_smoke.py``'s train leg shows), and the comparison of its first
call with the plain reference. The compiled Keras model and the two
mappings between the reference's tree and the program's are the
configuration's adapter's (``adapters/<name>.py``).

The timed call is the whole-epoch executable: one dispatch runs every
step of an epoch and returns the sum of the losses and the state after
the last step. What parts two precisions after all of an epoch's steps
is set by the course the loss took, not by the arithmetic (PERF.md), so
the numbers that have to tell a precision apart are read off the first
steps: set-up drives the one model through ``first_steps`` epochs of one
batch each (the same ``fit``, the same feed, the same step under a scan
of length one) and reads each step's loss, the first gradient as the
optimizer got it (Adam's first moment after one step, kept whole on
the host: its distance from the reference's, leaf by leaf, is what
rounding moves at first order, where a norm moves at second order only)
and the parameters' change after them. Then the first whole-epoch call follows
(``followed_steps``), through the window's own executable, and the
parameters' change after it is compared too: that is what a fault of
the timed path moves. The reference follows all of these steps in one
pass. Norms are compared by the worst leaf.
"""

from __future__ import annotations

import gc
import statistics
from typing import Dict, Optional

import numpy as np

from . import manifest
from .spans import Recorder


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


def _to_host(tree, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """Every leaf as a float32 array on the host, keyed by its path."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path):
            np.asarray(x, dtype=np.float32) * np.float32(scale)
            for path, x in flat}


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
        self.adapter = manifest.adapter_of(self.cfg)
        self.batch = int(self.tr["batch_per_chip"]) * len(devs)
        self.steps = int(self.tr["steps_per_epoch"])
        self.rows = self.batch * self.steps
        self.first_steps = int(self.tr.get("first_steps", 0))
        init_orca_context("local", devices=list(devs),
                          mesh_axes=self.tr.get("mesh_axes"))
        with rec.span("setup.weights"):
            p0 = ref_mod.make_params(seed, self.cfg)
            model = self.adapter.model(self.cfg, int(self.tr["seq_len"]))
            model.params = self.adapter.to_program_tree(p0)
        with rec.span("setup.data"):
            ids, y = ref_mod.make_data(seed, self.cfg, self.steps,
                                       self.batch, int(self.tr["seq_len"]))
            self.host_data = (ids, y)
            mesh = model._mesh()
            if mesh is not None and mesh.size > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P
                where = NamedSharding(mesh, P())
            else:
                where = devs[0]
            put = lambda a, b: {"x": jax.device_put(ids[a:b], where),
                                "y": jax.device_put(y[a:b], where)}
            self.data = put(0, self.rows)
            # the first batches once more, one batch an epoch
            self.first_data = [put(i * self.batch, (i + 1) * self.batch)
                               for i in range(self.first_steps)]
        self.model = model
        self.est = Estimator.from_keras(model)
        self.first: Optional[dict] = None

    def fit(self, epochs: int, data=None):
        with self.rec.span("fit.call"):
            return self.est.fit(self.data if data is None else data,
                                epochs=epochs, batch_size=self.batch,
                                shuffle=bool(self.tr["shuffle"]),
                                max_failure_retries=0)["loss"]

    def _state(self, which: str):
        return self.adapter.from_program_tree(
            getattr(self.model._opt_state[0], which))

    def first_call(self):
        """The first steps one by one and then the first epoch through
        the window's own call, and what the program's state says after
        each (scalars only; the arrays stay on the device)."""
        import jax
        p0 = self.ref_mod.make_params(self.seed, self.cfg)
        change = lambda: _leaf_norms(_diff(
            self.adapter.from_program_tree(self.model.params), p0))
        out = {"step_losses": []}
        for i, batch in enumerate(self.first_data):
            out["step_losses"].append(float(self.fit(1, batch)[0]))
            if i == 0:
                out["first_moment"] = _to_host(self._state("mu"))
        if self.first_data:
            out["first_change"] = change()
        out["loss"] = float(self.fit(1)[0])
        out.update(moment=_leaf_norms(self._state("mu")), change=change())
        del p0
        jax.block_until_ready(self.model.params)
        self.first = out
        return out

    def close(self):
        import jax
        from zoo_tpu.orca import stop_orca_context
        leaves = jax.tree_util.tree_leaves(
            (self.model.params, self.model._opt_state, self.data,
             self.first_data))
        self.model.params = self.model._opt_state = None
        self.data = self.first_data = None
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


def worst_leaf_distance(prog: Dict[str, np.ndarray],
                        ref: Dict[str, np.ndarray], leave_out=()) -> float:
    """The widest distance between the program's leaf and the
    reference's (the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    keys = [k for k in ref if k not in leave_out]
    norm = lambda a: float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
    floor = statistics.median(norm(ref[k]) for k in keys)
    return max(norm(prog[k] - ref[k]) / max(norm(ref[k]), floor)
               for k in keys)


def reference_first_call(cell, seed: int, devs, ref_mod, host_data,
                         lower: bool = False, rows: float = 1.0) -> dict:
    """The plain reference through the same steps on the same rows: the
    first batches one by one, then every batch of the first epoch."""
    import jax
    cfg, tr = cell.config, cell.traffic
    batch = int(tr["batch_per_chip"]) * len(devs)
    first = int(tr.get("first_steps", 0))
    steps = first + int(tr["followed_steps"])
    ids, y = (np.concatenate([a[:first * batch], a]) for a in host_data)
    p0 = ref_mod.make_params(seed, cfg)
    got = ref_mod.follow(
        p0, cfg, ids, y, steps, batch, lower=lower, rows=rows,
        devices=list(devs) if len(devs) > 1 else None, first=first)
    if len(devs) > 1:
        p0 = jax.device_put(
            p0, jax.tree_util.tree_leaves(got["params"])[0].sharding)
    losses = [float(x) for x in np.asarray(got["losses"])]
    # Adam's first moment after one step is (1 - beta_1) times the
    # first gradient
    first_moment = _to_host(got["first_grad"],
                            1.0 - float(cfg["train"]["beta_1"]))
    out = {"loss": float(np.mean(losses[first:])),
           "step_losses": losses[:first], "epoch_losses": losses[first:],
           "moment": _leaf_norms(got["moment"]),
           "change": _leaf_norms(_diff(got["params"], p0)),
           "first_grad": _leaf_norms(got["first_grad"]),
           "first_moment": first_moment,
           "first_change": _leaf_norms(_diff(got["params_first"], p0))}
    for leaf in jax.tree_util.tree_leaves((p0, got)):
        leaf.delete()
    return out


def compare_first_call(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct`` for a training cell, and
    some that are only read (those with no file under ``limits/``)."""
    g = ref["first_grad"]
    nought = statistics.median(g.values()) * 1e-3
    still = [k for k, v in g.items() if v < nought]
    out = {
        "epoch1_loss_gap": abs(prog["loss"] - ref["loss"])
        / abs(ref["loss"]),
        "moment_norm_gap_worst": worst_leaf_gap(prog["moment"],
                                                ref["moment"], still),
        "param_change_gap_worst": worst_leaf_gap(prog["change"],
                                                 ref["change"], still),
    }
    if prog.get("step_losses"):
        out["first_steps_loss_gap"] = max(
            abs(a - b) / abs(b)
            for a, b in zip(prog["step_losses"], ref["step_losses"]))
        norms = lambda t: {k: float(np.linalg.norm(v.astype(np.float64)))
                           for k, v in t.items()}
        out["first_grad_norm_gap_worst"] = worst_leaf_gap(
            norms(prog["first_moment"]), norms(ref["first_moment"]), still)
        out["first_grad_distance_worst"] = worst_leaf_distance(
            prog["first_moment"], ref["first_moment"], still)
        out["first_steps_change_gap_worst"] = worst_leaf_gap(
            prog["first_change"], ref["first_change"], still)
    return out
