"""Optimizer facade over optax with keras-1 names/defaults.

Reference: Python wrappers ``pyzoo/zoo/orca/learn/optimizers/`` +
``pipeline/api/keras/optimizers.py`` (Adam with schedule support,
AdamWeightDecay / LARS-style, ``PolyEpochDecay``), Scala
``keras/optimizers/``. The reference applied these slice-wise inside the
parameter-server update (``Topology.scala:1204``); here the whole update is
one fused XLA computation — the reference's "apply update on the aggregated
slice" is the optimizer update after psum'd grads, which XLA schedules as
reduce-scatter + apply + all-gather automatically when params are sharded.
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Union

import optax


class Optimizer:
    """Thin wrapper producing an optax GradientTransformation.

    ``plateau`` is set when the user passed a metric-driven
    :class:`~zoo_tpu.orca.learn.optimizers.schedule.Plateau` schedule: the
    transformation is then built with ``optax.inject_hyperparams`` so the
    training loop can write the reduced lr into the optimizer state between
    epochs (the reference's JVM Plateau mutates the optim method's ``clr``
    the same way, driver-side)."""

    #: True when the optimizer provides the direct-apply path
    #: (init_fused/apply_fused) backed by a Pallas fused kernel
    fused = False

    def __init__(self, tx: optax.GradientTransformation, name: str,
                 plateau=None):
        self.tx = tx
        self.name = name
        self.plateau = plateau

    def make(self) -> optax.GradientTransformation:
        return self.tx


def _schedule(lr: float, decay: float) -> Union[float, Callable]:
    """keras-1 `decay`: lr / (1 + decay * iterations)."""
    if not decay:
        return lr
    return lambda step: lr / (1.0 + decay * step)


def _resolve(factory, lr, keras_decay, learningrate_schedule, **kw):
    """Compile (base lr, keras decay, schedule object) into a
    GradientTransformation + optional Plateau controller.

    Accepts a Scheduler from ``zoo_tpu.orca.learn.optimizers.schedule``
    (reference ``orca/learn/optimizers/schedule.py``), a raw ``step -> lr``
    callable, or nothing (keras-1 inverse-time ``decay``)."""
    from zoo_tpu.orca.learn.optimizers.schedule import Plateau, Scheduler

    sched = learningrate_schedule
    if isinstance(sched, Plateau):
        return optax.inject_hyperparams(factory)(
            learning_rate=lr, **kw), sched.bind(lr)
    if isinstance(sched, Scheduler):
        return factory(sched.get_scheduler(lr), **kw), None
    if callable(sched):
        return factory(sched, **kw), None
    return factory(_schedule(lr, keras_decay), **kw), None


class SGD(Optimizer):
    def __init__(self, lr: float = 0.01, momentum: float = 0.0,
                 decay: float = 0.0, nesterov: bool = False,
                 learningrate_schedule=None):
        tx, plateau = _resolve(optax.sgd, lr, decay, learningrate_schedule,
                               momentum=momentum or None, nesterov=nesterov)
        super().__init__(tx, "sgd", plateau)


class Adam(Optimizer):
    def __init__(self, lr: float = 0.001, beta_1: float = 0.9,  # zoo-lint: config-parse
                 beta_2: float = 0.999, epsilon: float = 1e-8,
                 decay: float = 0.0, learningrate_schedule=None):
        tx, plateau = _resolve(optax.adam, lr, decay, learningrate_schedule,
                               b1=beta_1, b2=beta_2, eps=epsilon)
        super().__init__(tx, "adam", plateau)


class AdamWeightDecay(Optimizer):
    """BERT-style AdamW (reference: ``keras/optimizers.py`` AdamWeightDecay,
    used by the Scala ``BERT.scala`` training configs).

    ``fused=True`` applies the update with the Pallas fused-apply kernel
    (``ops/pallas/fused_optim.py`` — the "apply optimizer to the
    aggregated slice in-task" leg of the reference's PS allreduce,
    ``wp-bigdl.md:146-160``) through the direct-apply path of the train
    step, skipping the optax updates/apply round trip. Constant lr only
    (schedules stay on the optax path). ``fused=None`` (default) reads
    the ``ZOO_FUSED_OPTIM`` env knob — "1" turns the direct-apply path
    on deployment-wide for schedule-free configs (a scheduled config
    silently keeps the optax path rather than erroring, so one env var
    can cover a whole job). Inside a >1-device mesh the update runs as
    the partitionable elementwise form; off-TPU the kernel interprets —
    either way the fallback is clean."""

    def __init__(self, lr: float = 0.001, beta_1: float = 0.9,  # zoo-lint: config-parse
                 beta_2: float = 0.999, epsilon: float = 1e-6,
                 weight_decay: float = 0.01, total_steps: int = 0,
                 warmup_ratio: float = 0.1, learningrate_schedule=None,
                 fused: Optional[bool] = None):
        if learningrate_schedule is None and total_steps:
            warmup = max(1, int(total_steps * warmup_ratio))
            learningrate_schedule = optax.warmup_cosine_decay_schedule(
                0.0, lr, warmup, total_steps)
        tx, plateau = _resolve(optax.adamw, lr, 0.0, learningrate_schedule,
                               b1=beta_1, b2=beta_2, eps=epsilon,
                               weight_decay=weight_decay)
        super().__init__(tx, "adamw", plateau)
        if fused and learningrate_schedule is not None:
            raise ValueError("fused=True supports a constant lr only")
        if fused is None:
            fused = (os.environ.get("ZOO_FUSED_OPTIM", "").lower()
                     in ("1", "true")
                     and learningrate_schedule is None)
        if fused:
            self.fused = True
            self._fused_args = (float(lr), float(beta_1), float(beta_2),
                                float(epsilon), float(weight_decay))

    def init_fused(self, trainable):
        import jax
        import jax.numpy as jnp
        # zeros_like keeps the parameter's sharding, so fused moments are
        # FSDP-sharded exactly like the non-fused tx.init state
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=jnp.float32), trainable)
        return {"m": zeros,
                "v": jax.tree_util.tree_map(jnp.copy, zeros),
                "step": jnp.zeros((), jnp.int32)}

    def apply_fused(self, grads, state, trainable):
        """Direct-apply: returns (new_trainable, new_state)."""
        import jax
        from zoo_tpu.ops.pallas.fused_optim import fused_apply_adam

        lr, b1, b2, eps, wd = self._fused_args
        step = state["step"] + 1

        def leaf(p, g, m, v):
            return fused_apply_adam(p, g, m, v, step, lr, beta1=b1,
                                    beta2=b2, eps=eps, weight_decay=wd)

        out = jax.tree_util.tree_map(leaf, trainable, grads,
                                     state["m"], state["v"])
        is_triple = lambda t: isinstance(t, tuple) and len(t) == 3
        new_p = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_triple)
        new_m = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_triple)
        new_v = jax.tree_util.tree_map(lambda t: t[2], out, is_leaf=is_triple)
        return new_p, {"m": new_m, "v": new_v, "step": step}


class RMSprop(Optimizer):
    def __init__(self, lr: float = 0.001, rho: float = 0.9,
                 epsilon: float = 1e-8, decay: float = 0.0,
                 learningrate_schedule=None):
        tx, plateau = _resolve(optax.rmsprop, lr, decay,
                               learningrate_schedule, decay=rho, eps=epsilon)
        super().__init__(tx, "rmsprop", plateau)


class Adagrad(Optimizer):
    def __init__(self, lr: float = 0.01, epsilon: float = 1e-8,
                 decay: float = 0.0, learningrate_schedule=None):
        tx, plateau = _resolve(optax.adagrad, lr, decay,
                               learningrate_schedule, eps=epsilon)
        super().__init__(tx, "adagrad", plateau)


class Adadelta(Optimizer):
    def __init__(self, lr: float = 1.0, rho: float = 0.95,
                 epsilon: float = 1e-8, decay: float = 0.0,
                 learningrate_schedule=None):
        tx, plateau = _resolve(optax.adadelta, lr, decay,
                               learningrate_schedule, rho=rho, eps=epsilon)
        super().__init__(tx, "adadelta", plateau)


class Adamax(Optimizer):
    def __init__(self, lr: float = 0.002, beta_1: float = 0.9,
                 beta_2: float = 0.999, epsilon: float = 1e-8,
                 decay: float = 0.0, learningrate_schedule=None):
        tx, plateau = _resolve(optax.adamax, lr, decay,
                               learningrate_schedule,
                               b1=beta_1, b2=beta_2, eps=epsilon)
        super().__init__(tx, "adamax", plateau)


class LARS(Optimizer):
    """Layer-wise adaptive rate scaling for large-batch training (reference
    ships a LARS-ish variant for ImageNet runs)."""

    def __init__(self, lr: float = 0.1, momentum: float = 0.9,
                 weight_decay: float = 1e-4, trust_coefficient: float = 0.001,
                 learningrate_schedule=None):
        tx, plateau = _resolve(optax.lars, lr, 0.0, learningrate_schedule,
                               weight_decay=weight_decay, momentum=momentum,
                               trust_coefficient=trust_coefficient)
        super().__init__(tx, "lars", plateau)


_ALIASES = {
    "sgd": SGD,
    "adam": Adam,
    "adamw": AdamWeightDecay,
    "rmsprop": RMSprop,
    "adagrad": Adagrad,
    "adadelta": Adadelta,
    "adamax": Adamax,
    "lars": LARS,
}


def get_optimizer(identifier) -> Optimizer:
    if isinstance(identifier, Optimizer):
        return identifier
    if isinstance(identifier, optax.GradientTransformation):
        return Optimizer(identifier, "optax")
    key = str(identifier).lower()
    if key not in _ALIASES:
        raise ValueError(f"unknown optimizer: {identifier}")
    return _ALIASES[key]()
