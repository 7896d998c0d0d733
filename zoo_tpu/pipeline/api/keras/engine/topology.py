"""Sequential / functional Model with compile/fit/evaluate/predict.

Rebuild of the reference's ``KerasNet`` (Scala
``pipeline/api/keras/models/Topology.scala:139,347,504`` — compile/fit/
evaluate/predict over FeatureSet + InternalDistriOptimizer) and the Python
facade ``pyzoo/zoo/pipeline/api/keras/engine/topology.py``.

The TPU re-architecture collapses the reference's per-iteration "2 Spark
jobs + JNI weight push/pull + PS-shuffle allreduce" (``Topology.scala:1262``,
``wp-bigdl.md:146-160``) into ONE jitted XLA computation per step: forward,
backward, gradient allreduce over the mesh ``data`` axes, and the optimizer
update are fused and scheduled by XLA; weights never leave the device.
"""

from __future__ import annotations

import contextlib
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from zoo_tpu.common.context import get_runtime_context
from zoo_tpu.obs.metrics import counter as _obs_counter
from zoo_tpu.obs.tracing import emit_span, span, watch_compiles
from zoo_tpu.pipeline.api.keras.engine.base import KTensor, Layer
from zoo_tpu.pipeline.api.keras.engine import data_utils
from zoo_tpu.pipeline.api.keras.metrics import Metric, get_metric
from zoo_tpu.pipeline.api.keras.objectives import get_loss
from zoo_tpu.pipeline.api.keras.optimizers import get_optimizer


def _split_state(params: Dict) -> Tuple[Dict, Dict]:
    """Separate non-trainable running stats (e.g. BatchNorm) from trainable
    params so grads are only taken w.r.t. the latter."""
    trainable, state = {}, {}
    for lname, p in params.items():
        if isinstance(p, dict) and "stats" in p:
            state[lname] = {"stats": p["stats"]}
            trainable[lname] = {k: v for k, v in p.items() if k != "stats"}
        else:
            trainable[lname] = p
    return trainable, state


def _merge_state(trainable: Dict, state: Dict) -> Dict:
    out = dict(trainable)
    for lname, st in state.items():
        merged = dict(out.get(lname, {}))
        merged.update(st)
        out[lname] = merged
    return out


#: params key the pipeline plan stacks the homogeneous layer run under
#: (same literal as ``zoo_tpu.parallel.plans.PIPE_BODY_KEY``; kept
#: inline so _forward tracing never imports the plans module)
_PIPE_BODY_KEY = "__pipe_body__"

# Event-file-backed summaries (own writer + disk read-back) live in
# zoo_tpu.tensorboard; re-exported here for the keras facade.
from zoo_tpu.tensorboard import TrainSummary  # noqa: E402

_collective_bytes = _obs_counter(
    "zoo_mesh_collective_bytes_total",
    "Estimated per-step collective traffic the active sharding plan "
    "implies, accumulated over executed train steps (static plan "
    "estimate — fsdp weight gathers + grad reductions; see "
    "zoo_tpu.parallel.plans.estimate_collective_bytes)",
    labels=("op",))

# serializes lazy jit-cache builds: concurrent first predicts (the
# multi-replica ServingServer batcher threads) could otherwise each
# build a PRIVATE jit object for the same step fn — two full XLA
# compiles of the same executable, a multi-second p99 spike per extra
# thread on TPU. Module-level (not an instance attr) so models stay
# cloudpickle-serializable.
_JIT_BUILD_LOCK = threading.Lock()


def _scan_steps(step, params, opt_state, rng, stacked):
    """``lax.scan`` of the train step over batches stacked as
    (k, batch, ...); the shared core of the multi-step and whole-epoch
    dispatch paths — their per-step math must stay identical."""
    def body(carry, batch):
        p, o, r = carry
        p, o, r, loss = step(p, o, r, *batch)
        return (p, o, r), loss

    (params, opt_state, rng), losses = jax.lax.scan(
        body, (params, opt_state, rng), stacked)
    return params, opt_state, rng, jnp.sum(losses)


class KerasNet:
    """Shared training engine for Sequential and Model."""

    def __init__(self, name: Optional[str] = None):
        self.name = name or type(self).__name__.lower()
        self.params: Optional[Dict] = None
        self.optimizer = None
        self.loss_fn: Optional[Callable] = None
        self.metrics: List[Metric] = []
        self._opt_state = None
        self._step = 0
        self.train_summary = TrainSummary()
        self.validation_summary = TrainSummary()
        self._jit_train = None
        self._jit_multi = None
        self._jit_eval = None
        self._jit_pred = None
        self._built_shapes: Optional[List[Tuple]] = None
        self._grad_clip: Optional[Tuple] = None
        self._guard = None  # TrainingGuard (orca/learn/guard.py)

    # -- param keys --------------------------------------------------------
    def _param_keys(self) -> Dict[int, str]:
        """Deterministic params keys by layer position+type (NOT the
        process-global auto names) so checkpoints restore into fresh model
        instances — the reference gets this for free from its Scala module
        serialization; position-keying is our equivalent."""
        return {id(layer): f"{i:03d}_{type(layer).__name__.lower()}"
                for i, layer in enumerate(self.layers)}

    def _key_of(self, layer) -> str:
        return self._param_keys()[id(layer)]

    # -- to be provided by subclasses ------------------------------------
    def _init_params(self, rng, input_shapes) -> Dict:
        raise NotImplementedError

    def _forward(self, params, inputs: List, *, training: bool, rng,
                 collect: Optional[Dict]):
        raise NotImplementedError

    def _input_shapes(self) -> Optional[List[Tuple]]:
        raise NotImplementedError

    @property
    def layers(self) -> List[Layer]:
        raise NotImplementedError

    # -- public API (keras-1 names, reference Topology.scala) -------------
    def compile(self, optimizer, loss, metrics=None,
                loss_weights=None, dtype_policy: str = "float32",
                plan: Optional[str] = None):
        """reference: ``KerasNet.compile`` ``Topology.scala:139``.

        ``loss_weights``: optional per-output scalar weights for
        multi-output models (keras semantics; reference multi-task use).

        ``dtype_policy``: "float32" (default) or "mixed_bfloat16" — params
        and optimizer state stay f32, forward/backward compute runs in
        bf16 on the MXU with f32 islands in the normalizations/softmax
        (net-new: the reference's fabric is f32-only CPU).

        ``plan``: sharding plan for every placement/step this model
        makes (``zoo_tpu.parallel.plans`` registry; default env
        ``ZOO_PLAN`` → ``"auto"``). ``"pipeline"`` additionally
        restructures the params tree: the longest homogeneous layer run
        stacks into one stage-stacked body the GPipe microbatch
        schedule consumes (guard counters / rng / loss stay replicated
        exactly as every other plan, so guard/checkpoint/preemption
        inherit unchanged)."""
        if dtype_policy not in ("float32", "mixed_bfloat16"):
            raise ValueError(f"unknown dtype_policy: {dtype_policy}")
        watch_compiles()    # a recompile inside fit gets a name and a cost
        from zoo_tpu.common import knobs as _knobs
        plan = plan or _knobs.value("ZOO_PLAN")
        if plan != "auto":
            from zoo_tpu.parallel.plans import get_plan
            get_plan(plan)  # unknown plan names fail here, not mid-fit
        self._plan = plan
        if plan == "pipeline" and self.params is not None:
            self.params = self._stack_pipe_body(self.params)
        n_out = len(getattr(self, "outputs", [None]))
        if n_out > 1 and not isinstance(loss, (list, tuple)):
            raise ValueError(
                f"model has {n_out} outputs; compile(loss=[...]) needs one "
                "loss per output")
        if isinstance(loss, (list, tuple)) and len(loss) != n_out:
            raise ValueError(f"{len(loss)} losses for {n_out} outputs")
        if loss_weights is not None and not isinstance(loss,
                                                       (list, tuple)):
            raise ValueError("loss_weights needs a list of losses")
        self.dtype_policy = dtype_policy
        self.optimizer = get_optimizer(optimizer)
        if isinstance(loss, (list, tuple)):
            # multi-output: one loss per output, weighted sum (the
            # reference's multi-task graphs combine per-head criteria the
            # same way)
            fns = [get_loss(l) for l in loss]
            ws = ([float(w) for w in loss_weights]
                  if loss_weights is not None else [1.0] * len(fns))
            if len(ws) != len(fns):
                raise ValueError(f"{len(ws)} loss_weights for "
                                 f"{len(fns)} losses")

            def _multi_loss(ys, preds):
                ys = ys if isinstance(ys, (list, tuple)) else [ys]
                preds = preds if isinstance(preds, (list, tuple)) else [preds]
                if not (len(fns) == len(ys) == len(preds)):
                    raise ValueError(
                        f"{len(fns)} losses, {len(ys)} label sets, "
                        f"{len(preds)} outputs — counts must match")
                return sum(w * f(y, p)
                           for w, f, y, p in zip(ws, fns, ys, preds))

            self.loss_fn = _multi_loss
            self.loss_name = "multi"
        else:
            self.loss_fn = get_loss(loss)
            self.loss_name = (loss if isinstance(loss, str)
                              else getattr(loss, "__name__", None))
        self.metrics = [get_metric(m) for m in (metrics or [])]
        self._jit_train = self._jit_eval = self._jit_pred = None
        self._jit_multi = self._own_jit_train = None
        self._jit_epoch_cache = None
        self._opt_state = None  # a new optimizer cannot reuse old state
        return self

    def _cast_compute(self, tree):
        """Cast float32 leaves to the compute dtype under the policy."""
        if getattr(self, "dtype_policy", "float32") != "mixed_bfloat16":
            return tree
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, tree)

    # -- gradient clipping (reference: Scala ``Estimator.scala:68`` area —
    # constant + L2-norm clipping applied inside DistriOptimizer) ----------
    def _drop_train_caches(self):
        """Invalidate every cache holding a traced train step — required
        whenever something the step closure bakes in changes (grad clip,
        loss, a layer-mode flag like seq2seq's train_self_feed)."""
        self._jit_train = self._jit_multi = self._own_jit_train = None
        self._jit_epoch_cache = None

    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float):
        """Clip every gradient element into [min_value, max_value]."""
        self._grad_clip = ("const", float(min_value), float(max_value))
        # clip is in the step: drop every cache holding a traced step
        self._drop_train_caches()
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        """Scale gradients so their global L2 norm is at most clip_norm."""
        self._grad_clip = ("l2", float(clip_norm))
        self._drop_train_caches()
        return self

    def clear_gradient_clipping(self):
        self._grad_clip = None
        self._drop_train_caches()
        return self

    def _apply_grad_clip(self, grads):
        """Applied to raw grads before the optimizer update — outside the
        optax chain so toggling clipping never invalidates optimizer state."""
        if self._grad_clip is None:
            return grads
        if self._grad_clip[0] == "const":
            _, lo, hi = self._grad_clip
            return jax.tree_util.tree_map(
                lambda g: jnp.clip(g, lo, hi), grads)
        (_, norm) = self._grad_clip
        import optax
        gnorm = optax.global_norm(grads)
        scale = jnp.minimum(1.0, norm / (gnorm + 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    def set_guard(self, guard):
        """Attach a :class:`zoo_tpu.orca.learn.guard.TrainingGuard`. The
        guard changes the traced step (health fold + device counters in
        the optimizer-state carry), so every train-step cache drops —
        attach once, before training, like the estimators do."""
        self._guard = guard
        self._drop_train_caches()
        return self

    def clear_guard(self):
        self._guard = None
        self._drop_train_caches()
        return self

    def _active_guard(self):
        g = getattr(self, "_guard", None)
        return g if g is not None and g.active else None

    def set_tensorboard(self, log_dir: str, app_name: str):
        """reference: ``Topology.scala:162-168``."""
        self.train_summary = TrainSummary(log_dir, app_name + "/train")
        self.validation_summary = TrainSummary(log_dir, app_name + "/val")

    def set_profile(self, trace_dir: Optional[str] = None,
                    trace_epochs: int = 1):
        """Enable per-phase step timers for the next ``fit`` (data-wait /
        device-step avg-ms scalars into the train summary) and, when
        ``trace_dir`` is given, an XLA profiler capture of the first
        ``trace_epochs`` epochs (rebuild of SURVEY §5.1; per-stage
        ``Timer.scala`` + net-new ``jax.profiler`` depth). Forces a
        device sync per step while enabled (accurate step times at the
        cost of dispatch overlap); ``clear_profile()`` turns it off."""
        from zoo_tpu.common.profiling import StepProfiler
        self._profiler = StepProfiler(trace_dir=trace_dir,
                                      trace_epochs=trace_epochs)
        return self._profiler

    def clear_profile(self):
        self._profiler = None

    def get_profile_stats(self):
        prof = getattr(self, "_profiler", None)
        return prof.stats() if prof else {}

    def get_train_summary(self, tag: str = "Loss"):
        return self.train_summary.read_scalar(tag)

    def get_validation_summary(self, tag: str):
        return self.validation_summary.read_scalar(tag)

    def build(self, rng=None, input_shapes=None):
        """Materialize params (idempotent)."""
        if self.params is not None:
            return self.params
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        shapes = input_shapes or self._input_shapes()
        if shapes is None:
            raise ValueError(
                f"{self.name}: cannot infer input shape; pass input_shape to "
                "the first layer or call build(input_shapes=...)")
        self._built_shapes = [tuple(s) for s in shapes]
        self.params = self._init_params(rng, shapes)
        if self._plan_name() == "pipeline":
            self.params = self._stack_pipe_body(self.params)
        return self.params

    def _n_inputs(self) -> int:
        shapes = self._built_shapes or self._input_shapes()
        return len(shapes) if shapes else 1

    # -- devices / sharding ----------------------------------------------
    def _mesh(self):
        ctx = get_runtime_context(required=False)
        return ctx.mesh if ctx is not None else None

    def _plan_name(self) -> str:
        """The sharding plan ``compile(plan=...)`` pinned (``"auto"``
        before compile / on models from old pickles)."""
        return getattr(self, "_plan", "auto")

    def _place(self, params):
        """Place params per the mesh plan: replicated across ``data``,
        ZeRO-sharded across ``fsdp``, tensor-parallel across ``model``,
        stage/expert-sharded across ``pipe``/``expert`` under the
        pipeline/moe plans (see ``zoo_tpu.parallel.plans``)."""
        from zoo_tpu.parallel.plans import place_params
        return place_params(params, self._mesh(), self._plan_name())

    # -- pipeline plan (GPipe body) ---------------------------------------
    def _stack_pipe_body(self, params):
        raise ValueError(
            "plan='pipeline' needs a Sequential model (got "
            f"{type(self).__name__}: no unambiguous layer chain to "
            "stage)")

    def _pipe_microbatches(self, stages: int) -> int:
        """GPipe microbatch count: ``ZOO_PIPE_MICROBATCHES`` (> 0) or
        one microbatch per stage."""
        from zoo_tpu.common import knobs as _knobs
        m = int(_knobs.value("ZOO_PIPE_MICROBATCHES") or 0)
        return m if m > 0 else stages

    def _apply_pipe_body(self, body, h, *, training):
        """Apply the stage-stacked homogeneous body: the GPipe
        microbatch schedule over the ``pipe`` mesh axis when training on
        one, a plain ``lax.scan`` over the layer stack otherwise — the
        same layer-by-layer math either way."""
        tmpl = self._pipe_template

        def step(carry, leaf_slice):
            return tmpl.call(leaf_slice, carry, training=training,
                             rng=None), None

        mesh = self._mesh()
        pipe = mesh.shape.get("pipe", 1) if mesh is not None else 1
        if training and pipe > 1:
            from zoo_tpu.parallel.pipeline import (
                pipeline_apply,
                stack_stages,
            )
            stages = stack_stages(body, pipe)

            def stage_fn(p_slice, hh):
                out, _ = jax.lax.scan(step, hh, p_slice)
                return out

            return pipeline_apply(stage_fn, stages, h, mesh,
                                  self._pipe_microbatches(pipe))
        out, _ = jax.lax.scan(step, h, body)
        return out

    def _put_batch(self, arrs: List[np.ndarray]):
        mesh = self._mesh()
        if mesh is None:
            return [jnp.asarray(a) for a in arrs]
        from zoo_tpu.parallel.mesh import batch_sharding, host_local_to_global
        if jax.process_count() > 1:
            # multi-host: each process contributes its local rows of the
            # global batch — assembled without any driver-side collect
            # (SURVEY §7.4 hard part #1; reference: ray_xshards.py locality)
            return [host_local_to_global(mesh,
                                         batch_sharding(mesh, a.ndim).spec,
                                         np.asarray(a)) for a in arrs]
        return [jax.device_put(a, batch_sharding(mesh, a.ndim)) for a in arrs]

    def _put_stacked(self, arrs: List):
        """Place (k, batch, ...) superbatches for the scanned multi-step:
        scan dim replicated, batch dim sharded over the data axes."""
        mesh = self._mesh()
        if mesh is None:
            return [jnp.asarray(a) for a in arrs]
        from zoo_tpu.parallel.mesh import stacked_batch_sharding
        return [jax.device_put(a, stacked_batch_sharding(mesh, a.ndim))
                for a in arrs]

    def _adapt_inputs(self, xs: List[np.ndarray]) -> List[np.ndarray]:
        """Single-input model fed k feature columns → stack into one
        (batch, k) tensor (the reference's NNEstimator assembles feature
        cols the same way via SeqToTensor, ``feature/common.py:94``)."""
        shapes = self._input_shapes() or self._built_shapes
        if shapes and len(shapes) == 1 and len(xs) > 1 \
                and all(a.ndim == 1 for a in xs):
            return [np.stack(xs, axis=1)]
        return xs

    # -- jitted steps -----------------------------------------------------
    def _make_step_fn(self):
        tx = self.optimizer.make()
        n_inputs = self._n_inputs()
        guard = self._active_guard()

        def step(params, opt_state, rng, *batch):
            # rng advances inside the jitted step — a host-side split per
            # step would be an extra dispatch (and a real cost when the
            # device sits behind a high-latency transport)
            if guard is not None:
                # the guard's device counters ride the opt-state carry so
                # the step keeps its (params, opt_state, rng, *batch)
                # signature through scan/jit/donation unchanged
                opt_state, gstate = opt_state
            step_rng, new_rng = jax.random.split(rng)
            xs = list(batch[:n_inputs])
            labels = list(batch[n_inputs:])
            ys = labels[0] if len(labels) == 1 else labels
            trainable, state = _split_state(params)

            def loss_fn(tr):
                collect = {}
                # cast trainables only: running stats (BatchNorm EMA) must
                # keep f32 resolution or momentum-0.99 increments vanish
                # below a bf16 ulp
                preds = self._forward(
                    _merge_state(self._cast_compute(tr), state),
                    self._cast_compute(xs), training=True, rng=step_rng,
                    collect=collect)
                if not getattr(self.loss_fn, "_handles_low_precision",
                               False):
                    preds = jax.tree.map(
                        lambda p: p.astype(jnp.float32)
                        if hasattr(p, "dtype") and p.dtype == jnp.bfloat16
                        else p, preds)
                return self.loss_fn(ys, preds), collect

            (loss, collect), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(trainable)
            grads = self._apply_grad_clip(grads)

            def _update(tr, opt, g):
                if getattr(self.optimizer, "fused", False):
                    # direct-apply path: the Pallas fused kernel writes
                    # new params in one pass, no optax updates/apply
                    # round trip
                    return self.optimizer.apply_fused(g, opt, tr)
                upd, opt = tx.update(g, opt, tr)
                import optax
                return optax.apply_updates(tr, upd), opt

            if guard is not None:
                # in-step health guard: the whole optimizer update runs
                # under lax.cond — a non-finite loss/grad-norm takes the
                # identity branch, so params, opt state and running
                # stats pass through UNCHANGED (buffers forwarded; no
                # host sync, and good steps pay only the norm reduce)
                ok = guard.grad_norm_ok(loss, grads)

                def _good(op):
                    tr, opt = _update(op[0], op[1], op[2])
                    return tr, opt, op[3]

                def _skip(op):
                    return op[0], op[1], state

                trainable, opt_state, new_stats = jax.lax.cond(
                    ok, _good, _skip,
                    (trainable, opt_state, grads, collect or state))
                gstate = guard.gstate_update(gstate, ok)
                loss = jnp.where(ok, loss, 0.0)
                return (_merge_state(trainable, new_stats),
                        (opt_state, gstate), new_rng, loss)
            trainable, opt_state = _update(trainable, opt_state, grads)
            new_params = _merge_state(trainable, collect or state)
            return new_params, opt_state, new_rng, loss

        return step

    # -- explicit GSPMD shardings (docs/multichip.md) ---------------------
    # On a >1-device mesh the train step is jitted with explicit
    # NamedSharding in/out shardings instead of relying on committed-input
    # inference: params/opt-state follow the placement plan
    # (zoo_tpu.parallel.plans — replicated over `data`, ZeRO-sharded over
    # `fsdp`, tensor-parallel over `model`), batches ride the data axes,
    # rng/loss and the guard's device counters are replicated. Explicit
    # out_shardings pin the updated params to the SAME layout, so a plan
    # regression cannot silently come back replicated (the hlo_check
    # FSDP lint asserts the same thing from the compiled text).
    def _state_shardings(self, params, opt_state):
        """(params_shardings, opt_state_shardings, replicated) for the
        current mesh, from the live placed arrays; None off-mesh."""
        mesh = self._mesh()
        if mesh is None or mesh.size <= 1:
            return None
        from zoo_tpu.parallel.mesh import replicated_sharding
        from zoo_tpu.parallel.plans import shardings_of
        return (shardings_of(params, mesh), shardings_of(opt_state, mesh),
                replicated_sharding(mesh))

    def _step_shardings(self, shard, batch_ndims, stacked: bool):
        """jit (in_shardings, out_shardings) for the train-step seam."""
        if shard is None:
            return None
        mesh = self._mesh()
        from zoo_tpu.parallel.mesh import (
            batch_sharding,
            stacked_batch_sharding,
        )
        p_sh, o_sh, rep = shard
        bfn = stacked_batch_sharding if stacked else batch_sharding
        ins = (p_sh, o_sh, rep) + tuple(
            bfn(mesh, nd + (1 if stacked else 0)) for nd in batch_ndims)
        return ins, (p_sh, o_sh, rep, rep)

    def _jit_step(self, fn, shardings):
        if shardings is None:
            return jax.jit(fn, donate_argnums=(0, 1, 2))
        ins, outs = shardings
        return jax.jit(fn, donate_argnums=(0, 1, 2),
                       in_shardings=ins, out_shardings=outs)

    def _build_train_step(self, shardings=None):
        return self._jit_step(self._make_step_fn(), shardings)

    def _build_multi_train_step(self, shardings=None):
        """K training steps per dispatch: ``lax.scan`` of the step over
        batches stacked as (k, batch, ...). One XLA execution covers k
        steps, amortizing per-call dispatch latency — the TPU-native
        idiom (the device runs autonomously instead of waiting on the
        host for every step). What a dispatch costs on today's
        installation is not measured yet (PERF.md). The
        per-step math is IDENTICAL to the single-step path (same step
        function, scanned)."""
        step = self._make_step_fn()

        def multi(params, opt_state, rng, *stacked):
            return _scan_steps(step, params, opt_state, rng, stacked)

        return self._jit_step(multi, shardings)

    def _build_epoch_train_step(self, k: int, bs: int, gather: bool,
                                shard=None):
        """A FULL epoch in one dispatch: permutation-gather of the (small,
        device-resident) dataset + ``lax.scan`` of the step over all ``k``
        batches, inside a single jit call. For small models the
        per-dispatch overhead otherwise dominates the epoch (two
        superbatch dispatches can cost more than a whole NCF epoch's
        compute). Only used for datasets small enough that the permuted
        gather copy is cheap (fit caps it at 256MB)."""
        step = self._make_step_fn()
        mesh = self._mesh()

        def epoch_fn(params, opt_state, rng, *args):
            if gather:
                *arrs, perm = args
                stacked = [a[perm].reshape((k, bs) + a.shape[1:])
                           for a in arrs]
            else:
                # shuffle=False: an identity gather would copy the whole
                # dataset in HBM for nothing — reshape is free
                stacked = [a[:k * bs].reshape((k, bs) + a.shape[1:])
                           for a in args]
            if mesh is not None and mesh.size > 1:
                # multi-device: pin the per-step batch dim onto the data
                # axes (the _put_stacked layout) so the scanned steps run
                # sharded instead of replicated
                from zoo_tpu.parallel.mesh import stacked_batch_sharding
                stacked = [jax.lax.with_sharding_constraint(
                    a, stacked_batch_sharding(mesh, a.ndim))
                    for a in stacked]
            return _scan_steps(step, params, opt_state, rng, stacked)

        if shard is not None:
            # dataset operands keep their resident placement (the gather
            # re-pins batches via the constraint above); the carried
            # params/opt-state come back pinned to the plan's layout
            p_sh, o_sh, rep = shard
            return jax.jit(epoch_fn, donate_argnums=(0, 1, 2),
                           out_shardings=(p_sh, o_sh, rep, rep))
        return jax.jit(epoch_fn, donate_argnums=(0, 1, 2))

    def _build_pred_step(self):
        def step(params, *xs):
            tr, state = _split_state(params)  # keep running stats f32
            preds = self._forward(_merge_state(self._cast_compute(tr),
                                               state),
                                  self._cast_compute(list(xs)),
                                  training=False, rng=None, collect=None)
            return jax.tree.map(
                lambda p: p.astype(jnp.float32)
                if hasattr(p, "dtype") and p.dtype == jnp.bfloat16 else p,
                preds)
        return jax.jit(step)

    def lower_train_hlo(self, x, y=None, batch_size: int = 32,
                        feature_cols=None, label_cols=None,
                        seed: int = 0) -> str:
        """Optimized-HLO text of the jitted single-batch train step at
        these shapes and the current mesh's shardings — the input to
        sharding-quality checks (``zoo_tpu.parallel.hlo_check``): a
        silently-replicating sharding regression still trains with finite
        loss, but its compiled collective mix (no all-gather under FSDP,
        a full-param all-gather under pure DP, ...) gives it away.
        Note: ``.lower().compile()`` is AOT — it does NOT share or
        populate fit's jit call cache, so this costs one extra compile
        at these shapes."""
        if self.loss_fn is None:
            raise RuntimeError("call compile() before lower_train_hlo()")
        xs, ys = data_utils.to_xy_arrays(x, y, feature_cols, label_cols)
        xs = self._adapt_inputs(xs)
        ys_list = list(ys) if isinstance(ys, (list, tuple)) else [ys]
        self.build(jax.random.PRNGKey(seed),
                   [(None,) + a.shape[1:] for a in xs])
        params = self._place(self.params)
        tx = self.optimizer.make()
        trainable, _ = _split_state(params)
        opt_state = self._opt_state or (
            self.optimizer.init_fused(trainable)
            if getattr(self.optimizer, "fused", False) else
            tx.init(trainable))
        if self._active_guard() is not None:
            # the guarded step carries the guard counters in opt_state
            opt_state = (opt_state, self._active_guard().device_init())
        rng = jax.random.PRNGKey(seed + 1)
        mesh = self._mesh()
        _shard = None
        if mesh is not None and mesh.size > 1:
            from zoo_tpu.parallel.plans import ensure_placed
            opt_state = ensure_placed(opt_state, mesh)
            _shard = self._state_shardings(params, opt_state)
            rng = jax.device_put(rng, _shard[2])
        local_bs = max(batch_size // jax.process_count(), 1)
        host_batch = [np.asarray(a[:local_bs]) for a in xs + ys_list]
        batch = self._put_batch(host_batch)
        # use OUR jitted step, never an interposed _jit_train (the
        # elastic-retry fault-injection contract replaces it with plain
        # callables that have no .lower); don't clobber the interposer
        jt = getattr(self, "_own_jit_train", None)
        interposed = self._jit_train is not None \
            and self._jit_train is not jt
        if not interposed and getattr(self, "_jit_mesh", None) != mesh:
            self._drop_train_caches()  # stale-mesh shardings baked in
            jt = None
            self._jit_mesh = mesh
        if jt is None:
            jt = self._own_jit_train = self._build_train_step(
                self._step_shardings(_shard,
                                     [a.ndim for a in host_batch], False))
        if self._jit_train is None:
            self._jit_train = jt
        return jt.lower(params, opt_state, rng,
                        *batch).compile().as_text()

    # -- training loop ----------------------------------------------------
    def fit(self, x, y=None, batch_size: int = 32, nb_epoch: int = 10,
            validation_data=None, shuffle: bool = True,
            feature_cols=None, label_cols=None, seed: int = 0,
            verbose: int = 1) -> Dict[str, List[float]]:
        """reference: ``KerasNet.fit`` ``Topology.scala:347`` (trains via
        InternalDistriOptimizer there; a jitted step loop here)."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this model was int8-quantized (quantize_model) and is "
                "inference-only; re-load the float checkpoint to train")
        if self.loss_fn is None:
            raise RuntimeError("call compile() before fit()")
        t_fit = time.perf_counter()
        xs, ys = data_utils.to_xy_arrays(x, y, feature_cols, label_cols)
        xs = self._adapt_inputs(xs)
        if ys is None:
            raise ValueError("fit requires labels")
        n_out = len(getattr(self, "outputs", [None]))
        if isinstance(ys, (list, tuple)):
            if n_out <= 1:
                # single-output model: a list of per-sample label rows is
                # ONE label array, not a multi-output label set
                ys = np.stack([np.asarray(a) for a in ys]) \
                    if len(ys) > 1 else np.asarray(ys[0])
                ys_list = [ys]
            elif len(ys) != n_out:
                raise ValueError(f"model has {n_out} outputs but got "
                                 f"{len(ys)} label arrays")
            else:
                ys_list = list(ys)
        else:
            ys_list = [ys]
        n = data_utils.num_samples(xs)

        mesh = self._mesh()
        if mesh is not None:
            from zoo_tpu.parallel.mesh import validate_batch_size
            validate_batch_size(batch_size, mesh)
        # multi-host SPMD: ``batch_size`` is the GLOBAL batch; each process
        # feeds its local rows (batch_size / process_count). Every process
        # must hold the same local sample count so step counts agree.
        pc = jax.process_count()
        if batch_size % pc:
            raise ValueError(f"batch_size ({batch_size}) must divide by "
                             f"process_count ({pc})")
        local_bs = batch_size // pc
        if n < local_bs:
            raise ValueError(f"local dataset ({n}) smaller than per-process "
                             f"batch ({local_bs})")

        self.build(jax.random.PRNGKey(seed),
                   [(None,) + a.shape[1:] for a in xs])
        params = self._place(self.params)
        tx = self.optimizer.make()
        trainable, _ = _split_state(params)
        opt_state = self._opt_state or (
            self.optimizer.init_fused(trainable)
            if getattr(self.optimizer, "fused", False) else
            tx.init(trainable))
        if (self._opt_state is not None and mesh is not None
                and mesh.size > 1 and self._plan_name() != "auto"):
            # reshard-on-restore for plan-sharded moments: a checkpoint
            # restore places leaves mesh-generically (replicated for a
            # pipe/expert-sharded shape), but a previously compiled step
            # expects the plan layout; pin every moment back onto the
            # shardings a fresh init of the placed params carries
            from zoo_tpu.parallel.plans import shardings_of
            tmpl = (self.optimizer.init_fused(trainable)
                    if getattr(self.optimizer, "fused", False)
                    else tx.init(trainable))
            opt_state = jax.tree_util.tree_map(
                lambda s, a: jax.device_put(a, s),
                shardings_of(tmpl, mesh), opt_state)

        guard = self._active_guard()
        if guard is not None:
            guard.begin_fit()
            # the guard's device-side (bad, streak) counters ride the
            # optimizer-state carry; the guarded step unwraps them
            opt_state = (opt_state, guard.device_init())
        # >1-device mesh: commit every state leaf to its plan sharding and
        # capture the explicit in/out shardings the jitted steps are built
        # with (params/opt-state per the plan, guard counters replicated)
        _shard = None
        _coll_est = None
        if mesh is not None and mesh.size > 1:
            from zoo_tpu.parallel.plans import (
                ensure_placed,
                estimate_collective_bytes,
            )
            opt_state = ensure_placed(opt_state, mesh)
            _shard = self._state_shardings(params, opt_state)
            _plan = self._plan_name()
            _act_bytes = 0
            if _plan in ("pipeline", "moe"):
                # activation proxy at the stage/expert cut: one local
                # batch of input rows (the static estimate only needs
                # the order of magnitude the ring/all_to_all moves)
                _act_bytes = local_bs * sum(
                    int(np.prod(a.shape[1:], dtype=np.int64))
                    * a.dtype.itemsize for a in xs)
            _coll_est = {k: v for k, v in estimate_collective_bytes(
                trainable, mesh, _plan, activation_bytes=_act_bytes,
                n_microbatch=self._pipe_microbatches(
                    mesh.shape.get("pipe", 1))).items() if v}
        # boundary bookkeeping: per-epoch cumulative baselines so each
        # superbatch boundary sees window deltas (reset at epoch start)
        gb = {"loss": 0.0, "steps": 0, "bad": 0, "bad0": 0, "idx": None,
              "n": 0}

        def _guard_boundary(epoch, final=False):
            """Superbatch-boundary guard check: read the device counters
            (the only host sync the guard adds), escalate to rollback /
            preempt when the controller says so."""
            nonlocal params, opt_state, loss_sum, n_steps
            gb["n"] += 1
            if not (final or guard.preempt_requested
                    or gb["n"] % guard.config.check_every == 0):
                return
            inner, gstate = opt_state
            g = jax.device_get(gstate)
            cur = float(np.asarray(loss_sum)) if loss_sum is not None \
                else 0.0
            act = guard.on_boundary(
                bad_total=int(g["bad"]), streak=int(g["streak"]),
                window_loss=cur - gb["loss"],
                window_steps=n_steps - gb["steps"],
                global_step=self._step, epoch=epoch,
                batch_hint=gb["idx"])
            gb["loss"], gb["steps"], gb["bad"] = cur, n_steps, int(g["bad"])
            if act == "rollback":
                state, aux, lr_scale = guard.rollback()
                params = self._place(state["params"])
                tr, _ = _split_state(params)
                inner = aux if aux is not None else (
                    self.optimizer.init_fused(tr)
                    if getattr(self.optimizer, "fused", False)
                    else tx.init(tr))
                if _shard is not None and aux is not None:
                    # reshard-on-restore: the checkpointed opt state is
                    # host numpy; pin every moment back onto the SAME
                    # mesh layout the step was compiled for, so rollback
                    # under FSDP/TP keeps PR 4 semantics bit-unchanged
                    inner = jax.tree_util.tree_map(
                        lambda s, a: jax.device_put(a, s),
                        _shard[1][0], inner)
                hp = getattr(inner, "hyperparams", None)
                if lr_scale != 1.0 and hp is not None \
                        and "learning_rate" in hp:
                    hp["learning_rate"] = jnp.asarray(
                        float(np.asarray(hp["learning_rate"])) * lr_scale,
                        jnp.float32)
                opt_state = (inner, guard.device_init())
                if _shard is not None:
                    from zoo_tpu.parallel.plans import ensure_placed
                    opt_state = ensure_placed(opt_state, mesh)
                gb["bad"] = gb["bad0"] = 0
                if not final:
                    # the diverged pre-rollback losses must not leak
                    # into this epoch's reported loss/throughput: the
                    # epoch restarts its accumulators at the restore
                    # point (a rollback AT epoch end keeps them — that
                    # epoch really did diverge, and its loss says so)
                    loss_sum, n_steps = None, 0
                    gb["loss"], gb["steps"] = 0.0, 0
            elif act == "preempt":
                # commit the CURRENT state to the model so the owner's
                # save callback snapshots exactly this step, then save
                # (coordinated across hosts) and exit resume-don't-retry
                self.params = jax.device_get(params) if mesh is None \
                    else params
                self._opt_state = inner
                guard.preempt_checkpoint(step=self._step)

        rng = jax.random.PRNGKey(seed + 1)
        if _shard is not None:
            rng = jax.device_put(rng, _shard[2])
        nprng = np.random.RandomState(seed)
        val_arrays = None
        if validation_data is not None:
            val_arrays = data_utils.to_xy_arrays(
                validation_data[0] if isinstance(validation_data, tuple)
                else validation_data,
                validation_data[1] if isinstance(validation_data, tuple)
                and len(validation_data) > 1 else None,
                feature_cols, label_cols)
            val_arrays = (self._adapt_inputs(val_arrays[0]), val_arrays[1])
        history: Dict[str, List[float]] = {"loss": []}
        from zoo_tpu.orca.data.ingest import staged_pipeline
        arrs = xs + ys_list
        sample_bytes = sum(a[:1].nbytes for a in arrs)
        # Host→HBM transfers are chunked into SUPERBATCHES (many training
        # batches per device_put, ~64MB or 16 batches) and sliced on-device:
        # per-batch puts pay a full host→device round trip each, which no
        # depth-2 prefetch can hide. The staging thread still overlaps
        # transfer with compute.
        device_resident = all(hasattr(a, "devices") for a in arrs)
        if device_resident:
            # dataset already lives in HBM: slicing is device-side, so the
            # 64MB host-transfer budget does not apply; a deep scan group
            # amortizes per-dispatch overhead
            group = 64
        else:
            group = max(1, min(16, (64 << 20) // max(sample_bytes * local_bs,
                                                     1)))
        if pc > 1:
            # a staged multi-host global array cannot be host-sliced into
            # sub-batches; assemble exactly one global batch per put
            group = 1
        n_batches = max(n // local_bs, 1)
        prof = getattr(self, "_profiler", None)
        # k steps per dispatch via lax.scan. Not taken when: the profiler
        # needs per-step dispatch boundaries; multi-host (per-process
        # global assembly is one batch at a time); a caller interposed on
        # _jit_train (the elastic-retry fault-injection contract routes
        # every step through it); or the batch count has no divisor in
        # [2, group] (a ragged scan tail would force a second compile —
        # the plain path then keeps the transfer-chunked group as-is).
        scan_group = min(group, n_batches)
        while scan_group > 1 and n_batches % scan_group:
            scan_group -= 1
        # "interposed" = somebody replaced _jit_train with their own
        # wrapper (the elastic-retry fault-injection contract); our own
        # cached build (e.g. from a profiled fit) must not disable scan
        interposed = self._jit_train is not None \
            and self._jit_train is not getattr(self, "_own_jit_train", None)
        if not interposed and getattr(self, "_jit_mesh", None) != mesh:
            # cached steps bake their explicit shardings in; a context
            # switch to a different mesh (AutoML sub-meshes, re-init)
            # must rebuild them, never feed a stale-mesh executable
            self._drop_train_caches()
            self._jit_mesh = mesh
        # whole-epoch dispatch: small device-resident dataset on one chip
        # -> permutation-gather + full-epoch scan in ONE jit call per
        # epoch (see _build_epoch_train_step). The 256MB cap bounds the
        # permuted-copy HBM cost; the even-division requirement avoids a
        # ragged tail batch forcing a second compile.
        use_epoch = (device_resident and pc == 1
                     and prof is None and not interposed
                     and n % local_bs == 0 and n_batches >= 2
                     and sum(a.nbytes for a in arrs) <= (256 << 20))
        use_scan = scan_group > 1 and prof is None and pc == 1 \
            and not interposed and not use_epoch
        batch_ndims = [a.ndim for a in arrs]
        if use_epoch:
            if getattr(self, "_jit_epoch_cache", None) is None:
                self._jit_epoch_cache = {}
        elif use_scan:
            group = scan_group
            # getattr: instances unpickled from blobs predating _jit_multi
            if getattr(self, "_jit_multi", None) is None:
                self._jit_multi = self._build_multi_train_step(
                    self._step_shardings(_shard, batch_ndims, True))
        elif self._jit_train is None:
            self._jit_train = self._own_jit_train = \
                self._build_train_step(
                    self._step_shardings(_shard, batch_ndims, False))
        # host-fed path: stage superbatch slices into rotating
        # preallocated buffers (double-buffered device_put — the DMA of
        # superbatch k reads buffer A while k+1 is sliced into buffer
        # B). maybe_create allocates the buffers off XLA's zero-copy
        # alignment and probes each one, falling back to plain
        # allocation if device_put would alias it; multi-host keeps the
        # global-assembly path, and a multi-device CPU mesh is excluded
        # (its per-device placement semantics are not covered by the
        # probe).
        staging_pool = None
        if not device_resident and pc == 1 and (
                mesh is None or getattr(mesh, "size", 1) == 1
                or jax.default_backend() != "cpu"):
            from zoo_tpu.orca.data.ingest import StagingBufferPool
            staging_pool = StagingBufferPool.maybe_create(
                arrs, rows=group * local_bs)
        def _launch_epoch(epoch):
            """Dispatch every step of one epoch (the whole-epoch
            executable, or the staged superbatch loop)."""
            nonlocal params, opt_state, rng, loss_sum, n_steps
            if use_epoch:
                kk = n // local_bs
                # mesh identity in the key: the built closure bakes the
                # mesh in (sharding constraint), so a context change must
                # not reuse a stale-mesh epoch fn. Mesh is value-hashable
                # (axis names + device array incl. shape), unlike id()
                # which a GC'd mesh can leak to a new object.
                key = (kk, local_bs, bool(shuffle), mesh)
                je = self._jit_epoch_cache.get(key)
                if je is None:
                    je = self._jit_epoch_cache[key] = \
                        self._build_epoch_train_step(kk, local_bs,
                                                     bool(shuffle),
                                                     shard=_shard)
                extra_args = []
                if shuffle:
                    perm = nprng.permutation(n).astype(np.int32)
                    extra_args = [jnp.asarray(perm)]
                params, opt_state, rng, loss_sum = je(
                    params, opt_state, rng, *arrs, *extra_args)
                self._step += kk
                n_steps = kk
            else:
                if device_resident and (mesh is None or mesh.size == 1):
                    # HBM-resident dataset on one chip: gather + reshape for a
                    # whole superbatch in ONE jitted call. Python-level
                    # per-array slicing costs 2 dispatches per array — for
                    # small-sample models (NCF) that made the HBM-staged
                    # path slower than feeding from host.
                    if getattr(self, "_jit_stage", None) is None:
                        import functools

                        @functools.partial(jax.jit, static_argnums=(2, 3))
                        def _jit_stage(arrs, idx, k, bs):
                            out = [a[idx] for a in arrs]
                            if k:
                                out = [a.reshape((k, bs) + a.shape[1:])
                                       for a in out]
                            return out
                        self._jit_stage = _jit_stage

                    def _stage(idx):
                        k = len(idx) // local_bs if use_scan else 0
                        return self._jit_stage(arrs, jnp.asarray(idx), k,
                                               local_bs)
                    # device-side gather: one stage (the work IS the
                    # dispatch; splitting it buys nothing)
                    stages = [("stage", _stage)]
                else:
                    # host-fed path: separate slice and device-put
                    # stages, each on its own staging thread — the step
                    # on superbatch k overlaps the host→device transfer
                    # of k+1 AND the host slicing of k+2 (the async
                    # ingest pipeline; see orca/data/ingest.py).
                    # reset() reclaims buffers a prior epoch's teardown
                    # (error, guard rollback) stranded in flight; its
                    # generation token fences off that epoch's stage
                    # threads, which may still be running (the pipeline
                    # close() does not join) and must not touch THIS
                    # epoch's slots
                    pool_gen = (staging_pool.reset()
                                if staging_pool is not None else None)

                    def _slice(idx):
                        if staging_pool is not None:
                            sliced = staging_pool.take(arrs, idx,
                                                       gen=pool_gen)
                        else:
                            sliced = [a[idx] for a in arrs]
                        if guard is not None:
                            # chaos seam: armed tests corrupt the host
                            # batch in place (poison-batch injection);
                            # the idx hint feeds quarantine records
                            # (approximate — the slice stage runs one
                            # superbatch ahead of the step)
                            gb["idx"] = (int(idx[0]), int(idx[-1]))
                            from zoo_tpu.util.resilience import (
                                fault_point,
                            )
                            fault_point("fit.batch", arrays=sliced,
                                        idx=idx)
                        if use_scan:  # (k*bs,...) -> (k, bs, ...) for scan
                            sliced = [a.reshape((len(idx) // local_bs,
                                                 local_bs)
                                                + a.shape[1:])
                                      for a in sliced]
                        return sliced

                    def _put(sliced):
                        out = self._put_stacked(sliced) if use_scan \
                            else self._put_batch(sliced)
                        if staging_pool is not None:
                            # the buffer may be reused only after the
                            # host→device transfer has READ it; blocking
                            # here costs nothing — this IS the transfer
                            # stage's thread, and the step consumes
                            # `out` downstream anyway
                            jax.block_until_ready(out)
                            staging_pool.recycle(gen=pool_gen)
                        return out

                    stages = [("slice", _slice), ("device_put", _put)]

                # stage fns run on the pipeline's daemon threads; pin
                # the CALLER's runtime context (possibly a thread-local
                # sub-mesh scope, e.g. concurrent AutoML trials) so the
                # staged batches land on the same mesh as the params
                _caller_ctx = get_runtime_context(required=False)
                if _caller_ctx is not None:
                    from zoo_tpu.common.context import (
                        runtime_context_scope,
                    )

                    def _pin(fn, _ctx=_caller_ctx):
                        def pinned(item, _fn=fn):
                            with runtime_context_scope(_ctx):
                                return _fn(item)
                        return pinned

                    stages = [(name, _pin(fn)) for name, fn in stages]

                # depth=1: superbatches are large by design, and two
                # depth-2 stages would keep ~3 extra host copies
                # resident; one buffer per stage is all the overlap
                # needs (slice k+2 | transfer k+1 | step k)
                batches = staged_pipeline(
                    data_utils.batch_slices(n, local_bs, shuffle, nprng,
                                            group=group),
                    stages, depth=1)
                try:
                    with (prof.epoch_trace() if prof
                          else contextlib.nullcontext()):
                        source = (prof.timed_iter(iter(batches), "data")
                                  if prof else batches)
                        for staged in source:
                            if use_scan:
                                k = staged[0].shape[0]
                                params, opt_state, rng, loss = self._jit_multi(
                                    params, opt_state, rng, *staged)
                                self._step += k
                                n_steps += k
                                loss_sum = loss if loss_sum is None \
                                    else loss_sum + loss
                                if guard is not None:
                                    _guard_boundary(epoch)
                                continue
                            n_sub = (staged[0].shape[0] // local_bs
                                     if group > 1 else 1)
                            for j in range(n_sub):
                                if group > 1:
                                    # re-place the sub-slice so a multi-device
                                    # mesh keeps the guaranteed batch sharding
                                    # (device-to-device; a no-op on one chip)
                                    with (prof.phase("reshard") if prof
                                          else contextlib.nullcontext()):
                                        sub = self._put_batch(
                                            [t[j * local_bs:(j + 1) * local_bs]
                                             for t in staged])
                                else:
                                    sub = staged
                                if prof:
                                    with prof.phase("step"):
                                        params, opt_state, rng, loss = \
                                            self._jit_train(params, opt_state,
                                                            rng, *sub)
                                        if prof.sync:
                                            # sync so the phase measures the
                                            # real device step, not dispatch
                                            jax.block_until_ready(loss)
                                else:
                                    params, opt_state, rng, loss = \
                                        self._jit_train(params, opt_state,
                                                        rng, *sub)
                                self._step += 1
                                n_steps += 1
                                # running device-side sum: one host transfer
                                # per epoch (a per-step sync stalls the device
                                # on a host round trip every step)
                                loss_sum = loss if loss_sum is None \
                                    else loss_sum + loss
                            if guard is not None:
                                _guard_boundary(epoch)
                finally:
                    batches.close()

        def _epoch_host(epoch, epoch_loss, t0):
            """The host's work between two epochs: summaries,
            validation, plateau, printing."""
            from zoo_tpu.common.context import ZooContext
            if ZooContext.debug_nans and not np.isfinite(epoch_loss):
                raise FloatingPointError(
                    f"{self.name}: non-finite training loss "
                    f"({epoch_loss}) in epoch {epoch + 1} — NaN-check "
                    "mode (ZooContext.debug_nans) treats this as fatal; "
                    "jax_debug_nans should have pinpointed the producing "
                    "op above")
            if _coll_est:
                # static plan estimate x steps actually executed: the
                # obs-side answer to "what did this epoch move over ICI"
                for op_, nbytes_ in _coll_est.items():
                    _collective_bytes.labels(op=op_).inc(
                        float(nbytes_) * n_steps)
            history["loss"].append(epoch_loss)
            self.train_summary.add_scalar("Loss", epoch_loss, self._step)
            self.train_summary.add_scalar(
                "Throughput",
                n_steps * batch_size / max(time.perf_counter() - t0, 1e-9),
                self._step)
            if val_arrays is not None:
                vx, vy = val_arrays
                self.params = params  # evaluate on current params
                with (prof.phase("eval") if prof
                      else contextlib.nullcontext()):
                    val = self._evaluate_arrays(vx, vy, batch_size)
                for k, v in val.items():
                    history.setdefault("val_" + k, []).append(v)
                    self.validation_summary.add_scalar(k, v, self._step)
            if prof:
                for tag, val_ms in prof.epoch_scalars().items():
                    self.train_summary.add_scalar(tag, val_ms, self._step)
            plateau = getattr(self.optimizer, "plateau", None)
            if plateau is not None:
                mon = plateau.monitor
                if mon.lower() == "loss":
                    watched = epoch_loss
                else:
                    series = history.get(mon) or history.get("val_" + mon)
                    watched = series[-1] if series else None
                if watched is None:
                    import warnings
                    warnings.warn(
                        f"Plateau monitors '{mon}' but no such series was "
                        "produced this epoch (pass validation_data / the "
                        "metric); skipping lr adjustment")
                else:
                    new_lr = plateau.update(watched)
                    # inject_hyperparams keeps lr in the optimizer state, so
                    # the jitted step picks the new value up as an argument
                    _inner_opt = opt_state[0] if guard is not None \
                        else opt_state
                    new_lr = jnp.asarray(new_lr, dtype=jnp.float32)
                    if _shard is not None:
                        # keep the explicit in_shardings contract: every
                        # opt-state leaf stays mesh-placed (replicated)
                        new_lr = jax.device_put(new_lr, _shard[2])
                    _inner_opt.hyperparams["learning_rate"] = new_lr
            if verbose:
                extra = {k: v[-1] for k, v in history.items() if k != "loss"}
                print(f"Epoch {epoch + 1}/{nb_epoch} - loss: "
                      f"{epoch_loss:.4f}" +
                      "".join(f" - {k}: {v:.4f}" for k, v in extra.items()))

        # placement, building or fetching the step, staging: recorded
        # after the fact (ring and JSONL, no profiler annotation) so the
        # 270 lines above need no span to unwind on a bad argument
        d_setup = time.perf_counter() - t_fit
        emit_span("fit.setup", time.time() - d_setup, d_setup, t0=t_fit)
        for epoch in range(nb_epoch):
            with span("fit.epoch"):
                t0 = time.perf_counter()  # monotonic: NTP-proof Throughput
                loss_sum, n_steps = None, 0
                gb["loss"], gb["steps"] = 0.0, 0  # per-epoch baselines
                gb["bad0"] = gb["bad"]
                with span("fit.epoch.launch"):
                    _launch_epoch(epoch)
                # the epoch's one block on the device: its loss sum comes
                # to the host (with a guard, the guard's counters first)
                with span("fit.epoch.loss_sync"):
                    if guard is not None:
                        _guard_boundary(epoch, final=True)
                        # skipped steps contributed 0 to the sanitized
                        # loss sum; keep them out of the mean too
                        denom = max(
                            n_steps - max(0, gb["bad"] - gb["bad0"]), 1)
                    else:
                        denom = max(n_steps, 1)
                    if guard is not None and loss_sum is None:
                        # a mid-epoch rollback wiped every step of this
                        # epoch: the epoch effectively did not run and
                        # there is no honest loss to report. Raise the
                        # typed error the Estimator's retry perimeter
                        # turns into "restore the verified checkpoint and
                        # retrain the lost epoch" — the guard ladder's
                        # designed endWhen semantics
                        from zoo_tpu.orca.learn.guard import (
                            EpochRolledBack,
                        )
                        raise EpochRolledBack(
                            f"{self.name}: guard rollback wiped every "
                            f"step of epoch {epoch + 1}; retrain it from "
                            "the restored checkpoint")
                    epoch_loss = float(np.asarray(loss_sum)) / denom
                with span("fit.epoch.host"):
                    _epoch_host(epoch, epoch_loss, t0)
        with span("fit.params_to_host"):
            self.params = jax.device_get(params) if mesh is None \
                else params
        if guard is not None:
            opt_state = opt_state[0]  # shed the guard counters
        self._opt_state = opt_state
        return history

    # -- evaluation / inference -------------------------------------------
    def _shard_multiple(self) -> int:
        mesh = self._mesh()
        if mesh is None:
            return 1
        from zoo_tpu.parallel.mesh import data_axes
        denom = 1
        for a in data_axes(mesh):
            denom *= mesh.shape[a]
        return denom

    def _predict_arrays(self, xs, batch_size: int) -> np.ndarray:
        """Predictions for this process's rows. On a multi-host mesh each
        process feeds its local rows of the global batch and gets its local
        predictions back (``batch_size`` is global, like fit)."""
        if self._jit_pred is None:
            with _JIT_BUILD_LOCK:
                if self._jit_pred is None:
                    self._jit_pred = self._build_pred_step()
        params = self._place(self.params)
        n = data_utils.num_samples(xs)
        pc = jax.process_count()
        mult = max(1, self._shard_multiple() // pc)
        local_target = max(1, batch_size // pc)
        bs = max(mult, (min(local_target, n) // mult) * mult)
        mesh = self._mesh()
        outs = []
        for idx in data_utils.batch_slices(n, bs, False,
                                           drop_remainder=False):
            chunk = [a[idx] for a in xs]
            padded, real = data_utils.pad_batch(chunk, bs)
            preds = self._jit_pred(params, *self._put_batch(padded))
            if pc > 1:
                # bring back only this process's rows of the global output
                from jax.experimental import multihost_utils
                from zoo_tpu.parallel.mesh import batch_sharding

                def _localize(p):
                    out = multihost_utils.global_array_to_host_local_array(
                        p, mesh, batch_sharding(mesh, p.ndim).spec)
                    return jnp.asarray(out)

                preds = tuple(_localize(p) for p in preds) \
                    if isinstance(preds, tuple) else _localize(preds)
            # stays on device (lazy slice) — batches pipeline without a
            # per-batch host sync; ONE transfer at the end
            if isinstance(preds, tuple):
                outs.append(tuple(p[:real] if real != bs else p
                                  for p in preds))
            else:
                outs.append(preds[:real] if real != bs else preds)
        if outs and isinstance(outs[0], tuple):
            return tuple(np.asarray(jnp.concatenate([o[i] for o in outs],
                                                    axis=0))
                         for i in range(len(outs[0])))
        return np.asarray(jnp.concatenate(outs, axis=0))

    def _evaluate_arrays(self, xs, ys, batch_size) -> Dict[str, float]:
        """Exact STREAMING evaluation: per-batch loss/metric partials
        accumulate on device — O(1) host memory and one device→host sync
        regardless of dataset size (the reference streams its
        ValidationMethod aggregation per batch the same way; round-1
        materialized the full prediction array on host)."""
        if len(getattr(self, "outputs", [None])) > 1:
            # multi-output: combined loss over the heads; per-head metrics
            # are not aggregated (pass per-head eval sets instead)
            preds = self._predict_arrays(xs, batch_size)
            yt = [jnp.asarray(a) for a in ys] \
                if isinstance(ys, (list, tuple)) else jnp.asarray(ys)
            if self.loss_fn is None:
                return {}
            return {"loss": float(self.loss_fn(
                yt, tuple(jnp.asarray(p) for p in preds)))}
        if self._jit_pred is None:
            with _JIT_BUILD_LOCK:
                if self._jit_pred is None:
                    self._jit_pred = self._build_pred_step()
        params = self._place(self.params)
        ys = np.asarray(ys) if not hasattr(ys, "devices") else ys
        n = data_utils.num_samples(xs)
        mult = self._shard_multiple()
        bs = max(mult, (min(batch_size, n) // mult) * mult)
        loss_sum = None
        totals = {m.name: None for m in self.metrics}
        seen = 0
        for idx in data_utils.batch_slices(n, bs, False,
                                           drop_remainder=False):
            chunk = [a[idx] for a in xs]
            yb = ys[idx]
            padded, real = data_utils.pad_batch(chunk, bs)
            preds = self._jit_pred(params, *self._put_batch(padded))
            preds = preds[:real]  # lazy device slice, no sync
            yt = jnp.asarray(yb)
            if self.loss_fn is not None:
                contrib = self.loss_fn(yt, preds) * real
                loss_sum = contrib if loss_sum is None \
                    else loss_sum + contrib
            for m in self.metrics:
                s, c = m.batch_eval(yt, preds)
                prev = totals[m.name]
                totals[m.name] = (s, c) if prev is None \
                    else (prev[0] + s, prev[1] + c)
            seen += real
        out = {}
        if loss_sum is not None:
            out["loss"] = float(np.asarray(loss_sum)) / max(seen, 1)
        for m in self.metrics:
            s, c = totals[m.name]
            out[m.name] = float(np.asarray(m.finalize(s, c)))
        return out

    def evaluate(self, x, y=None, batch_size: int = 32,
                 feature_cols=None, label_cols=None) -> Dict[str, float]:
        """reference: ``KerasNet.evaluate`` ``Topology.scala:504``."""
        xs, ys = data_utils.to_xy_arrays(x, y, feature_cols, label_cols)
        xs = self._adapt_inputs(xs)
        if ys is None:
            raise ValueError("evaluate requires labels")
        if self.params is None:
            self.build(input_shapes=[(None,) + a.shape[1:] for a in xs])
        return self._evaluate_arrays(xs, ys, batch_size)

    def predict(self, x, batch_size: int = 256, feature_cols=None
                ) -> np.ndarray:
        """reference: ``KerasNet.predict`` (distributed Predictor.scala).
        Ragged tails are padded then trimmed (the reference pads per-thread
        batches for inference, ``tf_dataset.py`` per-thread batch)."""
        xs, _ = data_utils.to_xy_arrays(x, None, feature_cols, None)
        xs = self._adapt_inputs(xs)
        if self.params is None:
            self.build(input_shapes=[(None,) + a.shape[1:] for a in xs])
        return self._predict_arrays(xs, batch_size)

    # -- persistence -------------------------------------------------------
    def to_bytes(self) -> bytes:
        """Serialize the WHOLE model (architecture + weights) with
        cloudpickle — the rebuild of the reference's Scala module
        serialization (``SerializerSpec``-covered save/load round trips).
        jit caches and summaries are dropped; params go to host numpy."""
        import cloudpickle

        jt, je, jp = self._jit_train, self._jit_eval, self._jit_pred
        jm = getattr(self, "_jit_multi", None)
        jo = getattr(self, "_own_jit_train", None)
        jc = getattr(self, "_jit_epoch_cache", None)
        jmesh = getattr(self, "_jit_mesh", None)
        ts, vs, opt = self.train_summary, self.validation_summary, \
            self._opt_state
        prof = getattr(self, "_profiler", None)
        grd = getattr(self, "_guard", None)
        params = self.params
        try:
            self._jit_train = self._jit_eval = self._jit_pred = None
            self._jit_multi = None
            self._own_jit_train = None
            self._jit_stage = None
            self._jit_epoch_cache = None
            self._jit_mesh = None  # Mesh holds live Device handles
            self._opt_state = None
            self._profiler = None
            self._guard = None  # holds locks/events; owners re-attach
            self.train_summary = TrainSummary()
            self.validation_summary = TrainSummary()
            if params is not None:
                self.params = jax.tree_util.tree_map(np.asarray, params)
            return cloudpickle.dumps(self)
        finally:
            self._jit_train, self._jit_eval, self._jit_pred = jt, je, jp
            self._jit_multi = jm
            self._own_jit_train = jo
            self._jit_epoch_cache = jc
            self._jit_mesh = jmesh
            self.train_summary, self.validation_summary = ts, vs
            self._opt_state = opt
            self._profiler = prof
            self._guard = grd
            self.params = params

    def save(self, path: str):
        with open(path, "wb") as f:
            f.write(self.to_bytes())
        return path

    @staticmethod
    def load(path: str) -> "KerasNet":
        import cloudpickle

        with open(path, "rb") as f:
            return cloudpickle.load(f)

    def save_weights(self, path: str):
        host = jax.tree_util.tree_map(np.asarray, self.params)
        with open(path, "wb") as f:
            pickle.dump({"params": host, "step": self._step}, f)

    def load_weights(self, path: str):
        """Restore a ``save_weights`` blob. Params are keyed by layer
        position+type (``_param_keys``), so a checkpoint only restores
        into a structurally identical model — a mismatch (layer inserted/
        removed/retyped, or a shape change) is a hard error here, never a
        silent mis-restore."""
        with open(path, "rb") as f:
            blob = pickle.load(f)
        loaded = blob["params"]
        if self.params is None:
            try:  # materialize the model's own structure to validate
                self.build()
            except ValueError:
                pass  # input shape unknowable here: accept unvalidated
        if self.params is not None:
            def _shapes(tree):
                return {k: np.shape(v) for k, v in
                        jax.tree_util.tree_leaves_with_path(tree)}
            have, got = _shapes(self.params), _shapes(loaded)
            if have != got:
                missing = sorted(set(map(str, have)) - set(map(str, got)))
                extra = sorted(set(map(str, got)) - set(map(str, have)))
                changed = sorted(str(k) for k in have
                                 if k in got and have[k] != got[k])
                raise ValueError(
                    "checkpoint does not match this model's structure "
                    "(params are keyed by layer position+type, so layers "
                    "must match one-for-one). "
                    f"missing={missing[:5]} unexpected={extra[:5]} "
                    f"shape-changed={changed[:5]}")
        self.params = loaded
        self._step = blob.get("step", 0)
        return self

    def summary(self):
        lines = [f'Model: "{self.name}"', "-" * 60]
        total = 0
        params = self.params or {}
        for layer in self.layers:
            p = params.get(self._key_of(layer), {})
            cnt = layer.param_count(p)
            total += cnt
            lines.append(f"{layer.name:<30}{type(layer).__name__:<20}{cnt}")
        lines.append("-" * 60)
        lines.append(f"Total params: {total}")
        print("\n".join(lines))
        return total


class Sequential(KerasNet):
    """Linear stack (reference: ``Sequential`` ``Topology.scala:1029``,
    Python ``keras/engine/topology.py:49``)."""

    def __init__(self, name: Optional[str] = None):
        super().__init__(name=name)
        self._layers: List[Layer] = []

    @property
    def layers(self) -> List[Layer]:
        return self._layers

    def add(self, layer: Layer) -> "Sequential":
        self._layers.append(layer)
        self.params = None  # invalidate
        return self

    def _input_shapes(self):
        if self._layers and self._layers[0].batch_input_shape is not None:
            return [self._layers[0].batch_input_shape]
        return None

    def _init_params(self, rng, input_shapes) -> Dict:
        shape = tuple(input_shapes[0])
        params: Dict = {}
        for layer in self._layers:
            rng, sub = jax.random.split(rng)
            params[self._key_of(layer)] = layer.build(sub, shape)
            shape = layer.compute_output_shape(shape)
        return params

    def _forward(self, params, inputs: List, *, training, rng, collect):
        h = inputs[0] if len(inputs) == 1 else inputs
        body = params.get(_PIPE_BODY_KEY) \
            if isinstance(params, dict) else None
        body_keys = set(getattr(self, "_pipe_body_keys", ()) or ())
        body_done = False
        for layer in self._layers:
            key = self._key_of(layer)
            if body is not None and key in body_keys:
                # the stacked homogeneous run applies as one unit (GPipe
                # schedule / scan) at the position of its first layer
                if not body_done:
                    h = self._apply_pipe_body(body, h, training=training)
                    body_done = True
                continue
            p = params.get(key, {})
            if collect is not None and hasattr(layer, "updated_stats") \
                    and training:
                collect[key] = {"stats": layer.updated_stats(p, h)}
            h = layer.call(p, h, training=training, rng=rng)
        return h

    # -- pipeline plan: body detection + stacking -------------------------
    def _find_pipe_body(self, params):
        """The longest contiguous run of layers with identical type,
        config, and param-tree signature — the candidate pipeline body.
        Returns ``(keys, template_layer)``; loud when no run exists."""
        def cfg_sig(layer):
            out = []
            for k, v in sorted(vars(layer).items()):
                if k.startswith("_") or k == "name":
                    continue
                if callable(v):
                    out.append((k, getattr(v, "__name__", str(type(v)))))
                elif isinstance(v, (int, float, str, bool, tuple)):
                    out.append((k, v))
                elif isinstance(v, list):
                    out.append((k, tuple(str(e) for e in v)))
            return tuple(out)

        best, cur, prev_sig = [], [], object()
        for layer in self._layers:
            p = params.get(self._key_of(layer), {})
            leaves = jax.tree_util.tree_flatten_with_path(p)[0]
            sig = None if not leaves else (
                type(layer).__name__, cfg_sig(layer),
                tuple((jax.tree_util.keystr(kp), tuple(np.shape(leaf)),
                       str(getattr(leaf, "dtype", "")))
                      for kp, leaf in leaves))
            if sig is not None and sig == prev_sig:
                cur.append(layer)
            else:
                cur = [layer] if sig is not None else []
            prev_sig = sig
            if len(cur) > len(best):
                best = list(cur)
        if len(best) < 2:
            raise ValueError(
                "plan='pipeline' needs a contiguous run of >= 2 "
                "identical layers (same type, config, and param "
                "shapes) to stage; this model has none")
        return [self._key_of(layer) for layer in best], best[0]

    def _stack_pipe_body(self, params):
        """Stack the body run's per-layer param dicts into one
        ``__pipe_body__`` entry with a leading layer dim — the tensor
        layout ``stack_stages`` splits and the pipeline plan shards
        over the ``pipe`` mesh axis."""
        if not isinstance(params, dict) or _PIPE_BODY_KEY in params:
            return params  # already stacked (compile-after-build)
        keys, template = self._find_pipe_body(params)
        body = [params[k] for k in keys]
        if any("stats" in p for p in body if isinstance(p, dict)):
            raise ValueError(
                "plan='pipeline' body layers must be stateless (the "
                "stacked stage scan cannot collect per-layer running "
                "stats); move BatchNorm-style layers out of the run")
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *body)
        out = {k: v for k, v in params.items() if k not in set(keys)}
        out[_PIPE_BODY_KEY] = stacked
        self._pipe_body_keys = tuple(keys)
        self._pipe_template = template
        return out

    def get_output_shape(self):
        shapes = self._input_shapes()
        shape = shapes[0]
        for layer in self._layers:
            shape = layer.compute_output_shape(shape)
        return shape


def Input(shape: Tuple, name: Optional[str] = None) -> KTensor:
    """Symbolic input (reference: ``Input`` in
    ``keras/engine/topology.py``; shape excludes batch)."""
    return KTensor((None,) + tuple(shape))


class Model(KerasNet):
    """Functional graph model (reference: ``Model`` ``Topology.scala:1145``
    Python ``keras/models.py``)."""

    def __init__(self, input: Union[KTensor, Sequence[KTensor]],
                 output: Union[KTensor, Sequence[KTensor]],
                 name: Optional[str] = None):
        super().__init__(name=name)
        self.inputs = list(input) if isinstance(input, (list, tuple)) \
            else [input]
        self.outputs = list(output) if isinstance(output, (list, tuple)) \
            else [output]
        self.output = self.outputs[0]  # back-compat single-output attr
        self._topo = self._toposort()

    def _toposort(self) -> List[KTensor]:
        seen, order = set(), []

        def visit(node: KTensor):
            if id(node) in seen:
                return
            seen.add(id(node))
            for parent in node.inbound:
                visit(parent)
            order.append(node)

        for out in self.outputs:
            visit(out)
        for t in self.inputs:
            if id(t) not in seen:
                raise ValueError("an input tensor is not connected to output")
        return order

    @property
    def layers(self) -> List[Layer]:
        out, seen = [], set()
        for node in self._topo:
            if node.layer is not None and id(node.layer) not in seen:
                seen.add(id(node.layer))
                out.append(node.layer)
        return out

    def _input_shapes(self):
        return [t.shape for t in self.inputs]

    def _init_params(self, rng, input_shapes) -> Dict:
        params: Dict = {}
        shapes = {id(t): tuple(s) for t, s in zip(self.inputs, input_shapes)}
        for node in self._topo:
            if node.layer is None:
                continue
            in_shapes = [shapes[id(p)] for p in node.inbound]
            arg = in_shapes if len(in_shapes) > 1 else in_shapes[0]
            key = self._key_of(node.layer)
            if key not in params:  # shared layers build once
                rng, sub = jax.random.split(rng)
                params[key] = node.layer.build(sub, arg)
            shapes[id(node)] = node.layer.compute_output_shape(arg)
        return params

    def _forward(self, params, inputs: List, *, training, rng, collect):
        values = {id(t): v for t, v in zip(self.inputs, inputs)}
        for node in self._topo:
            if node.layer is None:
                if id(node) not in values:
                    raise ValueError("missing input value")
                continue
            args = [values[id(p)] for p in node.inbound]
            arg = args if len(args) > 1 else args[0]
            key = self._key_of(node.layer)
            p = params.get(key, {})
            if collect is not None and hasattr(node.layer, "updated_stats") \
                    and training:
                collect[key] = {
                    "stats": node.layer.updated_stats(p, arg)}
            values[id(node)] = node.layer.call(p, arg, training=training,
                                               rng=rng)
        if len(self.outputs) == 1:
            return values[id(self.output)]
        return tuple(values[id(o)] for o in self.outputs)
