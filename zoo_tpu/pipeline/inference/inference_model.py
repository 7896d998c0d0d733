"""InferenceModel — thread-safe multi-backend inference holder.

Rebuild of ``pipeline/inference/InferenceModel.scala`` (657 LoC; loads
BigDL/Caffe/OpenVINO/TF/Torch with ``supported_concurrent_num`` controlling
a blocking pool of model copies) and the Python wrapper
``pyzoo/zoo/pipeline/inference/inference_model.py:24``.

On TPU there are no model copies: a jitted XLA executable is pure and
reentrant, so ``supported_concurrent_num`` maps to a semaphore that bounds
in-flight predict calls (protecting HBM, not correctness). Loading AOT
warm-compiles the forward for the configured batch size (the reference's
OpenVINO ahead-of-time IR compile maps to ``jit(...).lower().compile()``).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import numpy as np


class InferenceModel:
    def __init__(self, supported_concurrent_num: int = 1):
        self._sem = threading.Semaphore(supported_concurrent_num)
        self.supported_concurrent_num = supported_concurrent_num
        self._model = None
        self._batch_size: Optional[int] = None

    # -- loaders (reference: doLoad* family) -------------------------------
    def load_keras(self, model, batch_size: Optional[int] = None,
                   example_input: Optional[Sequence[np.ndarray]] = None):
        """Hold a zoo_tpu Keras-facade model; AOT-compile at ``batch_size``
        when example input is derivable."""
        self._model = model
        self._batch_size = batch_size
        if batch_size and model.params is not None:
            shapes = model._built_shapes or model._input_shapes()
            if example_input is None and shapes:
                example_input = [np.zeros((batch_size,) + tuple(s[1:]),
                                          np.float32) for s in shapes]
            if example_input is not None:
                model.predict(example_input if len(example_input) > 1
                              else example_input[0],
                              batch_size=batch_size)  # warm compile
        return self

    def load(self, path: str, batch_size: Optional[int] = None,  # zoo-lint: config-parse
             quantize: bool = False):
        """Load a full serialized zoo model (reference: ``doLoadBigDL``;
        ``quantize=True`` is the int8 path, reference
        ``doLoadOpenVINOInt8`` ``InferenceModel.scala:283``). The
        inference loaders quantize in ``auto`` mode: int8 is kept only
        when it measures faster than the float forward on the current
        backend (override with ``ZOO_INT8_MODE=force|off``)."""
        from zoo_tpu.pipeline.api.keras.engine.topology import KerasNet
        model = KerasNet.load(path)
        if quantize:
            model = quantize_model(
                model,
                mode=os.environ.get("ZOO_INT8_MODE") or "auto")
        return self.load_keras(model, batch_size=batch_size)

    def load_caffe(self, def_path: Optional[str], model_path: str,
                   batch_size: Optional[int] = None):
        """reference: ``doLoadCaffe`` — Caffe deploy net + weights."""
        from zoo_tpu.models.caffe_loader import load_caffe
        return self.load_keras(load_caffe(def_path, model_path),
                               batch_size=batch_size)

    def load_onnx(self, path_or_bytes, batch_size: Optional[int] = None):
        """ONNX graph as an inference holder (reference ONNX loader)."""
        from zoo_tpu.pipeline.api.onnx.onnx_loader import load_onnx
        return self.load_keras(load_onnx(path_or_bytes),
                               batch_size=batch_size)

    def load_encrypted(self, path: str, secret: str, salt: str,  # zoo-lint: config-parse
                       key_len: int = 128, mode: str = "cbc",
                       batch_size: Optional[int] = None,
                       quantize: bool = False):
        """Load an encrypted-at-rest zoo model (reference:
        ``doLoadEncrypted*`` via ``EncryptSupportive.scala:27``). The file
        is decrypted in memory only — plaintext never touches disk."""
        import cloudpickle

        from zoo_tpu.ppml.crypto import EncryptSupportive
        blob = EncryptSupportive.decrypt_file(path, secret, salt,
                                              key_len=key_len, mode=mode)
        model = cloudpickle.loads(blob)
        if quantize:
            model = quantize_model(
                model,
                mode=os.environ.get("ZOO_INT8_MODE") or "auto")
        return self.load_keras(model, batch_size=batch_size)

    def load_tf(self, model_or_path, batch_size: Optional[int] = None,
                example_inputs=None, signature: str = "serving_default"):
        """Load a TF model for inference (reference: ``doLoadTF`` /
        ``TFNet.scala:56``): a SavedModel directory path, a tf.keras model,
        or any tf.function-able callable. The graph is frozen and
        interpreted in JAX (``zoo_tpu.bridges.tf_graph``)."""
        from zoo_tpu.bridges.tf_graph import (
            TFGraphWrapper,
            convert_tf_callable,
            load_saved_model,
        )

        if isinstance(model_or_path, str):
            g = load_saved_model(model_or_path, signature=signature)
        else:
            if example_inputs is None:
                raise ValueError("pass example_inputs= for non-SavedModel "
                                 "TF objects")
            g = convert_tf_callable(model_or_path, list(example_inputs))
        self._model = TFGraphWrapper(g)
        self._batch_size = batch_size
        return self

    def load_torch(self, torch_model, input_shape=None,
                   batch_size: Optional[int] = None,
                   example_inputs=None, input_dtype="float32"):
        """reference: ``doLoadPyTorch`` — via the torch.export fx bridge
        (arbitrary forward graphs, not just Sequential). Pass
        ``example_inputs`` (list of arrays, batch dim included) for
        multi-input or non-float models, or ``input_dtype`` (e.g. "int32"
        for embedding-first nets) with ``input_shape``."""
        import numpy as _np

        from zoo_tpu.bridges.fx_bridge import torch_to_graph_net
        if example_inputs is None:
            if input_shape is None:
                raise ValueError("pass input_shape= or example_inputs=")
            example_inputs = [_np.zeros((2,) + tuple(input_shape),
                                        _np.dtype(input_dtype))]
        return self.load_keras(
            torch_to_graph_net(torch_model, list(example_inputs)),
            batch_size=batch_size)

    # -- inference ---------------------------------------------------------
    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Blocking-pool predict (reference: ``doPredict`` takes a copy from
        the blocking queue; here the semaphore bounds concurrency)."""
        if self._model is None:
            raise RuntimeError("no model loaded")
        bs = batch_size or self._batch_size or 256
        with self._sem:
            return self._model.predict(x, batch_size=bs)

    @property
    def model(self):
        return self._model


def save_encrypted(model, path: str, secret: str, salt: str,
                   key_len: int = 128, mode: str = "cbc"):
    """Serialize a zoo model encrypted at rest (counterpart of
    ``InferenceModel.load_encrypted``; reference ``EncryptSupportive``).
    Serialization happens in memory — plaintext never touches disk."""
    from zoo_tpu.ppml.crypto import EncryptSupportive
    enc = (EncryptSupportive.encrypt_bytes_with_aes_cbc if mode == "cbc"
           else EncryptSupportive.encrypt_bytes_with_aes_gcm)
    with open(path, "wb") as f:
        f.write(enc(model.to_bytes(), secret, salt, key_len))
    return path


# auto mode keeps int8 only when it beats the float forward by this
# factor (one constant, one decision rule)
INT8_MIN_SPEEDUP = 1.05


def _copy_tree(tree):
    """Shallow-copy every nested dict of a params tree (leaf arrays
    shared) — enough to undo the in-place W → W_q/W_scale rewrite."""
    if isinstance(tree, dict):
        return {k: _copy_tree(v) for k, v in tree.items()}
    return tree


#: (architecture fingerprint, sample shapes, sample_batch) -> (path,
#: speedup). An auto verdict is a property of the architecture and the
#: backend, not the weight values — re-quantizing the same topology
#: (rolling reloads, A/B replicas, per-request model copies) reuses the
#: measured verdict instead of paying the microbench again.
_AUTO_VERDICT_CACHE: dict = {}


def _model_fingerprint(model) -> tuple:
    """Architecture identity for the auto-verdict cache: layer types in
    order plus every param leaf's path/shape/dtype (values excluded)."""
    import jax

    layers = tuple(type(l).__name__ for l in getattr(model, "layers", ()))
    leaves = tuple(
        (jax.tree_util.keystr(kp), tuple(v.shape), str(v.dtype))
        for kp, v in jax.tree_util.tree_leaves_with_path(model.params))
    return (layers, leaves)


def _publish_quant_path(path: str, speedup: Optional[float]) -> None:
    """Record every quantize_model decision in the scrape — the chosen
    path is never silent. Prior verdicts flip to 0 (info-gauge style,
    like ``zoo_registry_version_info``) so exactly one series is 1."""
    from zoo_tpu.obs.metrics import gauge

    fam = gauge(
        "zoo_quant_path_info",
        "int8 quantization path chosen by quantize_model (1 = current "
        "verdict) with the measured int8/float speedup as a label "
        "(\"-\" when the mode skipped the microbench)",
        labels=("path", "speedup"))
    for child in fam.children():
        child.set(0.0)
    fam.labels(path=path,
               speedup="-" if speedup is None else f"{speedup:.3f}"
               ).set(1.0)


def _time_forward(model, xs, reps: int = 3) -> float:
    """Samples/s of the jitted forward over device-warm inputs (compile
    excluded by a warm-up call). Module-level so tests can stub it."""
    import time

    import jax

    step = model._build_pred_step()
    params = model.params
    out = step(params, *xs)
    jax.block_until_ready(out)
    n = xs[0].shape[0] * reps
    t0 = time.perf_counter()
    for _ in range(reps):
        out = step(params, *xs)
    jax.block_until_ready(out)
    return n / max(time.perf_counter() - t0, 1e-9)


def _apply_int8(model):
    from zoo_tpu.ops.pallas.quant import (
        quantize_conv_weights,
        quantize_int8,
    )
    from zoo_tpu.pipeline.api.keras.layers.convolutional import (
        Convolution2D,
    )
    from zoo_tpu.pipeline.api.keras.layers.core import Dense

    dense_keys = {model._key_of(l) for l in model.layers
                  if isinstance(l, Dense)}
    conv_keys = {model._key_of(l) for l in model.layers
                 if isinstance(l, Convolution2D)}

    def walk(tree):
        for key, val in list(tree.items()):
            if isinstance(val, dict):
                if key in dense_keys and "W" in val:
                    w = val.pop("W")
                    w_q, w_scale = quantize_int8(w, axis=0)
                    val["W_q"], val["W_scale"] = w_q, w_scale
                elif key in conv_keys and "W" in val:
                    w = val.pop("W")
                    val["W_q"], val["W_scale"] = quantize_conv_weights(w)
                else:
                    walk(val)

    walk(model.params)
    model._jit_pred = model._jit_eval = model._jit_train = None
    model._quantized = True  # inference-only: fit() refuses cleanly


def quantize_model(model, mode: Optional[str] = None,  # zoo-lint: config-parse
                   min_speedup: float = INT8_MIN_SPEEDUP,
                   sample_batch: int = 8):
    """Post-training int8 quantization of every Dense and Conv2D weight
    (per-output-channel symmetric); the forward then runs the int8 MXU
    matmul / int8 conv (``ops/pallas/quant.py``). TPU equivalent of the
    reference's OpenVINO int8 IR path (``doLoadOpenVINOInt8``) and the
    VNNI int8 story — whose headline use is conv-net inference
    (SSD/VGG, ``wp-bigdl.md:192-196``).

    ``mode`` (default ``"force"`` for API compatibility; the
    ``InferenceModel`` loaders default to ``"auto"``. Env
    ``ZOO_INT8_MODE`` fills in an UNSPECIFIED mode only — an explicit
    ``mode=`` argument always wins, so programmatic callers cannot be
    silently redirected by ambient environment):

    * ``"force"`` — always quantize (the historical behavior);
    * ``"off"`` — return the model unquantized;
    * ``"auto"`` — **measure-or-fallback**: quantize, microbench the
      int8 forward against the float forward at ``sample_batch`` rows,
      and KEEP int8 only if it wins by ``min_speedup``; otherwise
      restore the float weights (BENCH_r05 measured int8 ResNet-50
      *0.974x* the bf16 path — slower — on the current backend, so an
      unconditional int8 serve path was a pessimization).

    The chosen path is recorded on the model as ``_quant_path``
    (``"int8"`` / ``"bf16-fallback"`` / ``"bf16"``) with the measured
    ratio in ``_quant_speedup`` when auto measured one.
    """
    import logging

    mode = mode or os.environ.get("ZOO_INT8_MODE") or "force"
    if mode not in ("auto", "force", "off"):
        raise ValueError(f"unknown int8 mode {mode!r} "
                         "(expected auto|force|off)")
    if mode == "off":
        model._quant_path = "bf16"
        _publish_quant_path("bf16", None)
        return model
    if model.params is None:
        raise ValueError("model must be built before quantization")
    if mode == "force":
        _apply_int8(model)
        model._quant_path = "int8"
        _publish_quant_path("int8", None)
        return model

    # auto: measure int8 against float on this backend, fall back when
    # it doesn't win
    shapes = getattr(model, "_built_shapes", None) or \
        model._input_shapes()
    xs = None
    if shapes:
        try:
            xs = [np.zeros((sample_batch,) + tuple(s[1:]), np.float32)
                  for s in shapes]
        except TypeError:
            xs = None
    if xs is None:
        # nothing to measure with: behave like force (documented)
        _apply_int8(model)
        model._quant_path = "int8"
        _publish_quant_path("int8", None)
        return model
    key = (_model_fingerprint(model),
           tuple(tuple(x.shape) for x in xs), float(min_speedup))
    cached = _AUTO_VERDICT_CACHE.get(key)
    if cached is not None:
        # same architecture + sample shapes on this backend: replay the
        # verdict instead of re-benching (common under rolling reloads)
        path, speedup = cached
        model._quant_speedup = speedup
        model._quant_path = path
        if path == "int8":
            _apply_int8(model)
        _publish_quant_path(path, speedup)
        return model
    float_rate = _time_forward(model, xs)
    saved = _copy_tree(model.params)
    _apply_int8(model)
    int8_rate = _time_forward(model, xs)
    speedup = int8_rate / max(float_rate, 1e-9)
    model._quant_speedup = speedup
    if speedup >= min_speedup:
        model._quant_path = "int8"
        _AUTO_VERDICT_CACHE[key] = ("int8", speedup)
        _publish_quant_path("int8", speedup)
        return model
    # int8 loses on this backend: restore the float weights
    model.params = saved
    model._jit_pred = model._jit_eval = model._jit_train = None
    model._quantized = False
    model._quant_path = "bf16-fallback"
    _AUTO_VERDICT_CACHE[key] = ("bf16-fallback", speedup)
    _publish_quant_path("bf16-fallback", speedup)
    logging.getLogger(__name__).info(
        "int8 quantization measured %.3fx the float forward (< %.2fx "
        "threshold) on this backend — serving the bf16 path instead",
        speedup, min_speedup)
    return model
