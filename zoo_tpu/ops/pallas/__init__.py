"""Pallas TPU kernels for the hot ops.

The reference reaches native compute through JVM bindings (BigDL MKL-DNN,
libtensorflow JNI — SURVEY §2.9); here the native layer is Pallas kernels
compiled by Mosaic for the TPU's MXU/VPU:

- ``flash_attention`` — blockwise online-softmax attention (net-new vs the
  reference's dense ``TransformerLayer.scala:279`` math; required for the
  long-context path, SURVEY §5.7).
- ``quantized_matmul`` / ``quantize_int8`` — int8 inference path, the TPU
  equivalent of the reference's OpenVINO VNNI int8 story
  (``examples/vnni``, SURVEY §2.9(4)).
- ``fused_apply_sgd`` / ``fused_apply_adam`` — fused optimizer update, the
  TPU equivalent of BigDL's slice-wise parameter-manager "aggregate +
  apply" step (``docs/docs/wp-bigdl.md:146-160``).

Every kernel takes ``interpret=None`` and auto-falls-back to the Pallas
interpreter off-TPU so the hermetic CPU-mesh test rig (tests/conftest.py)
exercises the same code path CI-side.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128       # VPU lane width; minor dim of every scratch carrier
SUBLANES = 8      # f32 sublane count


def on_tpu() -> bool:  # zoo-lint: config-parse
    """True when jax's default backend is the TPU — the one fact every
    ``auto`` resolver (kernel vs reference, interpret vs compile, KV
    dtype) keys on. It asks the backend and nothing else: a process
    with no usable backend raises here instead of quietly reading as
    "not a TPU" and interpreting everything. The choice each resolver
    landed on is recorded where it is made (``llm_stats``, the model
    attributes), and ``chip_smoke.py`` asserts it on the chip.
    ``ZOO_PALLAS_FORCE_INTERPRET=1`` is the kill switch if a TPU
    cannot take the Mosaic kernels."""
    import os
    if os.environ.get("ZOO_PALLAS_FORCE_INTERPRET", "") in ("1", "true"):
        return False
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """None → interpret off-TPU, compile on TPU."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def pad_dim(x, axis: int, mult: int):
    """Zero-pad ``axis`` of ``x`` up to a multiple of ``mult``."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


from zoo_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from zoo_tpu.ops.pallas.paged_decode import paged_flash_decode  # noqa: E402
from zoo_tpu.ops.pallas.paged_prefill import paged_flash_prefill  # noqa: E402
from zoo_tpu.ops.pallas.sparse_decode import sparse_paged_decode  # noqa: E402
from zoo_tpu.ops.pallas.dsa_decode import dsa_paged_decode  # noqa: E402
from zoo_tpu.ops.pallas.lightning import lightning_decode  # noqa: E402
from zoo_tpu.ops.pallas.quant import (  # noqa: E402
    quantize_int8, quantized_matmul, quantized_dense,
    fused_quantized_matmul, resolve_int8_matmul,
    quantize_conv_weights, quantized_conv2d)
from zoo_tpu.ops.pallas.conv import (  # noqa: E402
    conv2d, conv2d_int8, resolve_conv_impl)
from zoo_tpu.ops.pallas.fused_optim import (  # noqa: E402
    fused_apply_sgd, fused_apply_adam)
from zoo_tpu.ops.pallas.fused_block import fused_bottleneck  # noqa: E402

__all__ = ["flash_attention", "paged_flash_decode",
           "paged_flash_prefill", "sparse_paged_decode",
           "dsa_paged_decode", "lightning_decode", "quantize_int8",
           "quantized_matmul", "fused_quantized_matmul",
           "resolve_int8_matmul",
           "quantized_dense", "quantize_conv_weights", "quantized_conv2d",
           "conv2d", "conv2d_int8", "resolve_conv_impl",
           "fused_apply_sgd", "fused_apply_adam", "fused_bottleneck",
           "on_tpu", "resolve_interpret"]
