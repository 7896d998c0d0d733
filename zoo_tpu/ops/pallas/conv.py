"""Implicit-GEMM convolution kernels (Pallas/MXU) and the one conv
dispatch point.

The training-side roofline stalls on convs: the XLA conv path measures
~0.197 MFU at ResNet-50's dominant shapes (BENCH_r05) while the MXU
sits idle between im2col materializations. These kernels lower
ResNet-50's 1x1/3x3 shapes to implicit GEMM — no im2col buffer ever
exists in HBM:

* **1x1**: a tiled matmul over the flattened spatial axis (stride
  handled by pre-slicing rows/cols, which for k=1 is exactly SAME and
  VALID semantics);
* **3x3 (stride 1)**: the whole spatially-padded input image streams
  through VMEM once per batch element; the kernel walks the 9 taps as
  static halo-shifted views of that resident block and accumulates all
  taps into one f32/int32 register accumulator feeding the same MXU
  call.

:func:`resolve_conv_impl` is the single selection rule (the XLA
``lax.conv`` reference unless ``impl``/``ZOO_CONV_IMPL`` asks for
``pallas``) used by the Keras conv layers and the int8 conv path, so
float/int8 and impl selection compose.

Every kernel runs off-TPU under Pallas interpret mode
(``ZOO_PALLAS_FORCE_INTERPRET=1`` or ``interpret=True``), which is how
the parity suites gate correctness on the CPU test mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from zoo_tpu.common import knobs
from zoo_tpu.ops.pallas import LANES as _LANES
from zoo_tpu.ops.pallas import SUBLANES as _SUBLANES
from zoo_tpu.ops.pallas import pad_dim as _pad_dim
from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret

__all__ = [
    "conv2d",
    "conv2d_int8",
    "resolve_conv_impl",
    "pallas_conv_supported",
]

_DN = ("NHWC", "HWIO", "NHWC")


def pallas_conv_supported(kernel: Tuple[int, int],
                          strides: Tuple[int, int] = (1, 1),
                          dilation: Tuple[int, int] = (1, 1)) -> bool:
    """Shapes the implicit-GEMM kernels cover: any-stride 1x1 (pre-
    sliced to a pure GEMM) and stride-1 3x3 (halo-walk). Everything
    else is the reference conv's job."""
    if tuple(dilation) != (1, 1):
        return False
    k = tuple(kernel)
    if k == (1, 1):
        return True
    return k == (3, 3) and tuple(strides) == (1, 1)


def resolve_conv_impl(impl: Optional[str] = None, *,
                      kernel: Tuple[int, int],
                      strides: Tuple[int, int] = (1, 1),
                      dilation: Tuple[int, int] = (1, 1)) -> str:
    """The one conv dispatch rule → ``"pallas"`` or ``"reference"``.

    ``impl=None`` reads ``ZOO_CONV_IMPL`` (``auto`` | ``pallas`` |
    ``reference``). ``auto`` is the XLA reference conv on every
    platform: the conv layers call this seam from ``fit``, and the
    implicit-GEMM kernel has no ``custom_vjp`` — ``jax.grad`` through
    it fails — and no device measurement on either side of the choice
    yet (ROADMAP S6 decides). The kernel stays reachable by an
    explicit ``pallas``, which on an unsupported shape fails loudly
    rather than silently falling back."""
    impl = impl or knobs.value("ZOO_CONV_IMPL")
    if impl not in ("auto", "pallas", "reference"):
        raise ValueError(f"unknown conv impl {impl!r} "
                         "(expected auto|pallas|reference)")
    supported = pallas_conv_supported(kernel, strides, dilation)
    if impl == "pallas":
        if not supported:
            raise ValueError(
                f"ZOO_CONV_IMPL=pallas but kernel={tuple(kernel)} "
                f"strides={tuple(strides)} dilation={tuple(dilation)} "
                "is outside the implicit-GEMM kernel's envelope "
                "(1x1 any stride, 3x3 stride 1)")
        return "pallas"
    return "reference"


def _spatial_pads(h: int, w: int, kh: int, kw: int,
                  strides: Tuple[int, int], padding: str):
    """XLA-convention SAME/VALID pads + output spatial dims."""
    sh, sw = strides
    padding = padding.upper()
    if padding == "VALID":
        return (0, 0), (0, 0), (h - kh) // sh + 1, (w - kw) // sw + 1
    if padding != "SAME":
        raise ValueError(f"unsupported padding {padding!r}")
    oh = -(-h // sh)
    ow = -(-w // sw)
    th = max((oh - 1) * sh + kh - h, 0)
    tw = max((ow - 1) * sw + kw - w, 0)
    return (th // 2, th - th // 2), (tw // 2, tw - tw // 2), oh, ow


def _conv_kernel(x_ref, w_ref, o_ref, *, taps, oh, ow):
    """Float implicit GEMM: all taps accumulate into one register
    accumulator; each tap is a static halo-shifted view of the
    VMEM-resident image block, flattened to (OH*OW, C) for the MXU."""
    c = x_ref.shape[-1]
    acc = jnp.zeros((oh * ow, o_ref.shape[-1]), jnp.float32)
    for t, (dy, dx) in enumerate(taps):
        xt = x_ref[0, dy:dy + oh, dx:dx + ow, :].reshape(oh * ow, c)
        acc += jax.lax.dot_general(
            xt, w_ref[t], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(1, oh, ow, -1).astype(o_ref.dtype)


def _conv_kernel_q(x_ref, w_ref, xs_ref, ws_ref, o_ref, *, taps, oh, ow):
    """Int8 implicit GEMM: int8×int8→int32 tap accumulation, per-image
    activation scale × per-output-channel weight scale dequant fused
    into the epilogue (the paged-kernel in-register dequant idiom)."""
    c = x_ref.shape[-1]
    acc = jnp.zeros((oh * ow, o_ref.shape[-1]), jnp.int32)
    for t, (dy, dx) in enumerate(taps):
        xt = x_ref[0, dy:dy + oh, dx:dx + ow, :].reshape(oh * ow, c)
        acc += jax.lax.dot_general(
            xt, w_ref[t], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    # both scales as (1, block_n) rows: sublane-only broadcasts, in
    # the reference's order (x scale, then w scale) so the result is
    # bit-equal to it
    y = acc.astype(jnp.float32) * xs_ref[0, :1, :] * ws_ref[:1, :]
    o_ref[...] = y.reshape(1, oh, ow, -1).astype(o_ref.dtype)


def _conv2d_pallas(x, w, strides, padding, interpret, *,
                   x_scale=None, w_scale=None, out_dtype=None,
                   block_n: int = 128):
    """Shared Pallas driver for the float and int8 implicit-GEMM conv.

    Grid (N, O/block_n); the padded image block has a constant index
    map over the output-channel axis so it stays VMEM-resident while
    every O tile reads it. Quantized when ``x_scale``/``w_scale`` are
    given (x then carries int8-range values)."""
    quant = x_scale is not None
    n, h, w_dim, c = x.shape
    kh, kw, _, o = w.shape
    sh, sw = strides
    if (kh, kw) == (1, 1):
        # stride pre-slice: for k=1 SAME never pads, so slicing rows/
        # cols IS the strided conv and the kernel runs stride-1
        if (sh, sw) != (1, 1):
            x = x[:, ::sh, ::sw, :]
        _, oh, ow, _ = x.shape
        taps = ((0, 0),)
    else:
        (ph0, ph1), (pw0, pw1), oh, ow = _spatial_pads(
            h, w_dim, kh, kw, strides, padding)
        if (ph0, ph1, pw0, pw1) != (0, 0, 0, 0):
            x = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
        taps = tuple((dy, dx) for dy in range(kh) for dx in range(kw))
    hp, wp = x.shape[1], x.shape[2]

    # channel axes pad to the lane width; O-pad columns are sliced off
    x = _pad_dim(x, 3, _LANES)
    cp = x.shape[3]
    wt = _pad_dim(_pad_dim(w, 2, _LANES), 3, block_n)
    op = wt.shape[3]
    wt = wt.reshape(kh * kw, cp, op)

    if quant:
        x = x.astype(jnp.int8)
        kernel = functools.partial(_conv_kernel_q, taps=taps,
                                   oh=oh, ow=ow)
        # per-image scale in a whole (8, block_n) tile per image, like
        # ``ws``: a one-row block of an (n, 128) array is not a shape
        # the TPU lowering can slice
        xs = jnp.broadcast_to(
            x_scale.reshape(n, 1, 1).astype(jnp.float32),
            (n, _SUBLANES, block_n))
        ws = jnp.broadcast_to(
            _pad_dim(w_scale.reshape(o).astype(jnp.float32), 0,
                     block_n)[None, :], (_SUBLANES, op))
        extra_in = [xs, ws]
        extra_specs = [
            pl.BlockSpec((1, _SUBLANES, block_n),
                         lambda ni, j: (ni, 0, 0)),
            pl.BlockSpec((_SUBLANES, block_n), lambda ni, j: (0, j)),
        ]
        out_dtype = out_dtype or jnp.float32
    else:
        kernel = functools.partial(_conv_kernel, taps=taps,
                                   oh=oh, ow=ow)
        extra_in, extra_specs = [], []
        out_dtype = out_dtype or x.dtype

    out = pl.pallas_call(
        kernel,
        grid=(n, op // block_n),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cp), lambda ni, j: (ni, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cp, block_n),
                         lambda ni, j: (0, 0, j)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((1, oh, ow, block_n),
                               lambda ni, j: (ni, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, op), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * oh * ow * len(taps) * cp * op,
            bytes_accessed=(n * hp * wp * cp * x.dtype.itemsize
                            + kh * kw * cp * op + n * oh * ow * op * 4),
            transcendentals=0),
        interpret=_resolve_interpret(interpret),
    )(x, wt, *extra_in)
    return out[..., :o]


def conv2d(x: jnp.ndarray, w: jnp.ndarray,
           strides: Tuple[int, int] = (1, 1), padding: str = "SAME",
           impl: Optional[str] = None,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """NHWC float conv2d behind the one dispatch point. The reference
    path is byte-for-byte the `lax.conv_general_dilated` call the conv
    layers always made; the Pallas path is the implicit-GEMM kernel."""
    kh, kw = int(w.shape[0]), int(w.shape[1])
    chosen = resolve_conv_impl(impl, kernel=(kh, kw),
                               strides=tuple(strides))
    if chosen == "reference":
        return jax.lax.conv_general_dilated(
            x, w, tuple(strides), padding.upper(),
            dimension_numbers=_DN)
    return _conv2d_pallas(x, w, tuple(strides), padding, interpret)


def conv2d_int8(x_q: jnp.ndarray, w_q: jnp.ndarray,
                x_scale: jnp.ndarray, w_scale: jnp.ndarray,
                strides: Tuple[int, int] = (1, 1),
                padding: str = "SAME",
                impl: Optional[str] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Int8 NHWC conv with fused dequant → f32.

    ``x_q`` carries int8-range values (already rounded/clipped; any
    float dtype), ``x_scale`` the (N,1,1,1) per-image activation scale,
    ``w_scale`` the (O,) per-output-channel weight scale. The Pallas
    path accumulates int8×int8→int32 on the MXU with dequant in the
    epilogue; the reference path keeps the historical XLA behavior
    (true int8 conv on TPU, f32 conv on the same integer values
    off-TPU)."""
    kh, kw = int(w_q.shape[0]), int(w_q.shape[1])
    chosen = resolve_conv_impl(impl, kernel=(kh, kw),
                               strides=tuple(strides))
    if chosen == "pallas":
        return _conv2d_pallas(x_q, w_q, tuple(strides), padding,
                              interpret, x_scale=x_scale,
                              w_scale=w_scale)
    if jax.default_backend() == "tpu":
        y = jax.lax.conv_general_dilated(
            x_q.astype(jnp.int8), w_q, tuple(strides), padding.upper(),
            dimension_numbers=_DN,
            preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        y = jax.lax.conv_general_dilated(
            x_q.astype(jnp.float32), w_q.astype(jnp.float32),
            tuple(strides), padding.upper(), dimension_numbers=_DN)
    return y * x_scale * w_scale
