"""Implicit-GEMM Pallas conv vs ``lax.conv_general_dilated`` (interpret
mode on the hermetic CPU rig — the same kernels compile via Mosaic on
TPU) plus the ``resolve_conv_impl`` dispatch contract (docs/kernels.md).

The 1x1 path is a pure strided GEMM and the int8 path dequantizes on
the same integer values as the reference, so both are exactly equal;
the 3x3 f32 path differs only by summation order."""

import numpy as np
import pytest

import jax.numpy as jnp

from zoo_tpu.ops.pallas import conv2d, conv2d_int8, resolve_conv_impl
from zoo_tpu.ops.pallas.conv import pallas_conv_supported
from zoo_tpu.ops.pallas.quant import quantize_conv_weights, quantized_conv2d


def _xw(h=8, w=8, c=8, k=3, o=24, n=2, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, h, w, c), jnp.float32)
    wts = jnp.asarray(rs.randn(k, k, c, o), jnp.float32)
    return x, wts


@pytest.mark.parametrize("h,w,c,k,stride,padding", [
    (8, 8, 8, 1, 1, "SAME"),
    (8, 8, 8, 1, 2, "SAME"),
    (9, 9, 16, 1, 2, "VALID"),
    (8, 8, 8, 3, 1, "SAME"),
    (8, 8, 16, 3, 1, "VALID"),
    (7, 7, 130, 3, 1, "SAME"),     # channels past one lane tile
])
def test_conv2d_pallas_matches_lax(h, w, c, k, stride, padding):
    x, wts = _xw(h, w, c, k)
    out = conv2d(x, wts, strides=(stride, stride), padding=padding,
                 impl="pallas")
    ref = conv2d(x, wts, strides=(stride, stride), padding=padding,
                 impl="reference")
    assert out.shape == ref.shape
    # f32 sum-order differs (register accumulation vs XLA's schedule);
    # error grows with the 9*C reduction length, ~5e-5 at C=130
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("k,stride,padding", [
    (1, 1, "SAME"), (1, 2, "VALID"), (3, 1, "SAME"), (3, 1, "VALID"),
])
def test_conv2d_int8_pallas_matches_reference_exactly(k, stride, padding):
    """Same quantized integers in, same dequant math out: the int8
    Pallas conv and the XLA reference agree bit for bit off-TPU."""
    x, wts = _xw(k=k)
    w_q, w_scale = quantize_conv_weights(wts)
    amax = jnp.max(jnp.abs(x), axis=(1, 2, 3), keepdims=True)
    x_scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    x_q = jnp.clip(jnp.round(x / x_scale), -127, 127)
    out = conv2d_int8(x_q, w_q, x_scale, w_scale.astype(jnp.float32),
                      strides=(stride, stride), padding=padding,
                      impl="pallas")
    ref = conv2d_int8(x_q, w_q, x_scale, w_scale.astype(jnp.float32),
                      strides=(stride, stride), padding=padding,
                      impl="reference")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quantized_conv2d_impl_agnostic():
    """The quantize_model serving path (quantized_conv2d) produces the
    same activations whichever backend the dispatch picks."""
    x, wts = _xw(k=3)
    w_q, w_scale = quantize_conv_weights(wts)
    y_p = quantized_conv2d(x, w_q, w_scale, impl="pallas")
    y_r = quantized_conv2d(x, w_q, w_scale, impl="reference")
    np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_r))
    # and the int8 conv tracks the float conv to quantization noise
    ref = conv2d(x, wts, impl="reference")
    rel = (np.abs(np.asarray(y_p - ref)).mean()
           / np.abs(np.asarray(ref)).mean())
    assert rel < 0.03, rel


def test_pallas_conv_supported_matrix():
    assert pallas_conv_supported((1, 1), (1, 1), (1, 1))
    assert pallas_conv_supported((1, 1), (2, 2), (1, 1))
    assert pallas_conv_supported((3, 3), (1, 1), (1, 1))
    assert not pallas_conv_supported((3, 3), (2, 2), (1, 1))
    assert not pallas_conv_supported((5, 5), (1, 1), (1, 1))
    assert not pallas_conv_supported((3, 3), (1, 1), (2, 2))


def _as_if_on_tpu(monkeypatch):
    """``on_tpu()`` true, kernels interpreted: what every ``auto``
    resolver sees on the chip, minus Mosaic."""
    import zoo_tpu.ops.pallas as zp
    import zoo_tpu.ops.pallas.conv as zconv
    monkeypatch.setattr(zp, "on_tpu", lambda: True)
    # the conv module binds its helpers at import (``_on_tpu`` is the
    # name the PR 18 rule consulted; it may no longer exist)
    monkeypatch.setattr(zconv, "_on_tpu", lambda: True, raising=False)
    monkeypatch.setattr(zconv, "_resolve_interpret",
                        lambda interpret: True)
    assert zp.on_tpu()


def test_resolve_conv_impl_dispatch(monkeypatch):
    # auto -> the XLA reference, off a TPU and on one: fit calls this
    # seam, and the kernel cannot be differentiated
    assert resolve_conv_impl(kernel=(3, 3)) == "reference"
    with monkeypatch.context() as m:
        _as_if_on_tpu(m)
        for kernel in ((1, 1), (3, 3)):
            assert resolve_conv_impl(kernel=kernel) == "reference"
            assert resolve_conv_impl("auto", kernel=kernel) == "reference"
    # env knob overrides auto at the single dispatch point
    monkeypatch.setenv("ZOO_CONV_IMPL", "pallas")
    assert resolve_conv_impl(kernel=(3, 3)) == "pallas"
    monkeypatch.setenv("ZOO_CONV_IMPL", "reference")
    assert resolve_conv_impl(kernel=(1, 1)) == "reference"
    monkeypatch.delenv("ZOO_CONV_IMPL")
    # a pallas request on an unsupported shape fails loudly, never
    # silently falls back
    with pytest.raises(ValueError, match="envelope"):
        resolve_conv_impl("pallas", kernel=(5, 5))
    with pytest.raises(ValueError):
        resolve_conv_impl("no-such-impl", kernel=(1, 1))


def test_conv_layer_trains_with_on_tpu_true(monkeypatch):
    """The conv seam regression (PR 18 -> PR 21): with ``on_tpu()``
    true, ``auto`` handed ``fit`` the Pallas conv, which has no vjp, so
    no conv net could take a step on a TPU. A small Conv2D +
    BatchNormalization model must fit, and ``jax.grad`` through the
    layer must match the XLA conv."""
    import jax

    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import (
        BatchNormalization,
        Convolution2D,
        Dense,
        Flatten,
    )

    _as_if_on_tpu(monkeypatch)
    rs = np.random.RandomState(0)
    x = rs.randn(16, 8, 8, 4).astype(np.float32)
    y = rs.randint(0, 3, 16).astype(np.int32)
    m = Sequential()
    conv = Convolution2D(8, 3, 3, border_mode="same", dim_ordering="tf",
                         input_shape=(8, 8, 4))
    m.add(conv)
    m.add(BatchNormalization(dim_ordering="tf"))
    m.add(Flatten())
    m.add(Dense(3))
    m.compile(optimizer="sgd",
              loss="sparse_categorical_crossentropy_from_logits")
    loss = m.fit(x, y, batch_size=8, nb_epoch=1, verbose=0)["loss"]
    assert np.isfinite(loss).all()

    # grad through the layer == grad through lax.conv on its weights
    params = conv.build(jax.random.PRNGKey(0), (None, 8, 8, 4))
    xj = jnp.asarray(x)

    def through_layer(p):
        return jnp.sum(conv.call(p, xj) ** 2)

    def through_lax(p):
        out = jax.lax.conv_general_dilated(
            xj, p["W"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if "b" in p:
            out = out + p["b"]
        return jnp.sum(out ** 2)

    got = jax.grad(through_layer)(params)
    want = jax.grad(through_lax)(params)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-5, atol=1e-4)
    # the kernel itself still cannot be differentiated: if this starts
    # passing, auto's rule can be revisited (ROADMAP S6)
    with pytest.raises(Exception, match="[Ll]inearization|differentiat"):
        jax.grad(lambda w: jnp.sum(conv2d(xj, w, impl="pallas",
                                          interpret=True)))(params["W"])
