"""Pallas kernel correctness vs dense JAX references (interpret mode on
the hermetic CPU rig; the same kernels compile via Mosaic on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zoo_tpu.ops.attention import dot_product_attention
from zoo_tpu.ops.pallas import (
    flash_attention, quantize_int8, quantized_matmul, quantized_dense,
    fused_apply_sgd, fused_apply_adam)


def _qkv(b=2, h=3, t=80, d=32, tk=None, seed=0):
    rs = np.random.RandomState(seed)
    tk = t if tk is None else tk
    q = jnp.asarray(rs.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, tk, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, tk, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = dot_product_attention(q, k, v, causal=causal, impl="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_cross_length():
    q, k, v = _qkv(t=40, tk=72)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = dot_product_attention(q, k, v, impl="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,tk", [(4, 16), (1, 16), (40, 72)])
def test_flash_attention_causal_cross_length_end_aligned(t, tk):
    # Decode-style tq < tk: causal must be END-aligned (the last query row
    # sees every key), matching the dense path's tril(k=tk-tq).
    q, k, v = _qkv(t=t, tk=tk)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    ref = dot_product_attention(q, k, v, causal=True, impl="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_match_dense(causal):
    q, k, v = _qkv(b=1, h=2, t=48, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=16, block_k=16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal,
                                             impl="dense") ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_quantized_matmul_close_to_f32():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(24, 96), jnp.float32)
    w = jnp.asarray(rs.randn(96, 40), jnp.float32)
    w_q, w_s = quantize_int8(w, axis=0)           # per-output-channel
    x_q, x_s = quantize_int8(x, axis=-1)          # per-row
    y = quantized_matmul(x_q, w_q, x_s, w_s, block_m=32, block_n=32,
                         block_k=32)
    ref = x @ w
    err = np.abs(np.asarray(y) - np.asarray(ref))
    scale = np.abs(np.asarray(ref)).mean()
    assert err.mean() / scale < 0.02, (err.mean(), scale)


def test_quantized_dense_bias_and_batch_dims():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(4, 6, 64), jnp.float32)
    w = jnp.asarray(rs.randn(64, 32), jnp.float32)
    b = jnp.asarray(rs.randn(32), jnp.float32)
    w_q, w_s = quantize_int8(w, axis=0)
    y = quantized_dense(x, w_q, w_s, bias=b)
    assert y.shape == (4, 6, 32)
    ref = x @ w + b
    rel = (np.abs(np.asarray(y - ref)).mean() /
           np.abs(np.asarray(ref)).mean())
    assert rel < 0.03, rel


def test_fused_sgd_matches_formula():
    rs = np.random.RandomState(3)
    p = jnp.asarray(rs.randn(13, 7), jnp.float32)   # odd shape → padding
    g = jnp.asarray(rs.randn(13, 7), jnp.float32)
    buf = jnp.zeros_like(p)
    p1, buf1 = fused_apply_sgd(p, g, buf, lr=0.1, momentum=0.9,
                               weight_decay=0.01)
    g_eff = g + 0.01 * p
    buf_ref = g_eff
    p_ref = p - 0.1 * buf_ref
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(buf1), np.asarray(buf_ref),
                               atol=1e-6)
    # second step exercises the momentum accumulation
    p2, buf2 = fused_apply_sgd(p1, g, buf1, lr=0.1, momentum=0.9,
                               weight_decay=0.0)
    buf_ref2 = 0.9 * buf_ref + g
    np.testing.assert_allclose(np.asarray(buf2), np.asarray(buf_ref2),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(p2),
                               np.asarray(p1 - 0.1 * buf_ref2), atol=1e-6)


def test_fused_adam_matches_optax():
    import optax
    rs = np.random.RandomState(4)
    p = jnp.asarray(rs.randn(33), jnp.float32)
    g = jnp.asarray(rs.randn(33), jnp.float32)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    p1, m1, v1 = fused_apply_adam(p, g, m, v, step=1, lr=1e-2)

    opt = optax.adam(1e-2)
    state = opt.init(p)
    upd, _ = opt.update(g, state, p)
    p_ref = optax.apply_updates(p, upd)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p_ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_gqa_matches_repeated_dense():
    """GQA-native flash: unrepeated kv heads through the kernel's index
    maps — values AND all three gradients must match dense attention on
    the explicitly repeated kv."""
    import jax
    import jax.numpy as jnp

    from zoo_tpu.ops.attention import dot_product_attention
    from zoo_tpu.ops.pallas import flash_attention

    rs = np.random.RandomState(0)
    B, HQ, HKV, T, D = 2, 6, 2, 32, 8
    q = jnp.asarray(rs.randn(B, HQ, T, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, HKV, T, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, HKV, T, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_dense(q, k, v):
        rep = HQ // HKV
        return jnp.sum(dot_product_attention(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True, impl="dense") ** 2)

    rep = HQ // HKV
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True)),
        np.asarray(dot_product_attention(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True, impl="dense")), atol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gd, "qkv"):
        assert a.shape == b.shape, (nm, a.shape, b.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, err_msg=f"d{nm}")


@pytest.mark.multichip
def test_flash_attention_placed_on_a_mesh_matches_unsharded():
    """A Mosaic kernel cannot be partitioned by GSPMD, so under a
    multi-device mesh ``dot_product_attention`` runs the flash kernel
    per device under shard_map — batch rows over the data axes, heads
    over ``model`` (found on the chip by PR 21's tp=2 serving leg). The
    result and the gradients must not depend on the placement."""
    from zoo_tpu.parallel import build_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = build_mesh(jax.devices()[:4],
                      axis_sizes={"data": 2, "model": 2})
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 4, 16, 8), jnp.float32)
    k = jnp.asarray(rs.randn(2, 2, 16, 8), jnp.float32)
    v = jnp.asarray(rs.randn(2, 2, 16, 8), jnp.float32)

    def loss(mesh):
        return lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True, impl="flash", mesh=mesh) ** 2)

    want = jax.value_and_grad(loss(None), (0, 1, 2))(q, k, v)
    got = jax.jit(jax.value_and_grad(loss(mesh), (0, 1, 2)))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_gqa_rejects_bad_head_ratio():
    import jax.numpy as jnp
    import pytest

    from zoo_tpu.ops.pallas import flash_attention

    q = jnp.zeros((1, 5, 16, 8))
    kv = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, kv, kv, interpret=True)


def test_fused_bottleneck_matches_xla():
    """The fused bottleneck kernel (interpret mode on CPU) matches the
    XLA conv composition; the no-fit geometry falls back cleanly."""
    from zoo_tpu.ops.pallas.fused_block import (
        _pick_k,
        _xla_block,
        fused_bottleneck,
    )

    rs = np.random.RandomState(0)
    b, h, w, cin, cmid = 4, 8, 8, 32, 16
    x = jnp.asarray(rs.randn(b, h, w, cin).astype(np.float32))
    w1 = jnp.asarray((rs.randn(cin, cmid) / np.sqrt(cin))
                     .astype(np.float32))
    w2 = jnp.asarray((rs.randn(3, 3, cmid, cmid) / np.sqrt(9 * cmid))
                     .astype(np.float32))
    w3 = jnp.asarray((rs.randn(cmid, cin) / np.sqrt(cmid))
                     .astype(np.float32))

    ref = np.asarray(_xla_block(x, w1, w2, w3))
    got = np.asarray(fused_bottleneck(x, w1, w2, w3, interpret=True))
    # the kernel computes in bf16 with f32 accumulation
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)

    # package interpret contract: the off-TPU DEFAULT also runs the
    # (interpreted) kernel, bf16 tolerance — not the XLA fallback
    fb = np.asarray(fused_bottleneck(x, w1, w2, w3))
    np.testing.assert_allclose(fb, ref, atol=5e-2, rtol=5e-2)

    # VMEM planner: real geometries fit, absurd ones return 0
    assert _pick_k(128, 56, 56, 256, 64) >= 1
    assert _pick_k(128, 112, 112, 2048, 512) == 0

    # interpret mode has no VMEM: the kernel must still run (not the
    # fallback) even on a geometry the TPU planner rejects
    b2, h2, w2_, cin2, cmid2 = 2, 12, 12, 2048, 512
    assert _pick_k(b2, h2, w2_, cin2, cmid2) == 0
    xb = jnp.asarray(rs.randn(b2, h2, w2_, cin2).astype(np.float32))
    wb1 = jnp.asarray((rs.randn(cin2, cmid2) / np.sqrt(cin2))
                      .astype(np.float32))
    wb2 = jnp.asarray((rs.randn(3, 3, cmid2, cmid2)
                       / np.sqrt(9 * cmid2)).astype(np.float32))
    wb3 = jnp.asarray((rs.randn(cmid2, cin2) / np.sqrt(cmid2))
                      .astype(np.float32))
    big_ref = np.asarray(_xla_block(xb, wb1, wb2, wb3))
    big_got = np.asarray(fused_bottleneck(xb, wb1, wb2, wb3,
                                          interpret=True))
    np.testing.assert_allclose(big_got, big_ref, atol=8e-2, rtol=8e-2)


def test_fused_bottleneck_custom_vjp_matches_xla_grads():
    """fused_bottleneck is differentiable: its custom_vjp (recompute
    backward through the XLA composition) matches jax.grad of the XLA
    block within bf16-forward tolerance."""
    import jax as _jax

    from zoo_tpu.ops.pallas.fused_block import _xla_block, fused_bottleneck

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 8, 8, 32).astype(np.float32))
    w1 = jnp.asarray((rs.randn(32, 8) * 0.1).astype(np.float32))
    w2 = jnp.asarray((rs.randn(3, 3, 8, 8) * 0.1).astype(np.float32))
    w3 = jnp.asarray((rs.randn(8, 32) * 0.1).astype(np.float32))

    def loss_fused(w1, w2, w3):
        return jnp.sum(fused_bottleneck(x, w1, w2, w3, True) ** 2)

    def loss_xla(w1, w2, w3):
        return jnp.sum(_xla_block(x, w1, w2, w3) ** 2)

    g1 = _jax.grad(loss_fused, argnums=(0, 1, 2))(w1, w2, w3)
    g2 = _jax.grad(loss_xla, argnums=(0, 1, 2))(w1, w2, w3)
    for a, b in zip(g1, g2):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < 0.01 * scale + 0.05


def test_fused_quantized_matmul_matches_two_pass():
    """The fused quantize->int8-dot->dequant kernel reproduces the
    two-pass reference (quantize_int8 + quantized_matmul) up to
    borderline activation rounding: XLA rewrites x/scale as
    x * (1/scale), which can flip a round() by one int8 step, so the
    bound is one dequantized ULP — not bit-exactness."""
    from zoo_tpu.ops.pallas import fused_quantized_matmul

    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(24, 96), jnp.float32)
    w = jnp.asarray(rs.randn(96, 40), jnp.float32)
    w_q, w_s = quantize_int8(w, axis=0)
    x_q, x_s = quantize_int8(x, axis=-1)
    ref = quantized_matmul(x_q, w_q, x_s, w_s, block_m=32, block_n=32,
                          block_k=32)
    got = fused_quantized_matmul(x, w_q, w_s, block_m=32, block_n=32,
                                 block_k=32)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=0)
    # and it tracks the f32 matmul to quantization noise
    rel = (np.abs(np.asarray(got) - np.asarray(x @ w)).mean()
           / np.abs(np.asarray(x @ w)).mean())
    assert rel < 0.02, rel


def test_fused_quantized_dense_paths_agree():
    """quantized_dense(impl=...) is the one int8 GEMM dispatch point:
    fused and unfused backends agree (1-ULP rounding tolerance) with
    bias and leading batch dims."""
    from zoo_tpu.ops.pallas import quantized_dense as qd

    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(4, 6, 64), jnp.float32)
    w = jnp.asarray(rs.randn(64, 32), jnp.float32)
    b = jnp.asarray(rs.randn(32), jnp.float32)
    w_q, w_s = quantize_int8(w, axis=0)
    y_f = qd(x, w_q, w_s, bias=b, impl="fused")
    y_u = qd(x, w_q, w_s, bias=b, impl="unfused")
    assert y_f.shape == y_u.shape == (4, 6, 32)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                               atol=1e-4, rtol=0)


def test_resolve_int8_matmul_dispatch(monkeypatch):
    from zoo_tpu.ops.pallas import resolve_int8_matmul

    assert resolve_int8_matmul() == "fused"          # auto default
    assert resolve_int8_matmul("unfused") == "unfused"
    monkeypatch.setenv("ZOO_INT8_MATMUL", "unfused")
    assert resolve_int8_matmul() == "unfused"
    assert resolve_int8_matmul("fused") == "fused"   # arg beats env
    monkeypatch.delenv("ZOO_INT8_MATMUL")
    with pytest.raises(ValueError):
        resolve_int8_matmul("no-such-impl")
