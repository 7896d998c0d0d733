"""Paged flash-prefill Pallas kernel (zoo_tpu/ops/pallas/paged_prefill.py):
numeric identity against the dense-gather reference across block-table
routing, batched sequences, GQA grouping, the causal-by-position mask
edges, and int8 in-register dequant — all through the Pallas
interpreter (the exact kernel TPU hardware compiles). The serving-level
token-identity checks (chunk prefill and the speculative verify
executable on ``ZOO_LLM_PREFILL_IMPL=flash``) live at the bottom.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zoo_tpu.ops.pallas.paged_prefill import paged_flash_prefill


def _dense_ref(q, kc, vc, bt, pos):
    """cache[block_table] gather + per-row position mask — the exact
    math model._prefill_attend runs on the dense anchor path."""
    S, C, H, D = q.shape
    nb, n_kv, bs, _ = kc.shape
    W = bt.shape[1]
    ctx = W * bs
    group = H // n_kv
    # (S, W, n_kv, bs, D) -> token-major (S, ctx, n_kv, D)
    keys = kc[bt].transpose(0, 1, 3, 2, 4).reshape(S, ctx, n_kv, D)
    vals = vc[bt].transpose(0, 1, 3, 2, 4).reshape(S, ctx, n_kv, D)
    qg = q.reshape(S, C, n_kv, group, D)
    s = jnp.einsum("sckgd,stkd->sckgt", qg, keys).astype(
        jnp.float32) / jnp.sqrt(float(D))
    live = jnp.arange(ctx)[None, None, :] <= pos[:, :, None]
    s = jnp.where(live[:, :, None, None, :], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
    return jnp.einsum("sckgt,stkd->sckgd", p, vals).reshape(S, C, H, D)


def _case(S=2, C=5, H=4, n_kv=2, D=16, nb=12, bs=4, W=4, seed=0,
          starts=None):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(S, C, H, D).astype(np.float32))
    kc = jnp.asarray(rs.randn(nb, n_kv, bs, D).astype(np.float32))
    vc = jnp.asarray(rs.randn(nb, n_kv, bs, D).astype(np.float32))
    bt = jnp.asarray(rs.randint(1, nb, (S, W)).astype(np.int32))
    if starts is None:
        starts = rs.randint(0, W * bs - C, (S,))
    pos = jnp.asarray((np.asarray(starts)[:, None]
                       + np.arange(C)[None, :]).astype(np.int32))
    return q, kc, vc, bt, pos


@pytest.mark.parametrize("shape", [
    dict(S=1, C=4),                       # the chunk-prefill shape
    dict(S=3, C=5, W=6),                  # the verify shape
    dict(S=2, C=8, H=4, n_kv=1, D=8, bs=8, W=3),   # MQA
    dict(S=2, C=3, H=4, n_kv=4, nb=9),             # MHA
])
def test_kernel_matches_dense_reference(shape):
    q, kc, vc, bt, pos = _case(**shape)
    ref = _dense_ref(q, kc, vc, bt, pos)
    out = paged_flash_prefill(q, kc, vc, bt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_position_edges():
    """Row at position 0 (one live column), a chunk ending exactly on
    the table edge, and equal clamped positions (the pad-row shape the
    verify executable feeds)."""
    q, kc, vc, bt, _ = _case(S=3, C=3)
    pos = jnp.asarray(np.array([[0, 1, 2], [13, 14, 15],
                                [15, 15, 15]], np.int32))
    ref = _dense_ref(q, kc, vc, bt, pos)
    out = paged_flash_prefill(q, kc, vc, bt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_int8_dequant_matches_dense_widen():
    from zoo_tpu.util.quantize import absmax_scale, narrow_int8, \
        widen_int8

    rs = np.random.RandomState(21)
    S, C, H, n_kv, D, nb, bs, W = 2, 4, 4, 2, 16, 10, 4, 4
    q = jnp.asarray(rs.randn(S, C, H, D).astype(np.float32))
    kc = rs.randn(nb, n_kv, bs, D).astype(np.float32)
    vc = rs.randn(nb, n_kv, bs, D).astype(np.float32)
    ks = np.asarray(absmax_scale(kc, axis=-1))
    vs = np.asarray(absmax_scale(vc, axis=-1))
    kq = narrow_int8(kc, ks[..., None])
    vq = narrow_int8(vc, vs[..., None])
    bt = jnp.asarray(rs.randint(1, nb, (S, W)).astype(np.int32))
    pos = jnp.asarray(np.array([[0, 1, 2, 3], [9, 10, 11, 12]],
                               np.int32))
    ref = _dense_ref(q, jnp.asarray(widen_int8(kq, ks[..., None])),
                     jnp.asarray(widen_int8(vq, vs[..., None])),
                     bt, pos)
    out = paged_flash_prefill(
        q, jnp.asarray(kq), jnp.asarray(vq), bt, pos,
        k_scale=jnp.asarray(ks.reshape(nb, 1, -1)),
        v_scale=jnp.asarray(vs.reshape(nb, 1, -1)),
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_kernel_reads_a_stacked_cache_at_a_traced_layer(kv):
    """What the chunk and verify executables run: the WHOLE
    (n_layer, ...) cache goes in and the layer is a traced scalar
    inside ``lax.scan``; each layer's output is the dense reference of
    ``cache[layer]``."""
    from zoo_tpu.util.quantize import absmax_scale, narrow_int8, \
        widen_int8

    rs = np.random.RandomState(6)
    L, S, C, H, n_kv, D, nb, bs, W = 3, 2, 4, 4, 2, 16, 10, 4, 4
    q = jnp.asarray(rs.randn(S, C, H, D).astype(np.float32))
    kc = rs.randn(L, nb, n_kv, bs, D).astype(np.float32)
    vc = rs.randn(L, nb, n_kv, bs, D).astype(np.float32)
    bt = jnp.asarray(rs.randint(1, nb, (S, W)).astype(np.int32))
    pos = jnp.asarray(np.array([[0, 1, 2, 3], [9, 10, 11, 12]], np.int32))
    if kv == "int8":
        ks, vs = (np.asarray(absmax_scale(c, axis=-1)) for c in (kc, vc))
        kq, vq = narrow_int8(kc, ks[..., None]), narrow_int8(vc, vs[..., None])
        kw = dict(k_scale=jnp.asarray(ks.reshape(L, nb, 1, -1)),
                  v_scale=jnp.asarray(vs.reshape(L, nb, 1, -1)))
        kd, vd = widen_int8(kq, ks[..., None]), widen_int8(vq, vs[..., None])
        tol = 2e-5
    else:
        kq, vq = jnp.asarray(kc, jnp.bfloat16), jnp.asarray(vc, jnp.bfloat16)
        kw, tol = {}, 2e-2
        kd, vd = np.asarray(kq, np.float32), np.asarray(vq, np.float32)
    kq, vq = jnp.asarray(kq), jnp.asarray(vq)

    def layer(_, i):
        return None, paged_flash_prefill(q, kq, vq, bt, pos, layer=i,
                                         interpret=True, **kw)

    _, outs = jax.jit(lambda: jax.lax.scan(layer, None, jnp.arange(L)))()
    for i in range(L):
        ref = _dense_ref(q, jnp.asarray(kd[i]), jnp.asarray(vd[i]), bt, pos)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   atol=tol, rtol=tol, err_msg=f"layer {i}")


def test_kernel_argument_validation():
    q, kc, vc, bt, pos = _case()
    with pytest.raises(ValueError, match="travel together"):
        paged_flash_prefill(q, kc, vc, bt, pos,
                            k_scale=jnp.zeros((12, 1, 8)),
                            interpret=True)
    with pytest.raises(ValueError, match="scale shape"):
        paged_flash_prefill(q, kc, vc, bt, pos,
                            k_scale=jnp.zeros((12, 2, 4)),
                            v_scale=jnp.zeros((12, 2, 4)),
                            interpret=True)
    with pytest.raises(ValueError, match="positions shape"):
        paged_flash_prefill(q, kc, vc, bt, pos[:, :2], interpret=True)


def test_kernel_under_jit():
    q, kc, vc, bt, pos = _case(seed=9)
    ref = _dense_ref(q, kc, vc, bt, pos)
    f = jax.jit(lambda *a: paged_flash_prefill(*a, interpret=True))
    np.testing.assert_allclose(np.asarray(f(q, kc, vc, bt, pos)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------- serving-level token identity

def test_chunk_prefill_flash_impl_token_identical():
    """ZOO_LLM_PREFILL_IMPL semantics: the chunk executable on the
    flash kernel (interpreted on CPU) emits the same tokens as the
    dense anchor, greedy and sampled, with the census unchanged."""
    import time

    from zoo_tpu.models.llm.llama import tiny_llama_config
    from zoo_tpu.serving.llm.engine import LLMEngine
    from zoo_tpu.serving.llm.model import (
        PagedLlamaModel,
        resolve_prefill_impl,
    )

    assert resolve_prefill_impl("dense") == "dense"
    assert resolve_prefill_impl("flash") == "flash"
    with pytest.raises(ValueError):
        resolve_prefill_impl("mosaic")

    cfg = tiny_llama_config(vocab=64)
    kw = dict(seed=0, num_slots=2, block_size=4, num_blocks=32,
              max_blocks_per_seq=8, prefill_buckets=(8, 32),
              prefill_chunk=4)
    prompts = [np.arange(2, 12) % 64, np.arange(3, 9) % 64]
    sampling = [None, dict(temperature=0.8, seed=9)]

    def gen(model, spec=None):
        eng = LLMEngine(model).start()
        try:
            hs = [eng.submit(p, 8, rid=f"f{i}", sampling=s)
                  for i, (p, s) in enumerate(zip(prompts, sampling))]
            end = time.monotonic() + 300
            while not all(h.done for h in hs):
                assert time.monotonic() < end
                time.sleep(0.005)
            assert all(h.outcome == "ok" for h in hs), \
                [(h.outcome, h.error) for h in hs]
            return [list(h.tokens) for h in hs], eng.stats()
        finally:
            eng.stop()

    dense, _ = gen(PagedLlamaModel(cfg, prefill_impl="dense", **kw))
    flash_model = PagedLlamaModel(cfg, prefill_impl="flash", **kw)
    assert flash_model.prefill_attention_impl == "flash"
    flash, st = gen(flash_model)
    assert flash == dense
    assert st["prefill_attention_impl"] == "flash"
    assert st["compiles"]["prefill_chunk"] == 1

    # the verify executable rides the same impl switch
    spec_model = PagedLlamaModel(cfg, prefill_impl="flash", spec_k=3,
                                 **kw)
    spec, st2 = gen(spec_model)
    assert spec == dense
    assert st2["compiles"]["verify"] == 1
