"""Paged flash-decode Pallas kernel (zoo_tpu/ops/pallas/paged_decode.py):
numeric identity against the dense-gather reference across block-table
routing, GQA grouping, split-KV merge edges, and the tp=2 head-sharded
layout the serving path runs it under (docs/multichip.md).

All kernel runs here go through the Pallas interpreter (the exact same
kernel TPU hardware compiles); the serving-level token-identity checks
live in tests/test_llm_serving.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zoo_tpu.ops.pallas.paged_decode import (
    paged_flash_decode,
    resolve_num_splits,
)


def _dense_ref(q, kc, vc, bt, pos):
    """The PR 7 gather-attention math the kernel must reproduce."""
    S, H, D = q.shape
    n_blocks, n_kv, bs, _ = kc.shape
    W = bt.shape[1]
    ctx = W * bs
    group = H // n_kv
    # (S, W, n_kv, bs, D) -> token-major (S, ctx, n_kv, D)
    keys = kc[bt].transpose(0, 1, 3, 2, 4).reshape(S, ctx, n_kv, D)
    vals = vc[bt].transpose(0, 1, 3, 2, 4).reshape(S, ctx, n_kv, D)
    qg = q.reshape(S, n_kv, group, D)
    s = jnp.einsum("skgd,stkd->skgt", qg, keys).astype(
        jnp.float32) / jnp.sqrt(float(D))
    live = jnp.arange(ctx)[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
    return jnp.einsum("skgt,stkd->skgd", p, vals).reshape(S, H, D)


def _case(S=3, H=4, n_kv=2, D=16, n_blocks=12, bs=4, W=4, seed=0,
          positions=None):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(S, H, D).astype(np.float32))
    kc = jnp.asarray(rs.randn(n_blocks, n_kv, bs, D).astype(np.float32))
    vc = jnp.asarray(rs.randn(n_blocks, n_kv, bs, D).astype(np.float32))
    bt = jnp.asarray(rs.randint(1, n_blocks, (S, W)).astype(np.int32))
    if positions is None:
        positions = rs.randint(0, W * bs, (S,))
    pos = jnp.asarray(np.asarray(positions, np.int32))
    return q, kc, vc, bt, pos


def _as_cache(kc, vc, kv):
    """float32 blocks (..., nb, n_kv, bs, D) as the cache holds them
    under ``kv``: (K, V, the kernel's scale arguments, the widened K and
    V the dense reference reads, tolerance)."""
    from zoo_tpu.util.quantize import absmax_scale, narrow_int8, \
        widen_int8

    if kv == "int8":
        ks, vs = (np.asarray(absmax_scale(c, axis=-1)) for c in (kc, vc))
        kq, vq = narrow_int8(kc, ks[..., None]), narrow_int8(vc, vs[..., None])
        rows = ks.shape[:-2] + (1, ks.shape[-2] * ks.shape[-1])
        kw = dict(k_scale=jnp.asarray(ks.reshape(rows)),
                  v_scale=jnp.asarray(vs.reshape(rows)))
        return (jnp.asarray(kq), jnp.asarray(vq), kw,
                widen_int8(kq, ks[..., None]), widen_int8(vq, vs[..., None]),
                2e-5)
    if kv == "bf16":
        kq, vq = jnp.asarray(kc, jnp.bfloat16), jnp.asarray(vc, jnp.bfloat16)
        return (kq, vq, {}, np.asarray(kq, np.float32),
                np.asarray(vq, np.float32), 2e-2)
    return jnp.asarray(kc), jnp.asarray(vc), {}, kc, vc, 2e-5


@pytest.mark.parametrize("splits", [1, 2, 4])
def test_kernel_matches_dense_reference(splits):
    q, kc, vc, bt, pos = _case()
    ref = _dense_ref(q, kc, vc, bt, pos)
    out = paged_flash_decode(q, kc, vc, bt, pos, num_splits=splits,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_position_edges():
    """position 0 (one live token), a block boundary, and a full table
    — the masking/skip edges; plus the mid-split boundary where the
    log-sum-exp merge sees one live and one dead split."""
    q, kc, vc, bt, pos = _case(S=4, W=4, bs=4,
                               positions=[0, 3, 8, 15])
    ref = _dense_ref(q, kc, vc, bt, pos)
    out = paged_flash_decode(q, kc, vc, bt, pos, num_splits=2,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_gqa_and_mha_layouts():
    for n_kv in (1, 2, 4):   # MQA, grouped, MHA
        q, kc, vc, bt, pos = _case(H=4, n_kv=n_kv, seed=3 + n_kv)
        ref = _dense_ref(q, kc, vc, bt, pos)
        out = paged_flash_decode(q, kc, vc, bt, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"n_kv={n_kv}")


def test_kernel_under_jit_with_donated_style_caches():
    q, kc, vc, bt, pos = _case(seed=9)
    ref = _dense_ref(q, kc, vc, bt, pos)
    f = jax.jit(lambda *a: paged_flash_decode(*a, interpret=True))
    np.testing.assert_allclose(np.asarray(f(q, kc, vc, bt, pos)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kernel_int8_dequant_matches_dense_widen():
    """The quantized-cache contract: the kernel fed int8 K/V plus
    per-(block, kv-head, row) absmax scales must equal the dense path's
    gather-then-widen on the SAME bytes — across splits and the
    position edges."""
    from zoo_tpu.util.quantize import absmax_scale, narrow_int8, \
        widen_int8

    rs = np.random.RandomState(21)
    S, H, n_kv, D, nb, bs, W = 3, 4, 2, 16, 12, 4, 4
    q = jnp.asarray(rs.randn(S, H, D).astype(np.float32))
    kc = rs.randn(nb, n_kv, bs, D).astype(np.float32)
    vc = rs.randn(nb, n_kv, bs, D).astype(np.float32)
    ks = np.asarray(absmax_scale(kc, axis=-1))       # (nb, n_kv, bs)
    vs = np.asarray(absmax_scale(vc, axis=-1))
    kq = narrow_int8(kc, ks[..., None])
    vq = narrow_int8(vc, vs[..., None])
    bt = jnp.asarray(rs.randint(1, nb, (S, W)).astype(np.int32))
    for splits, positions in ((1, None), (2, [0, 7, 15]),
                              (4, [3, 8, 12])):
        pos = jnp.asarray(np.asarray(
            positions if positions is not None
            else rs.randint(0, W * bs, (S,)), np.int32))
        ref = _dense_ref(q, jnp.asarray(widen_int8(kq, ks[..., None])),
                         jnp.asarray(widen_int8(vq, vs[..., None])),
                         bt, pos)
        out = paged_flash_decode(
            q, jnp.asarray(kq), jnp.asarray(vq), bt, pos,
            k_scale=jnp.asarray(ks.reshape(nb, 1, -1)),
            v_scale=jnp.asarray(vs.reshape(nb, 1, -1)),
            num_splits=splits, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"splits={splits}")


# the multi-entry step's edges, at N = 4 entries a step (``MAX_ENTRIES``
# held there so that every cache dtype meets the same steps) of 16 rows:
# a step is 64 positions. name -> (n_kv, block, D, W, splits, positions)
_STEP_EDGES = {
    # tables that are not whole steps: the padding is dead entries
    "width_5": (8, 16, 128, 5, 1, [0, 63, 64, 79]),
    "width_7_two_splits": (8, 16, 128, 7, 2, [5, 64, 100, 111]),
    # a position on a step's first and on its last row
    "step_first_and_last_row": (8, 16, 128, 12, 1, [64, 63, 128, 127]),
    # empty slots (position 0, the trash block) beside full ones
    "zero_beside_full": (8, 16, 128, 8, 2, [0, 127, 0, 127]),
    # four splits of one step each: the later ones hold no live row
    "dead_splits": (8, 16, 128, 16, 4, [10, 70, 10, 130]),
    # n_kv * block != 128: 8 key rows a block (an int8 cache's 8-lane
    # scale row cannot be sliced out of HBM: an entry a step)
    "narrow_blocks": (2, 4, 128, 7, 2, [0, 3, 16, 27]),
    # rows of 16 values: an entry a step for every dtype
    "narrow_rows": (4, 4, 16, 7, 1, [0, 4, 15, 27]),
}


@pytest.mark.parametrize("kv", ["int8", "bf16", "f32"])
@pytest.mark.parametrize("edge", list(_STEP_EDGES))
def test_kernel_step_edges(monkeypatch, edge, kv):
    from zoo_tpu.ops.pallas import paged_decode as pd

    monkeypatch.setattr(pd, "MAX_ENTRIES", 4)
    n_kv, bs, D, W, splits, positions = _STEP_EDGES[edge]
    q, kc, vc, bt, pos = _case(S=4, H=2 * n_kv, n_kv=n_kv, D=D,
                               n_blocks=24, bs=bs, W=W, seed=len(edge),
                               positions=positions)
    kq, vq, kw, kd, vd, tol = _as_cache(np.asarray(kc), np.asarray(vc), kv)
    ref = _dense_ref(q, jnp.asarray(kd), jnp.asarray(vd), bt, pos)
    out = paged_flash_decode(q, kq, vq, bt, pos, num_splits=splits,
                             interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_entries_per_step_follows_the_block_bytes():
    """N from the shapes: 16 int8 blocks of the cells' 8 x 16 x 128,
    8 in bf16, 4 in f32, never more than the table holds."""
    from zoo_tpu.ops.pallas.paged_decode import entries_per_step
    assert entries_per_step(160, 8, 16, 128, 1) == 16
    assert entries_per_step(160, 8, 16, 128, 2) == 8
    assert entries_per_step(160, 8, 16, 128, 4) == 4
    assert entries_per_step(5, 8, 16, 128, 1) == 5
    assert entries_per_step(160, 8, 32, 256, 4) == 1


def test_kernel_scales_must_travel_together():
    q, kc, vc, bt, pos = _case()
    with pytest.raises(ValueError):
        paged_flash_decode(q, kc, vc, bt, pos,
                           k_scale=jnp.zeros((12, 1, 8)),
                           interpret=True)


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_kernel_reads_a_stacked_cache_at_a_traced_layer(kv):
    """What the serving step runs: the WHOLE (n_layer, ...) cache goes
    in and the layer is a traced scalar inside ``lax.scan``; each
    layer's output is the dense reference of ``cache[layer]``. A layer
    index without a stacked cache, and the reverse, are refused."""
    rs = np.random.RandomState(5)
    L, S, H, n_kv, D, nb, bs, W = 3, 3, 4, 2, 16, 12, 4, 4
    q = jnp.asarray(rs.randn(S, H, D).astype(np.float32))
    kc = rs.randn(L, nb, n_kv, bs, D).astype(np.float32)
    vc = rs.randn(L, nb, n_kv, bs, D).astype(np.float32)
    bt = jnp.asarray(rs.randint(1, nb, (S, W)).astype(np.int32))
    pos = jnp.asarray([0, 7, 15], jnp.int32)
    kq, vq, kw, kd, vd, tol = _as_cache(kc, vc, kv)

    def layer(_, i):
        return None, paged_flash_decode(q, kq, vq, bt, pos, layer=i,
                                        num_splits=2, interpret=True, **kw)

    _, outs = jax.jit(lambda: jax.lax.scan(layer, None, jnp.arange(L)))()
    for i in range(L):
        ref = _dense_ref(q, jnp.asarray(kd[i]), jnp.asarray(vd[i]), bt, pos)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   atol=tol, rtol=tol, err_msg=f"layer {i}")
    with pytest.raises(ValueError, match="layer"):
        paged_flash_decode(q, kq, vq, bt, pos, interpret=True, **kw)
    with pytest.raises(ValueError, match="stacked"):
        paged_flash_decode(q, kq[0], vq[0], bt, pos, layer=0,
                           interpret=True)


def test_resolve_num_splits_divides_table():
    assert resolve_num_splits(16, 4) == 4
    assert resolve_num_splits(6, 4) == 3    # largest divisor <= 4
    assert resolve_num_splits(7, 4) == 1    # prime width
    assert resolve_num_splits(4, 99) == 4   # clamped to the width
    assert resolve_num_splits(5, 1) == 1


@pytest.mark.multichip
def test_kernel_tp2_head_sharded_matches_unsharded():
    """The tp=2 serving layout (docs/multichip.md): KV cache sharded on
    the kv-head axis, query heads sharded to match, the kernel run
    per-device under shard_map — must equal the unsharded kernel AND
    the dense reference."""
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    q, kc, vc, bt, pos = _case(S=3, H=4, n_kv=2, seed=11)
    ref = _dense_ref(q, kc, vc, bt, pos)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    sharded = jax.jit(jax.shard_map(
        lambda q_, k_, v_, b_, p_: paged_flash_decode(
            q_, k_, v_, b_, p_, interpret=True),
        mesh=mesh,
        in_specs=(P(None, "model", None), P(None, "model", None, None),
                  P(None, "model", None, None), P(None, None), P(None)),
        out_specs=P(None, "model", None), check_vma=False))
    out = sharded(q, kc, vc, bt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    plain = paged_flash_decode(q, kc, vc, bt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.multichip
def test_paged_model_tp2_flash_token_identical():
    """End to end: a tp=2 PagedLlamaModel decoding through the
    shard_map'd flash kernel emits the same tokens as the single-device
    dense-gather model on the same weights."""
    from zoo_tpu.models.llm.llama import tiny_llama_config
    from zoo_tpu.parallel import build_mesh
    from zoo_tpu.serving.llm.engine import LLMEngine
    from zoo_tpu.serving.llm.model import PagedLlamaModel

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    cfg = tiny_llama_config(vocab=64)
    kw = dict(seed=0, num_slots=2, block_size=4, num_blocks=24,
              max_blocks_per_seq=6, prefill_buckets=(8, 16))
    base = PagedLlamaModel(cfg, **kw)
    mesh = build_mesh(jax.devices()[:2], axis_sizes={"model": 2})
    tp = PagedLlamaModel(cfg, mesh=mesh, decode_impl="flash", **kw)
    assert tp.tp == 2 and tp.decode_attention_impl == "flash"

    import time as _t

    def streams(model):
        eng = LLMEngine(model).start()
        try:
            rs = np.random.RandomState(5)
            hs = [eng.submit(rs.randint(0, cfg.vocab, (n,)), 6)
                  for n in (3, 9)]
            end = _t.monotonic() + 300
            while not all(h.done for h in hs):
                assert _t.monotonic() < end, \
                    [(h.outcome, h.error) for h in hs]
                _t.sleep(0.005)
            assert all(h.outcome == "ok" for h in hs), \
                [(h.outcome, h.error) for h in hs]
            return [h.tokens for h in hs]
        finally:
            eng.stop()

    assert streams(tp) == streams(base)
    counts = tp.compile_counts()
    if counts["decode"] >= 0:
        assert counts["decode"] == 1, counts
