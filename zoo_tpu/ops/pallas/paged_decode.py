"""Paged flash-decode: single-query attention through a block table.

The decode hot path is memory-bound — each generated token must stream
every live K/V byte of its sequence out of HBM exactly once, so the
roofline that matters is HBM bytes/token, not FLOPs. PR 7's decode
executable paid that bill twice: ``cache[block_table]`` materializes a
gathered ``(slots, ctx, heads, d)`` copy of every sequence's K/V in HBM
*before* the attention math reads it back. This kernel is the
PagedAttention/flash-decoding rebuild (Kwon et al., SOSP '23; Dao et
al., 2023):

* **paged** — K/V blocks are read directly where they live, routed by a
  scalar-prefetched block table in the ``BlockSpec`` index maps, so the
  per-sequence gather copy never exists;
* **stacked** — the cache operand is the WHOLE ``(n_layer, num_blocks,
  H_kv, block, D)`` array and the layer is a third prefetched scalar,
  the leading block index of the K/V and scale maps, so a layer loop
  carries the cache and never slices a layer's slab out of it (a 4-D
  cache is the same path at one layer);
* **flash** — online-softmax accumulation in VMEM scratch, never a
  ``(ctx,)`` score row in HBM;
* **split-KV** — the sequence axis is cut into ``num_splits`` grid
  programs that each produce a partial ``(acc, m, l)``; a tiny jnp
  epilogue merges them with the standard log-sum-exp correction. At
  decode there is ONE query per sequence, so without the split the
  kernel exposes only ``slots x kv_heads`` programs of parallelism —
  splitting the KV length is what keeps the cores busy at low
  occupancy (the flash-decoding observation);
* **GQA-aware** — the ``group = n_head / n_kv_head`` query heads that
  share a KV head are batched into one ``(group, d) @ (d, block)``
  matmul, so each K/V block is streamed once per KV head, not once per
  query head.

Blocks past a sequence's live length are skipped via ``pl.when`` (no
MXU work, no DMA consumed), and a fully-dead split contributes
``m=-inf, l=0`` which the epilogue drops — inactive slots (position 0,
table full of trash-block zeros) produce garbage that the engine never
reads, exactly like the dense path.

Off-TPU the kernel runs under the Pallas interpreter (exact, slow), so
the CPU test rig asserts token identity against the dense-gather
reference on the same code path TPU hardware compiles.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zoo_tpu.ops.pallas import LANES as _LANES
from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret


def attend_block(h, q, k, v, k_scale, v_scale, start, limit, scale,
                 m_scr, l_scr, a_scr):
    """One kv head's share of one cache block, folded into the online
    softmax carried in VMEM scratch (shared by the paged decode and
    prefill kernels). ``q`` (rows, D) against ``k``/``v`` (block, D);
    column ``c`` of the block is cache index ``start + c`` and a row
    attends it iff that is ``<= limit`` (a scalar position, or a
    (rows, 1) column of per-row positions). An int8 block comes with
    its (1, block) scale rows (the head's stretch of the block's
    head-major scale row): it is widened in register and the scales
    land on the (rows, block) score tile — K's on the scores, V's on
    the probabilities, both row broadcasts with no relayout — so HBM
    moves the int8 bytes and the math stays f32."""
    if k_scale is not None:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (rows, block)
    if k_scale is not None:
        s_ = s_ * k_scale
    col = start + jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
    mask = col <= limit
    s_ = jnp.where(mask, s_, -jnp.inf)
    m_prev = m_scr[h][:, :1]                              # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(mask, s_ - safe, -jnp.inf))
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe), 0.0)
    l_new = corr * l_scr[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    a_scr[h] = a_scr[h] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # full-lane stores: every lane of a row carries the value
    m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
    l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def stacked_cache(k_cache, v_cache, k_scale, v_scale, layer):
    """The operands as both paged kernels take them: K and V
    ``(n_layer, num_blocks, H_kv, block, D)``, the scale planes (or
    None) ``(n_layer, num_blocks, 1, H_kv * block)`` in float32 — a
    block's scales are one head-major row, which at 128 values is how
    the TPU holds the plane anyway — and the layer as a ``(1,)`` int32
    array to prefetch. A 4-D cache (3-D scale planes) with no ``layer``
    is one layer's: the stacked case at layer 0, a free reshape."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale travel together")
    if k_cache.ndim == 4:
        if layer is not None:
            raise ValueError("a layer index needs the stacked "
                             "(n_layer, num_blocks, H_kv, block, D) cache")
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    elif layer is None:
        raise ValueError("a stacked cache needs the layer to attend")
    if k_scale is not None:
        n_layer, n_blocks, n_kv, block_size, _ = k_cache.shape
        want = (n_layer, n_blocks, 1, n_kv * block_size)
        for s_arr in (k_scale, v_scale):
            if s_arr.shape != want:
                raise ValueError(f"scale shape {s_arr.shape} != {want}")
        k_scale = k_scale.astype(jnp.float32)
        v_scale = v_scale.astype(jnp.float32)
    return (k_cache, v_cache, k_scale, v_scale,
            jnp.asarray(layer, jnp.int32).reshape(1))


def scale_row(ref, h, block_size):
    """Head ``h``'s (1, block) stretch of a block's head-major scale
    row ``(1, 1, H_kv * block)``; None for an unquantized cache."""
    if ref is None:
        return None
    return ref[0][:, h * block_size:(h + 1) * block_size]


def _kernel(bt_ref, pos_ref, lay_ref, q_ref, k_ref, v_ref, *rest,
            n_kv, block_size, bps, scale, quantized):
    """One (slot, split) program; the innermost grid axis walks the
    split's ``bps`` table entries with the online-softmax carry in VMEM
    scratch. Each entry's block arrives with ALL its kv heads —
    ``(n_kv, block_size, D)``, the cache's own minor dims, which is
    what the TPU lowering can slice — and the heads are walked by a
    static loop (the layer axis is squeezed out by the BlockSpec;
    ``lay_ref`` is for the index maps alone). ``quantized`` adds two
    per-(block, kv-head, row) scale refs after ``v_ref`` (see
    :func:`attend_block`)."""
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    acc_ref, m_ref, l_ref, m_scr, l_scr, a_scr = rest
    s = pl.program_id(0)
    split = pl.program_id(1)
    j = pl.program_id(2)
    pos = pos_ref[s]
    start = (split * bps + j) * block_size

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    # whole block past the live length: skip — no matmul, and (because
    # the index map clamps dead entries to block 0) no fresh DMA either
    @pl.when(start <= pos)
    def _step():
        for h in range(n_kv):
            attend_block(
                h, q_ref[0, h], k_ref[0, h], v_ref[0, h],
                scale_row(ks_ref, h, block_size),
                scale_row(vs_ref, h, block_size),
                start, pos, scale, m_scr, l_scr, a_scr)

    @pl.when(j == bps - 1)
    def _finish():
        acc_ref[0, 0] = a_scr[...].astype(acc_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def resolve_num_splits(table_width: int,  # zoo-lint: config-parse
                       requested: Optional[int] = None) -> int:
    """Largest divisor of ``table_width`` not exceeding the request
    (``ZOO_LLM_DECODE_SPLITS``, default 4): splits must tile the table
    exactly so every grid program walks the same number of entries."""
    if requested is None:
        requested = int(os.environ.get("ZOO_LLM_DECODE_SPLITS", "4"))
    requested = max(1, min(int(requested), table_width))
    for d in range(requested, 0, -1):
        if table_width % d == 0:
            return d
    return 1


def paged_flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray,
                       v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                       positions: jnp.ndarray, *,
                       layer=None,
                       k_scale: Optional[jnp.ndarray] = None,
                       v_scale: Optional[jnp.ndarray] = None,
                       scale: Optional[float] = None,
                       num_splits: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-query paged attention for one decode tick.

    ``q``: (S, H, D) — one query per slot; ``k_cache``/``v_cache``:
    (n_layer, num_blocks, H_kv, block_size, D), every layer's blocks,
    with ``layer`` the (traced) index of the layer attended —
    ``(block_size, D)`` are the minor dims so one block of every kv
    head is a slab the TPU can DMA and index by head; a 4-D
    (num_blocks, H_kv, block_size, D) cache with no ``layer`` is one
    layer's; ``block_tables``: (S, W) int32; ``positions``: (S,) int32
    — the cache index the slot's incoming token was written at (tokens
    ``0..position`` are attended). Returns (S, H, D) in ``q``'s dtype.

    An int8 cache passes ``k_scale``/``v_scale`` — per-(block, kv-head,
    row) absmax scales, a head-major row a block: (n_layer, num_blocks,
    1, H_kv * block_size) — and each block stream is dequantized in
    VMEM right after the DMA, so the HBM roofline sees int8 bytes while
    the softmax math stays f32 (a bf16 cache needs no scales; the
    matmuls widen it natively).
    """
    S, H, D = q.shape
    k_cache, v_cache, k_scale, v_scale, lay = stacked_cache(
        k_cache, v_cache, k_scale, v_scale, layer)
    _, _, n_kv, block_size, _ = k_cache.shape
    quantized = k_scale is not None
    if H % n_kv:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads "
                         f"({n_kv})")
    group = H // n_kv
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    interpret = _resolve_interpret(interpret)
    splits = resolve_num_splits(W, num_splits)
    bps = W // splits

    q4 = q.reshape(S, n_kv, group, D)
    bt = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    def _entry(s, sp, j, bt_ref, pos_ref):
        # dead entries (whole block past the live length) are clamped to
        # block 0 so the pipeline re-fetches the already-resident trash
        # block instead of streaming a block the kernel will skip
        idx = sp * bps + j
        live = idx * block_size <= pos_ref[s]
        return jnp.where(live, bt_ref[s, idx], 0)

    def _kv_map(s, sp, j, bt_ref, pos_ref, lay_ref):
        return lay_ref[0], _entry(s, sp, j, bt_ref, pos_ref), 0, 0, 0

    def _out_map(s, sp, j, bt_ref, pos_ref, lay_ref):
        return s, sp, 0, 0, 0

    kernel = functools.partial(
        _kernel, n_kv=n_kv, block_size=block_size, bps=bps, scale=scale,
        quantized=quantized)
    kv_spec = pl.BlockSpec((None, 1, n_kv, block_size, D), _kv_map)
    in_specs = [
        pl.BlockSpec((1, n_kv, group, D),
                     lambda s, sp, j, bt_ref, pos_ref, lay_ref:
                     (s, 0, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q4, k_cache, v_cache]
    if quantized:
        # the scale rows ride the exact same layer and block-table
        # routing as their K/V block (dead entries clamp to the trash
        # block too)
        in_specs += [pl.BlockSpec(
            (None, 1, 1, n_kv * block_size),
            lambda s, sp, j, bt_ref, pos_ref, lay_ref:
            (lay_ref[0], _entry(s, sp, j, bt_ref, pos_ref), 0, 0))] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, splits, bps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, n_kv, group, D), _out_map),
            pl.BlockSpec((1, 1, n_kv, group, _LANES), _out_map),
            pl.BlockSpec((1, 1, n_kv, group, _LANES), _out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, _LANES), jnp.float32),
            pltpu.VMEM((n_kv, group, _LANES), jnp.float32),
            pltpu.VMEM((n_kv, group, D), jnp.float32),
        ],
    )
    # (slot, split) programs are independent — mark them parallel so
    # Mosaic can spread them over cores (megacore); only the innermost
    # block walk carries the VMEM softmax state and must stay
    # sequential. Without this the whole grid serializes and the
    # split-KV axis adds epilogue cost without its parallelism.
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        out_shape=[
            jax.ShapeDtypeStruct((S, splits, n_kv, group, D),
                                 jnp.float32),
            jax.ShapeDtypeStruct((S, splits, n_kv, group, _LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((S, splits, n_kv, group, _LANES),
                                 jnp.float32),
        ],
        interpret=interpret,
        name="zoo_paged_decode",
    )(bt, pos, lay, *operands)

    # split-KV epilogue: merge the per-split partial softmaxes with the
    # log-sum-exp correction (dead splits carry m=-inf/l=0 and drop out)
    m0 = m[..., 0]                                  # (S, splits, n_kv, G)
    l0 = l[..., 0]
    m_max = jnp.max(m0, axis=1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m_max), m_max, 0.0)
    alpha = jnp.where(jnp.isfinite(m0), jnp.exp(m0 - m_safe), 0.0)
    l_tot = jnp.sum(alpha * l0, axis=1)             # (S, n_kv, G)
    o = jnp.sum(alpha[..., None] * acc, axis=1) / \
        jnp.where(l_tot == 0.0, 1.0, l_tot)[..., None]
    return o.astype(q.dtype).reshape(S, H, D)
