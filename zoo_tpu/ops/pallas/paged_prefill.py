"""Paged flash-prefill: a chunk of queries through a block table.

The PR 10 chunk executable bounded the prefill stall, but its attention
still ran the dense reference: every chunk call gathers the FULL table
width of cache (``cache[block_table]`` → a ``(ctx, heads, d)`` copy per
layer, broadcast over the chunk rows) before the masked softmax reads it
back — the exact double-billing the paged flash-decode kernel removed
from the decode path. This kernel is the prefill/verify counterpart:

* **paged** — K/V blocks are streamed IN PLACE through a
  scalar-prefetched block table (dead entries clamp to the resident
  trash block 0, so no DMA is wasted on blocks past the live length),
  out of the WHOLE stacked ``(n_layer, num_blocks, H_kv, block, D)``
  cache with the layer as a third prefetched scalar, as the decode
  kernel reads it: the layer loop carries the cache and slices nothing;
* **flash** — online-softmax accumulation in VMEM scratch per chunk
  row, never a ``(ctx,)`` score row in HBM;
* **chunk-causal** — each query row carries its own cache position and
  attends every resident column ``<= position``: causal within the
  chunk AND over everything earlier ticks wrote, because the chunk's
  own K/V are appended to the cache *before* the kernel runs (same
  ordering as the dense chunk path);
* **batched** — the leading axis is sequences: the chunked-prefill
  executable calls it with one sequence, the speculative-decode VERIFY
  executable with every slot's ``k + 1`` candidate rows at once; both
  shapes compile exactly once;
* **GQA-aware + int8** — the ``n_head / n_kv_head`` query heads of a
  KV head are batched per block stream, and an int8 cache hands the
  kernel its per-row absmax scales for in-register dequant after the
  DMA (HBM moves int8 bytes; the math stays f32, exactly like the
  dense path's gather-then-widen).

There is no split-KV axis: unlike decode (one query per sequence), a
chunk exposes ``rows x kv_heads`` programs of parallelism already, and
prefill is compute-bound — the sequential walk over table entries keeps
the online-softmax carry in VMEM with zero merge epilogue.

Off-TPU the kernel runs under the Pallas interpreter (exact, slow); the
CPU suite asserts token identity against the dense-gather reference on
the same code path TPU hardware compiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zoo_tpu.ops.pallas import LANES as _LANES
from zoo_tpu.ops.pallas import SUBLANES as _SUBLANES
from zoo_tpu.ops.pallas import pad_dim as _pad_dim
from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret
from zoo_tpu.ops.pallas.paged_decode import stacked_cache


def attend_block(h, q, k, v, k_scale, v_scale, start, limit, scale,
                 m_scr, l_scr, a_scr):
    """One kv head's share of one cache block, folded into the online
    softmax carried in VMEM scratch. ``q`` (rows, D) against ``k``/``v``
    (block, D); column ``c`` of the block is cache index ``start + c``
    and a row
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


def scale_row(ref, h, block_size):
    """Head ``h``'s (1, block) stretch of a block's head-major scale
    row ``(1, 1, H_kv * block)``; None for an unquantized cache."""
    if ref is None:
        return None
    return ref[0][:, h * block_size:(h + 1) * block_size]


def _kernel(bt_ref, last_ref, lay_ref, q_ref, pos_ref, k_ref, v_ref,
            *rest, n_kv, block_size, width, scale, quantized):
    """One (sequence, table-entry) program; the innermost grid axis
    walks the table with the online-softmax carry in VMEM scratch. Each
    entry's block arrives with ALL its kv heads — ``(n_kv, block_size,
    D)``, the cache's own minor dims, the layer axis squeezed out by
    the BlockSpec (``lay_ref`` is for the index maps alone) — and the
    heads are walked by a static loop. Rows = chunk positions x the kv
    head's query group, flattened (and padded to the sublane tile) by
    the wrapper, with each row's cache position riding a lane-broadcast
    carrier."""
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    out_ref, m_scr, l_scr, a_scr = rest
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    # rows attend columns <= their own position; positions are
    # nondecreasing per chunk, so a block wholly past the LAST row's
    # position is dead for every row — skip (the index map already
    # clamped its DMA to the resident trash block)
    start = j * block_size

    @pl.when(start <= last_ref[s])
    def _step():
        prow = pos_ref[0][:, :1]                              # (rows, 1)
        for h in range(n_kv):
            attend_block(
                h, q_ref[0, h], k_ref[0, h], v_ref[0, h],
                scale_row(ks_ref, h, block_size),
                scale_row(vs_ref, h, block_size),
                start, prow, scale, m_scr, l_scr, a_scr)

    @pl.when(j == width - 1)
    def _finish():
        l = l_scr[...][:, :, :1]
        out_ref[0] = (a_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            out_ref.dtype)


def paged_flash_prefill(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray,
                        block_tables: jnp.ndarray,
                        positions: jnp.ndarray, *,
                        layer=None,
                        k_scale: Optional[jnp.ndarray] = None,
                        v_scale: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Chunk-of-queries paged attention over a resident cache.

    ``q``: (S, C, H, D) — C query rows per sequence (a prefill chunk,
    or a verify pass's k+1 candidate rows); ``k_cache``/``v_cache``:
    (n_layer, num_blocks, H_kv, block_size, D) with ``layer`` the
    (traced) index of the layer attended, or one layer's 4-D
    (num_blocks, H_kv, block_size, D) with none; ``block_tables``:
    (S, W) int32;
    ``positions``: (S, C) int32 — the cache index each row's token was
    written at, NONDECREASING per sequence (row r attends every column
    ``<= positions[s, r]``, which covers causal-within-chunk plus the
    resident prefix). Returns (S, C, H, D) in ``q``'s dtype.

    An int8 cache passes ``k_scale``/``v_scale`` (per-(block, kv-head,
    row) absmax, a head-major row a block: (n_layer, num_blocks, 1,
    H_kv * block_size)); each block stream is widened in VMEM right
    after the DMA."""
    S, C, H, D = q.shape
    k_cache, v_cache, k_scale, v_scale, lay = stacked_cache(
        k_cache, v_cache, k_scale, v_scale, layer)
    _, _, n_kv, block_size, _ = k_cache.shape
    quantized = k_scale is not None
    if H % n_kv:
        raise ValueError(f"q heads ({H}) must be a multiple of kv "
                         f"heads ({n_kv})")
    if positions.shape != (S, C):
        raise ValueError(f"positions shape {positions.shape} != "
                         f"{(S, C)}")
    group = H // n_kv
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    interpret = _resolve_interpret(interpret)

    # (S, n_kv, C*group, D): a kv head's C*group query rows are one
    # matmul operand. The flatten happens HERE (an XLA reshape), not in
    # the kernel, and the row count is padded to the sublane tile —
    # pad rows sit at position 0, attend one column and are sliced off.
    rows = C * group
    q5 = q.reshape(S, C, n_kv, group, D).transpose(0, 2, 1, 3, 4)
    q4 = _pad_dim(q5.reshape(S, n_kv, rows, D), 2, _SUBLANES)
    rows_p = q4.shape[2]
    bt = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    # per-ROW positions in a lane-broadcast carrier (the flash kernel's
    # lse idiom): row r covers chunk index r // group
    prow = jnp.broadcast_to(
        _pad_dim(jnp.repeat(pos, group, axis=1), 1, _SUBLANES)[..., None],
        (S, rows_p, _LANES))
    last = pos[:, C - 1]

    def _entry(s, j, bt_ref, last_ref):
        # dead entries (whole block past the last row's position) clamp
        # to block 0 so the pipeline re-fetches the resident trash
        # block instead of streaming a block the kernel will skip
        live = j * block_size <= last_ref[s]
        return jnp.where(live, bt_ref[s, j], 0)

    def _q_map(s, j, bt_ref, last_ref, lay_ref):
        return s, 0, 0, 0

    def _kv_map(s, j, bt_ref, last_ref, lay_ref):
        return lay_ref[0], _entry(s, j, bt_ref, last_ref), 0, 0, 0

    kernel = functools.partial(
        _kernel, n_kv=n_kv, block_size=block_size, width=W, scale=scale,
        quantized=quantized)
    kv_spec = pl.BlockSpec((None, 1, n_kv, block_size, D), _kv_map)
    in_specs = [
        pl.BlockSpec((1, n_kv, rows_p, D), _q_map),
        pl.BlockSpec((1, rows_p, _LANES),
                     lambda s, j, bt_ref, last_ref, lay_ref: (s, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q4, prow, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec(
            (None, 1, 1, n_kv * block_size),
            lambda s, j, bt_ref, last_ref, lay_ref:
            (lay_ref[0], _entry(s, j, bt_ref, last_ref), 0, 0))] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, W),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, n_kv, rows_p, D), _q_map)],
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows_p, _LANES), jnp.float32),
            pltpu.VMEM((n_kv, rows_p, _LANES), jnp.float32),
            pltpu.VMEM((n_kv, rows_p, D), jnp.float32),
        ],
    )
    # sequence programs are independent — parallel over cores; the
    # table walk carries the VMEM softmax state and must stay
    # sequential
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        out_shape=[
            jax.ShapeDtypeStruct((S, n_kv, rows_p, D), q.dtype),
        ],
        interpret=interpret,
        name="zoo_paged_prefill",
    )(bt, last, lay, *operands)
    out = out[:, :, :rows].reshape(S, n_kv, C, group, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(S, C, H, D)
