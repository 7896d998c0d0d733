"""Absorbed latent-attention (MLA) paged decode: one query per slot
against a cache of latent rows, read once and used as key AND value.

A latent-attention layer caches ONE row a token, ``[c_kv ‖ k_rope]``
(``rank + rope`` values, 512 + 64 for GLM-4.7-Flash), shared by every
query head. In the absorbed form the per-head key up-projection is
folded into the query (``q_lat = q_nope . W_uk^T``, (H, rank)) and the
value up-projection into the output, so the attention itself is
multi-query over the latent rows::

    score = (q_lat . c_kv + q_rope . k_rope) * scale
    o_lat = softmax(score) . c_kv                      # (H, rank)

This kernel is that attention through the block table, in the shape of
:mod:`zoo_tpu.ops.pallas.paged_decode` (scalar-prefetched table, online
softmax in VMEM scratch, the context split into ``num_splits`` grid
programs merged by a jnp log-sum-exp epilogue), with three differences
the layout asks for:

* the cache operand is the WHOLE ``(n_layer, num_blocks, block, D)``
  array and the layer is a prefetched scalar in the index map, so the
  layer scan carries the cache and never slices a layer's slab out of
  it (no whole-cache or whole-layer copy on the decode path);
* a block of 16 latent rows is 18 KB, too small a DMA to amortise a
  grid step: each step fetches ``blocks_per_step`` table entries (the
  cache is handed over that many times, one ``BlockSpec`` each, so the
  pipeline keeps that many DMAs in flight) and attends them as one
  ``(H, D) @ (D, blocks_per_step * block)`` product;
* the H query heads of a slot ride one matmul against the shared rows,
  and the same VMEM tile is the value operand of the second.

Dead entries (past the slot's live length) clamp to the trash block 0,
which the position mask hides; a step whose entries are all dead is
skipped. Off-TPU the kernel runs under the Pallas interpreter, and
``serving/llm/model_mla.py`` falls back to a dense gather there.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zoo_tpu.ops.pallas import LANES as _LANES
from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret
from zoo_tpu.ops.pallas.paged_decode import resolve_num_splits

# table entries a grid step attends: 16 blocks of 16 rows are 256
# latent rows, 327 KB at the 640 bf16 values a row is held in
BLOCKS_PER_STEP = 16


def _kernel(layer_ref, bt_ref, pos_ref, ql_ref, qr_ref, *rest,
            n_fetch, block_size, steps, rank, rope, scale):
    """One (slot, split) program; the innermost grid axis walks the
    split's ``steps`` groups of ``n_fetch`` table entries with the
    online-softmax carry in VMEM scratch."""
    blocks = rest[:n_fetch]
    acc_ref, m_ref, l_ref, m_scr, l_scr, a_scr = rest[n_fetch:]
    s = pl.program_id(0)
    split = pl.program_id(1)
    j = pl.program_id(2)
    pos = pos_ref[s]
    start = (split * steps + j) * n_fetch * block_size

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    @pl.when(start <= pos)
    def _step():
        lat = jnp.concatenate([b[0, 0] for b in blocks], axis=0)
        ckv, kr = lat[:, :rank], lat[:, rank:rank + rope]
        contract_last = (((1,), (1,)), ((), ()))
        s_ = (jax.lax.dot_general(ql_ref[0], ckv, contract_last,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_ref[0], kr, contract_last,
                                    preferred_element_type=jnp.float32)
              ) * scale                                   # (H, rows)
        col = start + jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
        mask = col <= pos
        s_ = jnp.where(mask, s_, -jnp.inf)
        m_prev = m_scr[...][:, :1]                        # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        # the step holds at least one live column (start <= pos), so
        # m_new is finite
        p = jnp.exp(s_ - m_new)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new),
                         0.0)
        l_new = corr * l_scr[...][:, :1] + jnp.sum(p, axis=-1,
                                                    keepdims=True)
        a_scr[...] = a_scr[...] * corr + jax.lax.dot_general(
            p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == steps - 1)
    def _finish():
        acc_ref[0, 0] = a_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def mla_paged_decode(q_lat: jnp.ndarray, q_rope: jnp.ndarray,
                     cache: jnp.ndarray, layer, block_tables: jnp.ndarray,
                     positions: jnp.ndarray, *, scale: float,
                     blocks_per_step: Optional[int] = None,
                     num_splits: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Absorbed latent attention for one decode tick.

    ``q_lat`` (S, H, rank) and ``q_rope`` (S, H, rope): one query per
    slot, the nope half already folded through ``W_uk``; ``cache``
    (n_layer, num_blocks, block_size, D >= rank + rope), every layer's
    latent rows (the model pads D to whole 128-lane tiles); ``layer`` the (traced) index of the layer attended;
    ``block_tables`` (S, W) int32; ``positions`` (S,) int32, the cache
    index of the slot's incoming token (rows ``0..position`` are
    attended). Returns ``o_lat`` (S, H, rank) float32, to be folded
    through ``W_uv`` by the caller."""
    S, H, rank = q_lat.shape
    rope = q_rope.shape[-1]
    _, _, block_size, D = cache.shape
    if D < rank + rope:
        raise ValueError(f"cache rows are {D} wide, the queries ask for "
                         f"{rank} + {rope}")
    W = block_tables.shape[1]
    interpret = _resolve_interpret(interpret)
    n_fetch = int(blocks_per_step or BLOCKS_PER_STEP)
    n_fetch = max(1, min(n_fetch, W))
    groups = -(-W // n_fetch)
    splits = resolve_num_splits(groups, num_splits)
    steps = groups // splits
    # the table padded to whole steps: the padding is dead entries
    bt = jnp.pad(block_tables.astype(jnp.int32),
                 ((0, 0), (0, groups * n_fetch - W)))
    pos = positions.astype(jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    def fetch_map(i):
        def index(s, sp, j, lay_ref, bt_ref, pos_ref):
            # dead entries clamp to block 0, so the pipeline re-fetches
            # the resident trash block instead of streaming a block the
            # mask will hide
            idx = (sp * steps + j) * n_fetch + i
            live = idx * block_size <= pos_ref[s]
            return lay_ref[0], jnp.where(live, bt_ref[s, idx], 0), 0, 0
        return index

    def q_map(s, sp, j, lay_ref, bt_ref, pos_ref):
        return s, 0, 0

    def out_map(s, sp, j, lay_ref, bt_ref, pos_ref):
        return s, sp, 0, 0

    kernel = functools.partial(
        _kernel, n_fetch=n_fetch, block_size=block_size, steps=steps,
        rank=rank, rope=rope, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, splits, steps),
        in_specs=[pl.BlockSpec((1, H, rank), q_map),
                  pl.BlockSpec((1, H, rope), q_map)]
        + [pl.BlockSpec((1, 1, block_size, D), fetch_map(i))
           for i in range(n_fetch)],
        out_specs=[pl.BlockSpec((1, 1, H, rank), out_map),
                   pl.BlockSpec((1, 1, H, _LANES), out_map),
                   pl.BlockSpec((1, 1, H, _LANES), out_map)],
        scratch_shapes=[pltpu.VMEM((H, _LANES), jnp.float32),
                        pltpu.VMEM((H, _LANES), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        out_shape=[jax.ShapeDtypeStruct((S, splits, H, rank), jnp.float32),
                   jax.ShapeDtypeStruct((S, splits, H, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((S, splits, H, _LANES),
                                        jnp.float32)],
        interpret=interpret,
        name="zoo_mla_decode",
    )(lay, bt, pos, q_lat.astype(cache.dtype), q_rope.astype(cache.dtype),
      *([cache] * n_fetch))

    # split epilogue: merge the partial softmaxes with the log-sum-exp
    # correction (a dead split carries m=-inf / l=0 and drops out)
    m0, l0 = m[..., 0], l[..., 0]                       # (S, splits, H)
    m_max = jnp.max(m0, axis=1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m_max), m_max, 0.0)
    alpha = jnp.where(jnp.isfinite(m0), jnp.exp(m0 - m_safe), 0.0)
    l_tot = jnp.sum(alpha * l0, axis=1)                 # (S, H)
    return jnp.sum(alpha[..., None] * acc, axis=1) / \
        jnp.where(l_tot == 0.0, 1.0, l_tot)[..., None]


def mla_decode_reference(q_lat, q_rope, cache, layer, block_tables,
                         positions, *, scale: float) -> jnp.ndarray:
    """The dense gather the kernel is held to: materialise
    ``cache[layer][block_table]`` per slot, mask to the live length,
    softmax in float32. Also the off-TPU decode path."""
    S, H, rank = q_lat.shape
    g = cache[layer][block_tables]                  # (S, W, block, D)
    lat = g.reshape(S, -1, g.shape[-1])
    ckv, kr = lat[..., :rank], lat[..., rank:rank + q_rope.shape[-1]]
    dt = cache.dtype
    s_ = (jnp.einsum("shc,stc->sht", q_lat.astype(dt), ckv,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("shr,str->sht", q_rope.astype(dt), kr,
                       preferred_element_type=jnp.float32)) * scale
    live = jnp.arange(lat.shape[1])[None, :] <= positions[:, None]
    s_ = jnp.where(live[:, None, :], s_, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("sht,stc->shc", p.astype(dt), ckv,
                      preferred_element_type=jnp.float32)
