"""Block-sparse paged decode: single-query attention over the pages a
selection picked, one table a (slot, K/V head).

``zoo_paged_decode`` (:mod:`zoo_tpu.ops.pallas.paged_decode`) walks a
slot's ONE block table and every kv head rides along with each entry.
Under a block-sparse selection (InfLLM-V2) every K/V head of a slot
attends its own set of pages, in no order, and only the query's own
page is cut short by the causal bound. So this kernel takes, for every
(slot, kv head): a table of physical pages, the number of leading rows
of each that are attended (``lens``: the page size, the query's own
page less, 0 for a dead entry) and how many leading entries are live.

What it keeps of the PR 31 body: the cache stays whole in HBM
(``(n_layer, num_blocks, H_kv, block, D)`` and the layer a prefetched
scalar, so a layer loop carries the cache and slices nothing out of
it), a step copies several entries' ``(block, D)`` slabs of ONE kv head
into a double-buffered VMEM slab itself, the next step's in flight
while this one is attended, and the loop runs over the live steps
alone. What differs: the grid is (slot, kv head), a step's entries are
one ``(group, D) @ (D, entries * block)`` product against the group's
query rows (no block-diagonal waste: a head's pages hold that head's
keys only), and the mask is a row count an entry, not a position.

The same warning as there: the interpreter completes a DMA where it is
started, so a read ahead of its wait passes every CPU test and races on
the chip; ``chip_smoke.py --legs kernels`` holds it against
:func:`sparse_decode_reference` on the chip.
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

# the K + V bytes one step fetches, at most (8 bf16 pages of 64 x 128)
STEP_BYTES = 256 * 1024
MAX_ENTRIES = 16


def entries_per_step(width: int, block_size: int, head_dim: int,
                     itemsize: int) -> int:
    entry = 2 * block_size * head_dim * itemsize
    return max(1, min(STEP_BYTES // entry, MAX_ENTRIES, width))


def sparse_decode_reference(q, k_cache, v_cache, tables, lens, *, layer,
                            scale: Optional[float] = None):
    """The plain-XLA twin: gather every table entry's page and mask.
    ``q`` (S, G, Hg, D); caches (n_layer, num_blocks, G, block, D);
    ``tables`` / ``lens`` (S, G, E). Returns (S, G, Hg, D) float32; a
    (slot, head) with no live row reads 0."""
    S, G, Hg, D = q.shape
    bs = k_cache.shape[3]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    heads = jnp.arange(G)[None, :, None]
    keys = k_cache[layer, tables, heads]            # (S, G, E, block, D)
    vals = v_cache[layer, tables, heads]
    s = jnp.einsum("sghd,sgetd->sghet", q.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(bs)[None, None, None, :] < lens[..., None]
    s = jnp.where(live[:, :, None], s, -jnp.inf).reshape(S, G, Hg, -1)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).astype(vals.dtype)
    return jnp.einsum("sght,sgtd->sghd", p,
                      vals.reshape(S, G, -1, D),
                      preferred_element_type=jnp.float32)


def _kernel(tbl_ref, len_ref, n_ref, lay_ref, q_ref, k_hbm, v_hbm, o_ref,
            m_scr, l_scr, a_scr, k_buf, v_buf, sem, *, n_fetch, scale):
    """One (slot, kv head) program: a loop over the live steps of
    ``n_fetch`` table entries."""
    n_kv = pl.num_programs(1)
    g = pl.program_id(1)
    sg = pl.program_id(0) * n_kv + g
    n = n_ref[sg]
    lay = lay_ref[0]
    block_size, D = k_buf.shape[2], k_buf.shape[3]
    C = n_fetch * block_size
    steps = (n + n_fetch - 1) // n_fetch

    def copies(step, buf, routed):
        k_copies, v_copies = [], []
        for e in range(n_fetch):
            # a dead entry of the table names the trash block 0
            blk = tbl_ref[sg, step * n_fetch + e] if routed else 0
            k_copies.append(pltpu.make_async_copy(
                k_hbm.at[lay, blk, g], k_buf.at[buf, e], sem.at[0, buf]))
            v_copies.append(pltpu.make_async_copy(
                v_hbm.at[lay, blk, g], v_buf.at[buf, e], sem.at[1, buf]))
        return k_copies, v_copies

    def start(step, buf):
        k_copies, v_copies = copies(step, buf, True)
        for c in k_copies + v_copies:
            c.start()

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)

    @pl.when(steps > 0)
    def _first():
        start(0, 0)

    Hg = q_ref.shape[2]
    col = jax.lax.broadcasted_iota(jnp.int32, (Hg, C), 1)
    entry = jax.lax.div(col, block_size)
    row = jax.lax.rem(col, block_size)

    def step(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < steps)
        def _next():
            start(i + 1, 1 - buf)

        k_copies, v_copies = copies(i, buf, False)
        for c in k_copies:
            c.wait()
        keys = k_buf[buf].reshape(C, D)
        q = q_ref[0, 0].astype(keys.dtype)
        s_ = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (Hg, C)
        # the rows of each entry that are attended: its first ``len``
        limit = jnp.zeros((Hg, C), jnp.int32)
        for e in range(n_fetch):
            limit = jnp.where(entry == e,
                              len_ref[sg, i * n_fetch + e], limit)
        s_ = jnp.where(row < limit, s_, -jnp.inf)
        # the table is live-first: every live step has a live column
        m_prev = m_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[...][:, :1] + jnp.sum(p, axis=-1,
                                                   keepdims=True)
        for c in v_copies:
            c.wait()
        vals = v_buf[buf].reshape(C, D)
        a_scr[...] = a_scr[...] * corr + jax.lax.dot_general(
            p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    l_end = l_scr[...][:, :1]
    o_ref[0, 0] = a_scr[...] / jnp.where(l_end == 0.0, 1.0, l_end)


def sparse_paged_decode(q, k_cache, v_cache, tables, lens, n_live, *,
                        layer, scale: Optional[float] = None,
                        entries: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Single-query attention of every (slot, kv head) over its own
    table of pages.

    ``q``: (S, G, Hg, D), the ``Hg`` query heads of each of the ``G``
    kv heads; ``k_cache`` / ``v_cache``: (n_layer, num_blocks, G, block,
    D) with ``layer`` the (traced) layer attended; ``tables``: (S, G, E)
    int32 physical pages, LIVE ENTRIES FIRST; ``lens``: (S, G, E) int32,
    the leading rows of each entry that are attended (0 for a dead
    entry); ``n_live``: (S, G) int32, how many leading entries are live.
    Returns (S, G, Hg, D) float32; a (slot, head) with no live entry
    reads 0."""
    S, G, Hg, D = q.shape
    _, _, n_kv, block_size, _ = k_cache.shape
    if n_kv != G:
        raise ValueError(f"q groups ({G}) != kv heads ({n_kv})")
    E = tables.shape[-1]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    interpret = _resolve_interpret(interpret)
    n_fetch = entries or entries_per_step(E, block_size, D,
                                          k_cache.dtype.itemsize)
    width = -(-E // n_fetch) * n_fetch
    pad = ((0, 0), (0, width - E))
    tbl = jnp.pad(tables.astype(jnp.int32).reshape(S * G, E), pad)
    ln = jnp.pad(lens.astype(jnp.int32).reshape(S * G, E), pad)
    slab = (2, n_fetch, block_size, D)
    return pl.pallas_call(
        functools.partial(_kernel, n_fetch=n_fetch, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S, G),
            in_specs=[pl.BlockSpec((1, 1, Hg, D),
                                   lambda s, g, *_: (s, g, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, 1, Hg, D),
                                   lambda s, g, *_: (s, g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Hg, _LANES), jnp.float32),
                            pltpu.VMEM((Hg, _LANES), jnp.float32),
                            pltpu.VMEM((Hg, D), jnp.float32),
                            pltpu.VMEM(slab, k_cache.dtype),
                            pltpu.VMEM(slab, v_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        out_shape=jax.ShapeDtypeStruct((S, G, Hg, D), jnp.float32),
        interpret=interpret,
        name="zoo_sparse_decode",
    )(tbl, ln, n_live.astype(jnp.int32).reshape(S * G),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_cache, v_cache)
