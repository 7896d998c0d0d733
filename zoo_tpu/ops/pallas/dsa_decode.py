"""Token-sparse paged decode: single-query attention over the cache rows
a learned indexer picked, one list of tokens a slot shared by every
query head (DeepSeek Sparse Attention).

``zoo_sparse_decode`` (:mod:`zoo_tpu.ops.pallas.sparse_decode`) attends
whole pages, one table a K/V head. Under a selection by TOKEN the unit
is a row: each slot attends its own ``k`` tokens, scattered over the
context in no order, and every query head of the slot attends the same
ones. So the cache this kernel reads holds a token's keys AND values of
every K/V head together, ``kv`` ``(n_layer, num_blocks, block, R, C)``
with ``R * C = 2 * n_kv * D`` (K heads first, then V heads, each head's
``D`` values in a row of ``C`` lanes): at the published widths (4 K/V
heads of 128) a token is one ``(8, 128)`` tile of the TPU's layout, and
a selected token is ONE copy. A copy must move whole tiles (the
compiler refuses a row of a tile), which is why K and V share the leaf:
apart, each would be half a tile, padded to a whole one.

What it keeps of the paged kernels: the cache stays whole in HBM and the
layer is a prefetched scalar (a layer loop carries the cache and slices
nothing out of it), a step copies its tokens out of HBM into a
double-buffered VMEM slab itself, the next step's in flight while this
one is attended, and the loop runs over the live steps alone. What
differs: a step's entries are single tokens, named by their physical
row ``block * block_size + offset``; the grid is the slots; each K/V
head's group of query heads is one ``(group, D) @ (D, tokens)`` product
against that head's keys in the slab; the mask is the list's length.

The same warning as there: the interpreter completes a DMA where it is
started, so a read ahead of its wait passes every CPU test and races on
the chip; ``chip_smoke.py --legs kernels`` holds it against
:func:`dsa_decode_reference` on the chip.
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

# tokens one step copies
TOKENS_PER_STEP = 128


def kv_row_shape(n_kv: int, head_dim: int) -> tuple:
    """``(R, C)``: how a token's ``2 * n_kv * head_dim`` values (K heads,
    then V heads) lie in the ``kv`` leaf: rows of 128 lanes where they
    fill them, else one row."""
    width = 2 * n_kv * head_dim
    return (width // 128, 128) if width % 128 == 0 else (1, width)


def _head(x, h, D):
    """Head ``h``'s ``D`` values of tokens ``x`` (..., R, C) -> (..., D)."""
    C = x.shape[-1]
    return x[..., (h * D) // C, (h * D) % C:(h * D) % C + D]


def dsa_decode_reference(q, kv, rows, n_live, *, layer,
                         scale: Optional[float] = None):
    """The plain-XLA twin: gather every listed token and mask the list's
    tail. ``q`` (S, H, D); ``kv`` (n_layer, num_blocks, block, R, C);
    ``rows`` (S, K) physical rows, live first; ``n_live`` (S,). Returns
    (S, H, D) float32; a slot with no live row reads 0."""
    S, H, D = q.shape
    _, nb, bs, R, C = kv.shape
    G = R * C // (2 * D)
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    tok = kv[layer].reshape(nb * bs, R * C)[rows]         # (S, K, R * C)
    keys = tok[..., :G * D].reshape(S, -1, G, D)
    vals = tok[..., G * D:].reshape(S, -1, G, D)
    qg = q.reshape(S, G, H // G, D).astype(kv.dtype)
    s = jnp.einsum("sghd,skgd->sghk", qg, keys,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1])[None, :] < n_live[:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).astype(kv.dtype)
    return jnp.einsum("sghk,skgd->sghd", p, vals,
                      preferred_element_type=jnp.float32).reshape(S, H, D)


def _kernel(row_ref, n_ref, lay_ref, q_ref, kv_hbm, o_ref, m_scr, l_scr,
            a_scr, buf_ref, sem, *, tokens_per_step, block_size, groups,
            scale):
    """One slot's program: a loop over its live steps of
    ``tokens_per_step`` listed tokens."""
    s = pl.program_id(0)
    n = n_ref[s]
    lay = lay_ref[0]
    T = tokens_per_step
    H, D = q_ref.shape[1], q_ref.shape[2]
    Hg = H // groups
    steps = (n + T - 1) // T

    def copy(step, buf, t, routed):
        # a dead entry names whatever row it holds: copied, then masked
        r = row_ref[s, step * T + t] if routed else 0
        return pltpu.make_async_copy(
            kv_hbm.at[lay, r // block_size, r % block_size],
            buf_ref.at[buf, t], sem.at[buf])

    def start(step, buf):
        def one(t, c):
            copy(step, buf, t, True).start()
            return c
        jax.lax.fori_loop(0, T, one, 0)

    def wait(buf):
        def one(t, c):
            copy(0, buf, t, False).wait()
            return c
        jax.lax.fori_loop(0, T, one, 0)

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)

    @pl.when(steps > 0)
    def _first():
        start(0, 0)

    col = jax.lax.broadcasted_iota(jnp.int32, (Hg, T), 1)

    def step(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < steps)
        def _next():
            start(i + 1, 1 - buf)

        wait(buf)
        tok = buf_ref[buf]                                 # (T, R, C)
        live = col < n - i * T
        for g in range(groups):
            rows = slice(g * Hg, (g + 1) * Hg)
            keys, vals = _head(tok, g, D), _head(tok, groups + g, D)
            q = q_ref[0, rows, :].astype(tok.dtype)
            s_ = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (Hg, T)
            s_ = jnp.where(live, s_, -jnp.inf)
            # every live step has a live column: m_new is finite
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
            p = jnp.exp(s_ - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[rows, :] = jnp.broadcast_to(
                corr * l_scr[rows, :1] + jnp.sum(p, axis=-1, keepdims=True),
                (Hg, l_scr.shape[1]))
            m_scr[rows, :] = jnp.broadcast_to(m_new, (Hg, m_scr.shape[1]))
            a_scr[rows, :] = a_scr[rows, :] * corr + jax.lax.dot_general(
                p.astype(tok.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    l_end = l_scr[:, :1]
    o_ref[0] = a_scr[...] / jnp.where(l_end == 0.0, 1.0, l_end)


def dsa_paged_decode(q, kv, rows, n_live, *, layer,
                     scale: Optional[float] = None,
                     tokens_per_step: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Single-query attention of every slot over its own list of cached
    tokens, shared by all its query heads.

    ``q``: (S, H, D), the H query heads (K/V head ``g`` serves heads
    ``g * H / G`` onwards); ``kv``: (n_layer, num_blocks, block, R, C),
    a token's K heads then V heads (:func:`kv_row_shape`), with
    ``layer`` the (traced) layer attended; ``rows``: (S, K) int32
    physical rows ``block * block_size + offset``, LIVE ROWS FIRST (a
    dead entry may name any row: it is copied and masked); ``n_live``:
    (S,) int32, how many leading rows are live. Returns (S, H, D)
    float32; a slot with no live row reads 0."""
    S, H, D = q.shape
    _, _, block_size, R, C = kv.shape
    groups = R * C // (2 * D)
    if 2 * groups * D != R * C or H % groups:
        raise ValueError(f"a cached token of {R} x {C} values does not "
                         f"hold K and V heads of {D} for {H} query heads")
    K = rows.shape[-1]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    interpret = _resolve_interpret(interpret)
    T = min(tokens_per_step or TOKENS_PER_STEP, -(-K // 8) * 8)
    pad = -(-K // T) * T - K
    table = jnp.pad(rows.astype(jnp.int32), ((0, 0), (0, pad)))
    return pl.pallas_call(
        functools.partial(_kernel, tokens_per_step=T, block_size=block_size,
                          groups=groups, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, _LANES), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32),
                            pltpu.VMEM((H, D), jnp.float32),
                            pltpu.VMEM((2, T, R, C), kv.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        out_shape=jax.ShapeDtypeStruct((S, H, D), jnp.float32),
        interpret=interpret,
        name="zoo_dsa_decode",
    )(table, n_live.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, kv)
