"""Grouped matrix product over sorted rows: the experts' matmuls of a
dropless mixture-of-experts layer (``zoo_moe_gmm``).

``lhs`` (m, k) holds the (token, choice) rows of a layer SORTED by
expert, ``group_sizes`` (g,) how many rows each expert has, ``rhs``
(g, k, n) the experts' weights; row ``r`` of the result is ``lhs[r] @
rhs[expert of r]``. The work is laid out as in the public MegaBlocks /
megablox formulation (Gale et al., MLSys '23): the rows are cut into
tiles of ``tm``, and the grid walks the (row tile, expert) PAIRS that
share rows — at most ``m/tm + g - 1`` of them, found from
``group_sizes`` on the device and handed to the index maps as
prefetched scalars — so

* an expert with no rows is never visited and its weights are never
  read (a decode tick of 32 lanes reads the ~56 of 64 experts its
  tokens chose, nothing else);
* an expert's ``(k, tn)`` weight panel is read once per row tile it
  touches, straight from where the layer's leaf lies;
* a row tile that spans several experts is visited once per expert and
  each visit writes only its own rows (the output tile stays resident
  between consecutive visits);
* the shapes are fixed: one executable for any routing, all rows on one
  expert included.

``jax.lax.ragged_dot`` computes the same function (the CPU path and the
check of this kernel); on a TPU XLA lowers it to a kernel of its own
whose operations lose the caller's ``jax.named_scope``, which is why the
serving step calls this one: its time lands under ``zoo.moe_experts``
in the device trace.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret

TM = 128          # rows a tile
TN = 512          # output columns a step
TK_MAX = 2048     # contraction a step (whole, up to this)


def group_tiles(group_sizes: jnp.ndarray, m: int, tm: int):
    """The (row tile, group) pairs that share rows, in row order.
    Returns ``(starts, ends, group_ids, tile_ids, n_pairs)``: each
    group's row range, and for every grid slot ``t`` below the static
    bound ``m/tm + g - 1`` the pair it works on; slots at or past
    ``n_pairs`` repeat the last pair (no new block is fetched for them
    and the kernel skips them)."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    n_pairs = jnp.sum(tiles)
    bound = m // tm + g - 1
    before = jnp.cumsum(tiles) - tiles            # pairs before a group
    slot = jnp.minimum(jnp.arange(bound, dtype=jnp.int32),
                       jnp.maximum(n_pairs - 1, 0))
    # the group of slot t: the last group whose pairs start at or
    # before t (groups without rows add no pair and are passed over)
    group_ids = (jnp.searchsorted(before + tiles, slot, side="right")
                 ).astype(jnp.int32)
    group_ids = jnp.minimum(group_ids, g - 1)
    tile_ids = (jnp.take(first, group_ids)
                + slot - jnp.take(before, group_ids)).astype(jnp.int32)
    return (starts.astype(jnp.int32), ends.astype(jnp.int32), group_ids,
            jnp.clip(tile_ids, 0, m // tm - 1), n_pairs.reshape(1))


def _kernel(starts_ref, ends_ref, gid_ref, tid_ref, n_ref, lhs_ref,
            rhs_ref, out_ref, acc_ref, *, tm, k_steps):
    t = pl.program_id(1)
    kk = pl.program_id(2)
    live = t < n_ref[0]

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mul():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, kk == k_steps - 1))
    def _store():
        g = gid_ref[t]
        tile = tid_ref[t]
        rows = tile * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = jnp.logical_and(rows >= starts_ref[g], rows < ends_ref[g])
        # the tile's first visit finds nothing of value in the output
        # block; a later one keeps what the groups before it wrote
        first = jnp.logical_or(t == 0,
                               tid_ref[jnp.maximum(t - 1, 0)] != tile)
        kept = jnp.where(first, jnp.zeros_like(acc_ref), out_ref[...])
        out_ref[...] = jnp.where(mine, acc_ref[...], kept)


def moe_gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
            *, tiling: Optional[Tuple[int, int, int]] = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """``out[r] = lhs[r] @ rhs[group of r]`` for rows sorted by group.

    ``lhs`` (m, k) and ``rhs`` (g, k, n) in one dtype (bf16 on the
    chip); ``group_sizes`` (g,) int32 summing to ``m``. Returns (m, n)
    float32. ``m`` is padded to whole row tiles here; ``k`` and ``n``
    must be whole multiples of the contraction and column steps (every
    width of a served model is)."""
    m, k = lhs.shape
    g, k2, n = rhs.shape
    if k != k2:
        raise ValueError(f"lhs contracts {k}, rhs {k2}")
    tm, tk, tn = tiling or (TM, min(k, TK_MAX), min(n, TN))
    if k % tk or n % tn:
        raise ValueError(f"k={k} / n={n} are not whole steps of "
                         f"{tk} / {tn}")
    interpret = _resolve_interpret(interpret)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    meta = group_tiles(group_sizes, m_pad, tm)
    bound = meta[2].shape[0]
    k_steps = k // tk

    def lhs_map(j, t, kk, starts, ends, gid, tid, n_pairs):
        return tid[t], kk

    def rhs_map(j, t, kk, starts, ends, gid, tid, n_pairs):
        return gid[t], kk, j

    def out_map(j, t, kk, starts, ends, gid, tid, n_pairs):
        return tid[t], j

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_steps=k_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, bound, k_steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((1, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        interpret=interpret,
        name="zoo_moe_gmm",
    )(*meta, lhs, rhs)
    return out[:m] if m_pad != m else out
