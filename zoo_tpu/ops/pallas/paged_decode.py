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
  scalar-prefetched block table, so the per-sequence gather copy never
  exists;
* **stacked** — the cache operand is the WHOLE ``(n_layer, num_blocks,
  H_kv, block, D)`` array, left in HBM (``memory_space=pltpu.HBM``), and
  the layer is a third prefetched scalar, the leading index of every
  DMA's source, so a layer loop carries the cache and never slices a
  layer's slab out of it (a 4-D cache is the same path at one layer);
* **several entries a step** — a block of 16 int8 rows of 8 kv heads is
  16 KB, too small a DMA (and too little work) to amortise a step, so a
  step fetches :func:`entries_per_step` consecutive table entries of
  its slot — K, V and under int8 their two scale rows, one DMA each
  into a double-buffered VMEM slab ``(2, N, H_kv, block, D)``, the next
  step's in flight while this one is attended;
* **all kv heads at once** — an entry's block arrives with ALL its kv
  heads, ``H_kv * block`` key rows, and the slot's H query rows meet
  them in ONE ``(H, D) @ (D, H_kv * block)`` product: block-diagonal,
  a mask keeps row ``r`` to the columns of its own kv head
  (``r // group == col // block``) at positions ``<= pos``. The MXU
  does ``H_kv`` times the useful products and is idle anyway; the
  score tile is full vregs, there is no loop over heads, and the
  head-major scale row of an int8 block multiplies the tile as it
  lies (K's on the scores, V's on the probabilities);
* **flash** — one online-softmax update a step for every head, carried
  in VMEM scratch, never a ``(ctx,)`` score row in HBM;
* **time follows the live rows** — a (slot, split) program loops over
  the steps that hold a live position and no further
  (``fori_loop`` to ``ceil((pos + 1) / (N * block))``): a dead step
  costs nothing, a dead split writes ``m=-inf, l=0`` and moves no
  byte. Inside the last live step, entries past the position fetch
  the trash block 0 and the mask hides them;
* **split-KV** — the table's steps are cut into ``num_splits`` grid
  programs that each produce a partial ``(acc, m, l)``; a tiny jnp
  epilogue merges them with the standard log-sum-exp correction.

Mosaic slices an HBM ref in whole 128-lane tiles, so a cache whose rows
are narrower (``head_dim`` 64) or, under int8, whose head-major scale
row is (a tp = 2 shard's 64 lanes, 8 heads of 8 rows) cannot have its
blocks copied out by the kernel: there the SAME step body runs one
entry a grid step, the block brought by the BlockSpec pipeline and
clamped by the index maps (a dead step is a grid step with no DMA and
no product, as before PR 31).

What the interpreter cannot show: it completes a DMA where it starts,
so a read of a slab ahead of its wait passes every CPU test and races
on the chip (V's scale rows did, until they were read behind V's
wait). ``scripts/bench_paged_decode.py`` holds every form's result
against the parent's on the chip; ``chip_smoke.py`` against the dense
reference.

Inactive slots (position 0, table full of trash-block zeros) attend one
step of the trash block and produce garbage that the engine never
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

# the K + V bytes one grid step fetches, at most: 16 int8 entries of
# the serving cell (8 kv heads x 16 rows x 128), 4 of an f32 cache
STEP_BYTES = 512 * 1024
# the most table entries a step attends (each is four DMAs and two
# products in the unrolled body)
MAX_ENTRIES = 16


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


def entries_per_step(table_width: int, n_kv: int, block_size: int,
                     head_dim: int, itemsize: int) -> int:
    """Table entries one grid step fetches and attends: as many as
    ``STEP_BYTES`` of K + V hold, between 1 and ``MAX_ENTRIES``, and no
    more than the table has."""
    entry = 2 * n_kv * block_size * head_dim * itemsize
    return max(1, min(STEP_BYTES // entry, MAX_ENTRIES, table_width))


def _attend(q, pos, firsts, keys, k_scales, values, *, scale, group,
            m_scr, l_scr, a_scr):
    """One online-softmax update of every head for a step's table
    entries. ``q`` (H, D); entry ``e`` starts at cache index
    ``firsts[e]`` and brings ``keys[e]`` (n_kv, block, D) and, for an
    int8 cache, its (1, n_kv * block) head-major scale row
    ``k_scales[e]`` (else the list is None); ``values()`` returns the
    V blocks and their scale rows (or None) and is called after the
    scores: they may arrive behind K and are not to be read before
    their wait. Each block meets all H query rows in
    ONE product against its ``n_kv * block`` rows; the mask keeps a row
    to the columns of its own kv head at positions ``<= pos``. At
    least one entry holds a position ``<= pos``."""
    H = q.shape[0]
    n_kv, block_size, _ = keys[0].shape
    C = n_kv * block_size
    # ``t``: a column's row inside its block, or past every position
    # where the column is another kv head's (the block-diagonal)
    r_head = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (H, C), 0), group)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, C), 1)
    t = jnp.where(r_head == jax.lax.div(col, block_size),
                  jax.lax.rem(col, block_size), jnp.iinfo(jnp.int32).max)

    def rows_of(x):
        # (n_kv, block, D) -> (n_kv * block, D), widened first where the
        # merge would split a packed tile (int8 always: it is dequantized)
        if k_scales is not None or block_size % (32 // x.dtype.itemsize):
            x = x.astype(jnp.float32)
        return x.reshape(C, x.shape[-1])

    tiles = []
    for e, k in enumerate(keys):
        s_ = jax.lax.dot_general(
            q, rows_of(k), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # (H, C)
        if k_scales is not None:
            s_ = s_ * k_scales[e]
        tiles.append(jnp.where(t <= pos - firsts[e], s_, -jnp.inf))
    # a live entry gives every row a live column: m_new is finite
    m_prev = m_scr[...][:, :1]                                    # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(
        functools.reduce(jnp.maximum, tiles), axis=-1, keepdims=True))
    probs = [jnp.exp(s_ - m_new) for s_ in tiles]
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[...][:, :1] + jnp.sum(
        functools.reduce(jnp.add, probs), axis=-1, keepdims=True)
    acc = a_scr[...] * corr
    vals, v_scales = values()
    for e, v in enumerate(vals):
        p, v = probs[e], rows_of(v)
        if v_scales is not None:
            p = p * v_scales[e]
        acc = acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    a_scr[...] = acc
    # full-lane stores: every lane of a row carries the value
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _init(m_scr, l_scr, a_scr):
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    a_scr[...] = jnp.zeros_like(a_scr)


def _finish(acc_ref, m_ref, l_ref, m_scr, l_scr, a_scr):
    acc_ref[0, 0] = a_scr[...]
    m_ref[0, 0] = m_scr[...]
    l_ref[0, 0] = l_scr[...]


def _slab_kernel(bt_ref, pos_ref, lay_ref, q_ref, k_hbm, v_hbm, *rest,
                 n_fetch, steps, quantized, **attend):
    """One (slot, split) program: a loop over the split's LIVE steps of
    ``n_fetch`` table entries, the next step's blocks in flight while
    this one's are attended. ``k_hbm`` / ``v_hbm`` (and under
    ``quantized`` the two scale planes after them) are the whole arrays
    in HBM; the double-buffered slabs they are copied into are scratch
    (``k_buf`` / ``v_buf`` (2, n_fetch, n_kv, block, D), the scale rows
    (2, n_fetch, 1, n_kv * block))."""
    ks_hbm = vs_hbm = ks_buf = vs_buf = None
    if quantized:
        ks_hbm, vs_hbm, *rest = rest
    *outs, m_scr, l_scr, a_scr, k_buf, v_buf = rest[:8]
    if quantized:
        ks_buf, vs_buf = rest[8:10]
    sem = rest[-1]
    scr = (m_scr, l_scr, a_scr)
    s = pl.program_id(0)
    pos = pos_ref[s]
    lay = lay_ref[0]
    block_size = k_buf.shape[3]
    first = pl.program_id(1) * steps
    # the split's steps that hold a position <= pos
    live = jnp.clip(pos // (n_fetch * block_size) + 1 - first, 0, steps)

    def copies(step, buf, routed):
        """The DMAs of one step into slab ``buf``: K's (with their
        scale rows) on one semaphore, V's on the other. ``routed``
        reads the table; a wait only needs the shapes."""
        k_copies, v_copies = [], []
        for e in range(n_fetch):
            blk = 0
            if routed:
                idx = step * n_fetch + e
                # entries past the position: the resident trash block
                blk = jnp.where(idx * block_size <= pos, bt_ref[s, idx], 0)
            for hbm, slab, out, kind in (
                    (k_hbm, k_buf, k_copies, 0), (ks_hbm, ks_buf, k_copies, 0),
                    (v_hbm, v_buf, v_copies, 1), (vs_hbm, vs_buf, v_copies, 1)):
                if hbm is not None:
                    out.append(pltpu.make_async_copy(
                        hbm.at[lay, blk], slab.at[buf, e], sem.at[kind, buf]))
        return k_copies, v_copies

    def start(step, buf):
        k_copies, v_copies = copies(step, buf, True)
        for c in k_copies + v_copies:
            c.start()

    _init(*scr)

    @pl.when(live > 0)
    def _first():
        start(first, 0)

    def step(i, carry):
        buf = jax.lax.rem(i, 2)
        at = first + i

        @pl.when(i + 1 < live)
        def _next():
            start(at + 1, 1 - buf)

        k_copies, v_copies = copies(at, buf, False)
        for c in k_copies:
            c.wait()

        def entries(slab):
            if slab is not None:
                return [slab[buf, e] for e in range(n_fetch)]

        def values():
            for c in v_copies:
                c.wait()
            return entries(v_buf), entries(vs_buf)

        _attend(
            q_ref[0], pos,
            [(at * n_fetch + e) * block_size for e in range(n_fetch)],
            entries(k_buf), entries(ks_buf), values,
            m_scr=m_scr, l_scr=l_scr, a_scr=a_scr, **attend)
        return carry

    jax.lax.fori_loop(0, live, step, 0)
    _finish(*outs, *scr)


def _block_kernel(bt_ref, pos_ref, lay_ref, q_ref, k_ref, v_ref, *rest,
                  steps, quantized, **attend):
    """One (slot, split) program an entry a grid step: the innermost
    grid axis walks the split's ``steps`` table entries, each block
    ``(n_kv, block, D)`` (and its scale rows) brought by the BlockSpec
    pipeline, routed and clamped by the index maps."""
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref, *rest = rest
    *outs, m_scr, l_scr, a_scr = rest
    scr = (m_scr, l_scr, a_scr)
    j = pl.program_id(2)
    pos = pos_ref[pl.program_id(0)]
    first = (pl.program_id(1) * steps + j) * k_ref.shape[2]

    @pl.when(j == 0)
    def _first():
        _init(*scr)

    # a block past the live length: no product, and (the index map
    # clamped it to the resident trash block) no fresh DMA either
    @pl.when(first <= pos)
    def _step():
        _attend(q_ref[0], pos, [first], [k_ref[0]],
                [ks_ref[0]] if quantized else None,
                lambda: ([v_ref[0]], [vs_ref[0]] if quantized else None),
                m_scr=m_scr, l_scr=l_scr, a_scr=a_scr, **attend)

    @pl.when(j == steps - 1)
    def _last():
        _finish(*outs, *scr)


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
    head is one contiguous slab to DMA; a 4-D
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
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    interpret = _resolve_interpret(interpret)
    C = n_kv * block_size
    # Mosaic slices an HBM ref in whole 128-lane tiles: a cache whose
    # rows (or whose head-major scale row: a tp shard's, or 8 heads of
    # 8 rows) are narrower goes an entry a step through the BlockSpec
    # pipeline instead
    sliceable = D % _LANES == 0 and not (quantized and C % _LANES)
    n_fetch = entries_per_step(W, n_kv, block_size, D,
                               k_cache.dtype.itemsize) if sliceable else 1
    groups = -(-W // n_fetch)
    splits = resolve_num_splits(groups, num_splits)
    steps = groups // splits
    # the table padded to whole steps: the padding is dead entries
    bt = jnp.pad(block_tables.astype(jnp.int32),
                 ((0, 0), (0, groups * n_fetch - W)))
    pos = positions.astype(jnp.int32)
    scales = [k_scale, v_scale] if quantized else []

    def q_map(s, sp, *_):
        return s, 0, 0

    def out_map(s, sp, *_):
        return s, sp, 0, 0

    scratch = [pltpu.VMEM((H, _LANES), jnp.float32),
               pltpu.VMEM((H, _LANES), jnp.float32),
               pltpu.VMEM((H, D), jnp.float32)]
    static = dict(steps=steps, quantized=quantized, scale=scale,
                  group=H // n_kv)
    if sliceable:
        # the cache stays in HBM and the kernel copies a step's blocks
        # into its slabs itself; (slot, split) programs share nothing
        kernel = functools.partial(_slab_kernel, n_fetch=n_fetch, **static)
        grid, semantics = (S, splits), ("parallel", "parallel")
        kv_specs = [pl.BlockSpec(memory_space=pltpu.HBM)] * (2 + len(scales))
        slab = (2, n_fetch, n_kv, block_size, D)
        scratch += [pltpu.VMEM(slab, k_cache.dtype),
                    pltpu.VMEM(slab, v_cache.dtype)]
        scratch += [pltpu.VMEM((2, n_fetch, 1, C), jnp.float32)] * len(scales)
        scratch += [pltpu.SemaphoreType.DMA((2, 2))]
    else:
        kernel = functools.partial(_block_kernel, **static)
        # only the innermost walk carries the softmax state
        grid = (S, splits, steps)
        semantics = ("parallel", "parallel", "arbitrary")

        def entry(s, sp, j, bt_ref, pos_ref, lay_ref):
            # dead entries clamp to block 0, so the pipeline re-fetches
            # the resident trash block instead of streaming a block the
            # kernel will skip
            idx = sp * steps + j
            live = idx * block_size <= pos_ref[s]
            return lay_ref[0], jnp.where(live, bt_ref[s, idx], 0)

        kv_specs = [pl.BlockSpec((None, 1, n_kv, block_size, D),
                                 lambda *a: (*entry(*a), 0, 0, 0))] * 2
        kv_specs += [pl.BlockSpec((None, 1, 1, C),
                                  lambda *a: (*entry(*a), 0, 0))] * len(scales)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec((1, H, D), q_map)] + kv_specs,
            out_specs=[pl.BlockSpec((1, 1, H, D), out_map),
                       pl.BlockSpec((1, 1, H, _LANES), out_map),
                       pl.BlockSpec((1, 1, H, _LANES), out_map)],
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        out_shape=[
            jax.ShapeDtypeStruct((S, splits, H, D), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, H, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, H, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="zoo_paged_decode",
    )(bt, pos, lay, q, k_cache, v_cache, *scales)

    # split-KV epilogue: merge the per-split partial softmaxes with the
    # log-sum-exp correction (dead splits carry m=-inf/l=0 and drop out)
    m0 = m[..., 0]                                  # (S, splits, H)
    l0 = l[..., 0]
    m_max = jnp.max(m0, axis=1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m_max), m_max, 0.0)
    alpha = jnp.where(jnp.isfinite(m0), jnp.exp(m0 - m_safe), 0.0)
    l_tot = jnp.sum(alpha * l0, axis=1)             # (S, H)
    o = jnp.sum(alpha[..., None] * acc, axis=1) / \
        jnp.where(l_tot == 0.0, 1.0, l_tot)[..., None]
    return o.astype(q.dtype)
