"""Ring attention: context-parallel attention over the ``seq`` mesh axis.

Net-new subsystem (SURVEY §5.7 — the reference has NO long-context support;
its max sequence length is a plain hyperparameter on dense O(T²) attention).
This module scales sequence length across chips: Q/K/V are sharded over the
``seq`` axis; each device holds one block and K/V blocks rotate around the
ICI ring via ``lax.ppermute`` while a streaming (flash-style) softmax
accumulates — memory O(T/n per device), comm overlapped with compute by XLA.

Math: the standard online-softmax recurrence
    m' = max(m, rowmax(S));  l' = l·e^{m-m'} + rowsum(e^{S-m'})
    o' = o·e^{m-m'} + e^{S-m'}·V
applied once per incoming K/V block; causal masking is by global position
index, with the block origin tracked alongside the rotating K/V.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: Optional[float] = None):
    """Runs INSIDE shard_map. q: (B, Hq, Tl, D); k/v: (B, Hkv, Tl, D)
    with Hq a multiple of Hkv (GQA): the ring carries the UNREPEATED
    kv blocks — the group broadcast happens locally per block, so ICI
    traffic and resident K/V stay O(Hkv), not O(Hq)."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, tl, d = q.shape
    rep = h // k.shape[1]
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5

    q_pos = my_idx * tl + jnp.arange(tl)

    def block(q, k_blk, v_blk, src_idx, m, l, o):
        if rep > 1:  # GQA: local broadcast only
            k_blk = jnp.repeat(k_blk, rep, axis=1)
            v_blk = jnp.repeat(v_blk, rep, axis=1)
        # bf16 matmul operands, f32 scores/statistics: the online-softmax
        # running max/denominator/accumulator stay f32 across ring rounds
        # (same numerics as the dense path's f32 softmax island and the
        # flash kernel's f32 scratch) — bf16 accumulation loses ~1e-2
        # relative mass over long rings
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = src_idx * tl + jnp.arange(tl)
            allowed = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(allowed, s, jnp.finfo(s.dtype).min)
        # s is always finite (masking writes finfo.min, not -inf) and round
        # 0 visits the local block whose causal diagonal is always allowed,
        # so m is finite from round 0 on; exp(-inf - finite) = 0 covers the
        # initial carry.
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        # rotate first, then accumulate: round 0 handles the local block
        # outside the loop, so exactly n-1 rotations happen in total (no
        # wasted final permute whose result would be discarded)
        k_blk, v_blk, src_idx, m, l, o = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        src_idx = jax.lax.ppermute(src_idx, axis_name, perm)
        m, l, o = block(q, k_blk, v_blk, src_idx, m, l, o)
        return k_blk, v_blk, src_idx, m, l, o

    m0 = jnp.full((b, h, tl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tl), jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)
    m, l, o = block(q, k, v, my_idx, m0, l0, o0)
    carry = (k, v, my_idx, m, l, o)
    carry = jax.lax.fori_loop(0, n - 1, body, carry)
    _, _, _, m, l, o = carry
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(mesh: Mesh, q, k, v, *, causal: bool = False,
                   seq_axis: str = "seq"):
    """Context-parallel attention of global (B, H, T, D) arrays sharded on
    the T axis over ``seq_axis``. Returns output with q's sharding.
    ``k``/``v`` may carry fewer (grouped/GQA) heads than ``q`` — the ring
    rotates the small kv blocks and broadcasts per group locally.

    The reference equivalent does not exist; use this wherever a
    transformer's sequence no longer fits one chip.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"q heads ({q.shape[1]}) must be a multiple of "
                         f"kv heads ({k.shape[1]})")
    spec = P(None, None, seq_axis, None)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
