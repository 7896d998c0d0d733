"""Attention kernels.

The reference's only attention is the dense O(T^2) math inside
``TransformerLayer.scala:279`` / ``BERT.scala:402`` (no flash attention, no
context parallelism — SURVEY §5.7). Here the dense path is written so XLA
fuses softmax into the matmuls; the ring/context-parallel variant lives in
``zoo_tpu.parallel.ring_attention`` and shares this per-block math.

Layout: (batch, heads, seq, head_dim) throughout — heads-second is the
TPU-friendly layout (seq × head_dim trailing = MXU tiles).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _flash_attend(q, k, v, causal, scale, mesh):
    """The Pallas flash kernel, placed on the mesh by hand when it has
    to be. A Mosaic kernel has no partitioning rule — under a
    multi-device jit GSPMD refuses it ("Mosaic kernels cannot be
    automatically partitioned") — so every device runs the kernel on
    its own batch rows and heads under ``shard_map``: attention mixes
    neither, so no collective is needed. ``mesh=None`` means the
    runtime context's mesh, and that one is only consulted when the
    kernel really is compiled by Mosaic; interpreted (the CPU rig) it
    is plain HLO that GSPMD partitions by itself."""
    from functools import partial

    from zoo_tpu.ops.pallas import flash_attention, resolve_interpret
    attend = partial(flash_attention, causal=causal, scale=scale)
    if mesh is None and not resolve_interpret(None):
        from zoo_tpu.common.context import get_runtime_context
        ctx = get_runtime_context(required=False)
        mesh = ctx.mesh if ctx is not None else None
    if mesh is None or mesh.size == 1:
        return attend(q, k, v)
    from jax.sharding import PartitionSpec as P

    from zoo_tpu.parallel.mesh import data_axes
    rows = data_axes(mesh)
    heads = "model" if mesh.shape.get("model", 1) > 1 else None
    used = set(rows) | ({heads} - {None})
    if any(size > 1 for axis, size in mesh.shape.items()
           if axis not in used):
        # seq / pipe / expert layouts own their attention elsewhere
        # (ring attention, the stage worker's own shard_map)
        return attend(q, k, v)
    spec = P(rows or None, heads, None, None)
    return jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          causal: bool = False,
                          dropout_p: float = 0.0,
                          dropout_rng=None,
                          scale: Optional[float] = None,
                          impl: str = "auto",
                          mesh=None) -> jnp.ndarray:
    """Scaled dot-product attention over (B, H, T, D) tensors.

    ``mask``: optional (B, 1, 1, T) or (B, 1, T, T) additive-style boolean
    mask (True = attend). ``causal`` adds the autoregressive triangle (the
    reference's ``bidirectional=False`` TransformerLayer mode).

    ``impl``: "dense" (XLA-fused O(T^2) math), "flash" (the Pallas
    blockwise kernel, zoo_tpu.ops.pallas.flash_attention), or "auto" —
    flash on TPU when it applies (no arbitrary mask, no dropout),
    dense otherwise.

    GQA: ``k``/``v`` may carry fewer heads than ``q`` (``H_q % H_kv ==
    0``). The flash kernel consumes the unrepeated kv heads natively;
    the dense path broadcasts the groups here.

    ``mesh``: the mesh the operands are sharded over, when the caller
    has one of its own (tensor-parallel serving); default the runtime
    context's. Only the flash path needs it (:func:`_flash_attend`).
    """
    flash_ok = mask is None and dropout_p == 0.0
    if impl == "auto":
        # ONE owner for the flash-vs-dense policy (threshold, TPU
        # probe, env overrides): resolve_attention_impl. flash from
        # S>=512 up — with 512x512 blocks the kernel beats the dense
        # path there (measured v5e, B=64 H=12 D=64: fwd 3.3 vs 4.9 ms)
        # and it avoids materializing the f32 T^2 scores that dominate
        # the dense path's HBM traffic; at shorter seq the fused dense
        # path is faster (BERT-base S=128 dense 1.4x flash on v5e).
        # Lazy import: llama.py imports this module at load time.
        from zoo_tpu.models.llm.llama import resolve_attention_impl
        impl = resolve_attention_impl("auto", q.shape[-2]) \
            if flash_ok else "dense"
    if impl == "flash":
        if not flash_ok:
            raise ValueError("flash attention supports causal masking only "
                             "(no arbitrary mask / dropout); use the dense "
                             "impl for those")
        return _flash_attend(q, k, v, causal, scale, mesh)
    if k.shape[1] != q.shape[1]:  # GQA on the dense path: broadcast
        if q.shape[1] % k.shape[1]:
            raise ValueError(f"q heads ({q.shape[1]}) must be a multiple "
                             f"of kv heads ({k.shape[1]})")
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    # QK^T rides the MXU in the input dtype; the softmax runs in an f32
    # island (bf16 exp/normalize loses attention mass), then drops back
    # for the PV matmul
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale

    neg = jnp.finfo(jnp.float32).min
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        tri = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(tri, scores, neg)
    if mask is not None:
        scores = jnp.where(mask, scores, neg)

    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if dropout_p > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_p,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def split_heads(x: jnp.ndarray, n_head: int) -> jnp.ndarray:
    """(B, T, H*D) -> (B, H, T, D)."""
    b, t, hd = x.shape
    return x.reshape(b, t, n_head, hd // n_head).transpose(0, 2, 1, 3)


def merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(B, H, T, D) -> (B, T, H*D)."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)
