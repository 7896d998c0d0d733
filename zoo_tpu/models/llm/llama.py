"""Llama-family decoder-only LM (BASELINE.md stretch row: "Llama-3-8B …
FSDP-style shard over ICI").

Net-new vs the reference (its largest attention model is BERT,
``BERT.scala:402``): a modern decoder stack — RMSNorm pre-norm, rotary
position embeddings, grouped-query attention, SwiGLU MLP, no biases —
built in the same mega-layer idiom as ``TransformerLayer``
(``self_attention.py``): one Layer owning stacked per-block params run
under ``lax.scan``, so compile time is O(1) in depth and the (n_block,
d_in, d_out) weight stacking gives ``parallel.plans.leaf_sharding`` its
natural FSDP/TP axes (fsdp shards the block axis or the largest matmul
dim; model shards the matmul output dim — Megatron column style).

Attention rides ``ops.attention.dot_product_attention`` — the Pallas
flash kernel at long sequence, the XLA-fused dense path otherwise — or,
with ``attention_impl="ring"``, the sequence-parallel ring kernel over
the mesh ``seq`` axis (``parallel/ring_attention.py``), which carries
the unrepeated GQA kv heads around the ICI ring.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from zoo_tpu.ops.attention import dot_product_attention
from zoo_tpu.pipeline.api.keras.engine.base import Layer, get_initializer


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 32000
    hidden: int = 4096
    n_block: int = 32
    n_head: int = 32
    n_kv_head: int = 8
    intermediate: int = 14336
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_head


def llama3_8b_config() -> LlamaConfig:
    """Llama-3-8B shapes (public architecture card)."""
    return LlamaConfig(vocab=128256, hidden=4096, n_block=32, n_head=32,
                       n_kv_head=8, intermediate=14336,
                       rope_theta=500000.0)


def tiny_llama_config(vocab: int = 256) -> LlamaConfig:
    """Test/dryrun config: same topology, toy widths."""
    return LlamaConfig(vocab=vocab, hidden=64, n_block=2, n_head=4,
                       n_kv_head=2, intermediate=128, rope_theta=10000.0)


def llama_param_count(cfg: LlamaConfig) -> int:
    """Analytic parameter count (embed + blocks + final norm + lm head)."""
    h, kv = cfg.hidden, cfg.n_kv_head * cfg.head_dim
    per_block = (h * h + 2 * h * kv + h * h      # q, k, v, o
                 + 3 * h * cfg.intermediate      # w1 (gate), w3 (up), w2
                 + 2 * h)                        # two RMSNorm gains
    total = cfg.vocab * h + cfg.n_block * per_block + h
    if not cfg.tie_embeddings:
        total += cfg.vocab * h
    return total


def resolve_attention_impl(impl: str, seq_len: int) -> str:  # zoo-lint: config-parse
    """Concrete kernel for an ``attention_impl`` request at ``seq_len``.

    ``"auto"`` picks the Pallas flash kernel from
    ``ZOO_LLAMA_FLASH_MIN_SEQ`` tokens up (default 512 — the measured
    v5e crossover vs the fused dense path) when jax's backend is the
    TPU (``pallas.on_tpu()``), else the dense path. An ``auto`` that
    quietly stays dense costs long sequences dearly (the round-5
    record read 0.44 → 0.35 MFU from S=512 to S=4096 that way), so the
    choice is resolved here, by sequence length, and whoever measures
    records what it landed on. ``"dense"``/``"flash"``/``"ring"`` pass through
    untouched; ``ZOO_LLAMA_ATTN_IMPL`` force-overrides auto for A/B
    runs without a code change."""
    import os
    if impl != "auto":
        return impl
    forced = os.environ.get("ZOO_LLAMA_ATTN_IMPL", "")
    if forced:
        return forced
    from zoo_tpu.ops.pallas import on_tpu
    min_seq = int(os.environ.get("ZOO_LLAMA_FLASH_MIN_SEQ", "512"))
    return "flash" if seq_len >= min_seq and on_tpu() else "dense"


def _rms_norm(x, gain, eps):
    # f32 island for the moment/rsqrt only; the normalized tensor drops
    # to the compute dtype BEFORE the gain multiply, so autodiff saves a
    # bf16 residual — keeping the f32 product alive across the backward
    # pass was measured to carry 100MB/block of f32 through the scan
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    norm = (xf * inv).astype(x.dtype)
    return norm * gain.astype(x.dtype)


def rope_frequencies(head_dim: int, seq_len: int, theta: float):
    """(T, D/2) cos/sin tables, f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, inv)  # (T, D/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """Rotate (B, H, T, D) by per-position angles (HF rotate-half
    convention)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[None, None, :, :].astype(x.dtype)
    sin = sin[None, None, :, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


class Llama(Layer):
    """Decoder-only Llama LM as one mega-layer: int ids (B, T) →
    logits (B, T, vocab) (``lm_head=True``, default) or hidden states
    (B, T, hidden)."""

    def __init__(self, config: Optional[LlamaConfig] = None,
                 lm_head: bool = True, init="glorot_uniform",
                 attention_impl: str = "auto", remat: bool = False,
                 mesh=None, **kwargs):
        """``remat`` controls the per-block ``jax.checkpoint`` policy:

        * ``False`` — store all block activations (fastest when they fit);
        * ``True`` — full remat: backward recomputes the whole block, so
          a train step costs ~4x forward FLOPs instead of ~3x (a hard
          0.75x MFU ceiling) for O(1) activation memory in depth;
        * ``"dots"`` — save matmul/attention outputs, recompute only the
          cheap elementwise chains (``dots_with_no_batch_dims_saveable``):
          nearly the memory relief of full remat with none of the MXU
          recompute — the right default for training configs that
          otherwise OOM. Measured on v5e (768-hidden, S=512, B=64):
          full remat 0.32 MFU, "dots" 0.42, no-remat OOM.

        ``attention_impl="ring"``: sequence-parallel ring attention over
        the mesh ``seq`` axis (``parallel/ring_attention.py``) — shard
        the token axis of the inputs over ``seq`` and context length
        scales with the number of chips. Needs a mesh with a ``seq``
        axis: pass ``mesh=`` or set one via
        ``init_orca_context(mesh_axes={..., "seq": k})``. GQA note: the
        ring kernel wants equal q/kv heads, so kv heads are broadcast
        before the ring (same math as the dense path)."""
        super().__init__(**kwargs)
        self.cfg = config or LlamaConfig()
        if self.cfg.hidden % self.cfg.n_head:
            raise ValueError("hidden must divide by n_head")
        if self.cfg.n_head % self.cfg.n_kv_head:
            raise ValueError("n_head must divide by n_kv_head")
        self.lm_head = lm_head
        self.init = get_initializer(init)
        self.attention_impl = attention_impl
        if remat not in (False, True, "dots"):
            # any other truthy value would silently fall through to full
            # -block remat, quietly costing ~0.1 MFU vs "dots"
            raise ValueError(
                f"remat must be False, True or 'dots', got {remat!r}")
        self.remat = remat
        self.mesh = mesh

    def _seq_mesh(self):
        mesh = self.mesh
        if mesh is None:
            from zoo_tpu.common.context import get_runtime_context
            ctx = get_runtime_context(required=False)
            mesh = getattr(ctx, "mesh", None) if ctx else None
        # explicit meshes get the same validation as ambient ones: a
        # missing/size-1 seq axis must fail HERE, not as a cryptic
        # unresolved-axis error inside shard_map
        if mesh is None or "seq" not in mesh.axis_names \
                or mesh.shape.get("seq", 1) <= 1:
            raise ValueError(
                'attention_impl="ring" needs a mesh with a seq axis > 1; '
                "pass mesh= or init_orca_context(mesh_axes={'seq': k})")
        return mesh

    # -- params -----------------------------------------------------------
    def _mlp_block_params(self, k_gate, k_up):
        """The MLP half's weights — a separate hook so MoE variants can
        swap in expert banks without materializing (and discarding) the
        dense SwiGLU weights. Key derivation unchanged from round 2 so
        existing checkpoints keep their values."""
        c = self.cfg
        return {
            "w_gate": self.init(k_gate, (c.hidden, c.intermediate),
                                jnp.float32),
            "w_up": self.init(k_up, (c.hidden, c.intermediate),
                              jnp.float32),
            "w_down": self.init(
                jax.random.fold_in(k_up, 1), (c.intermediate, c.hidden),
                jnp.float32),
        }

    def _block_params(self, rng):
        c = self.cfg
        kv = c.n_kv_head * c.head_dim
        ks = jax.random.split(rng, 6)
        p = {
            "wq": self.init(ks[0], (c.hidden, c.hidden), jnp.float32),
            "wk": self.init(ks[1], (c.hidden, kv), jnp.float32),
            "wv": self.init(ks[2], (c.hidden, kv), jnp.float32),
            "wo": self.init(ks[3], (c.hidden, c.hidden), jnp.float32),
            "attn_norm": jnp.ones((c.hidden,), jnp.float32),
            "mlp_norm": jnp.ones((c.hidden,), jnp.float32),
        }
        p.update(self._mlp_block_params(ks[4], ks[5]))
        return p

    def build(self, rng, input_shape):
        c = self.cfg
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        blocks = jax.vmap(self._block_params)(
            jax.random.split(k_blocks, c.n_block))
        params = {
            "embed": self.init(k_embed, (c.vocab, c.hidden), jnp.float32)
            * 0.02 * (3.0 ** 0.5),  # small-embed init, LM convention
            "blocks": blocks,
            "final_norm": jnp.ones((c.hidden,), jnp.float32),
        }
        if self.lm_head and not c.tie_embeddings:
            params["head"] = self.init(k_head, (c.hidden, c.vocab),
                                       jnp.float32)
        return params

    # -- forward ----------------------------------------------------------
    def _attn_part(self, p, h, cos, sin):
        c = self.cfg
        B, T, _ = h.shape
        x = _rms_norm(h, p["attn_norm"], c.rms_eps)
        q = (x @ p["wq"]).reshape(B, T, c.n_head, c.head_dim)
        k = (x @ p["wk"]).reshape(B, T, c.n_kv_head, c.head_dim)
        v = (x @ p["wv"]).reshape(B, T, c.n_kv_head, c.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
        v = v.transpose(0, 2, 1, 3)
        impl = resolve_attention_impl(self.attention_impl, T)
        # trace-time record (T is static): bench rows and tests read the
        # concrete kernel the auto mode landed on for this shape
        self.last_attention_impl = impl
        if impl == "ring":
            # GQA-aware kernel: the ring carries the unrepeated kv heads
            from zoo_tpu.parallel.ring_attention import ring_attention
            a = ring_attention(self._seq_mesh(), q, k, v, causal=True)
        else:
            # GQA passes the unrepeated kv heads straight through: the
            # flash kernel maps query heads onto their group's kv head
            # in its index maps, the dense path broadcasts internally
            a = dot_product_attention(q, k, v, causal=True, impl=impl)
        a = a.transpose(0, 2, 1, 3).reshape(B, T, c.hidden)
        return h + a @ p["wo"]

    def _mlp_part(self, p, h):
        c = self.cfg
        x = _rms_norm(h, p["mlp_norm"], c.rms_eps)
        f = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
        return h + f

    def _block(self, p, h, cos, sin):
        return self._mlp_part(p, self._attn_part(p, h, cos, sin))

    def call(self, params, inputs, *, training=False, rng=None):
        c = self.cfg
        ids = inputs.astype(jnp.int32)
        h = jnp.take(params["embed"], ids, axis=0)
        cos, sin = rope_frequencies(c.head_dim, ids.shape[1], c.rope_theta)

        # prevent_cse=False: lax.scan already prevents CSE; the default
        # barriers would block fusions in every block iteration
        if self.remat == "dots":
            # Checkpoint ONLY the MLP half under the dots policy. The
            # attention half stays un-rematted: a whole-block remat
            # cannot reach the residuals inside the flash kernel's
            # custom_vjp, so it re-runs the attention forward per block
            # in the backward pass (~7% of step time at S=512); leaving
            # the half un-checkpointed lets autodiff keep exactly the
            # kernel residuals (q, k, v, o, lse) instead
            mlp_fn = jax.checkpoint(
                self._mlp_part, prevent_cse=False,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)

            def block_fn(p, h, cos, sin):
                return mlp_fn(p, self._attn_part(p, h, cos, sin))
        elif self.remat:
            block_fn = jax.checkpoint(self._block, prevent_cse=False)
        else:
            block_fn = self._block

        def body(carry, blk):
            return block_fn(blk, carry, cos, sin), None

        h, _ = jax.lax.scan(body, h, params["blocks"])
        h = _rms_norm(h, params["final_norm"], c.rms_eps)
        if not self.lm_head:
            return h
        head = (params["embed"].T if c.tie_embeddings
                else params["head"])
        return h @ head.astype(h.dtype)

    def compute_output_shape(self, input_shape):
        b, t = input_shape
        return (b, t, self.cfg.vocab if self.lm_head else self.cfg.hidden)
