"""The ``minicpm_sala`` decoder (MiniCPM-SALA): block-sparse attention
layers among Lightning linear-attention layers.

The published architecture (``config.json`` of ``openbmb/MiniCPM-SALA``),
in the repo's names:

* ``mixer_types`` names every layer's mixer. A ``minicpm4`` layer is
  grouped-query softmax attention (``n_head`` queries over ``n_kv_head``
  K/V heads of ``head_dim``, RMSNorm on every q and k head, NO rope)
  that, from ``dense_len`` resident tokens on, attends only the ``topk``
  blocks of ``sparse_block`` tokens that a selection picks per K/V
  head: scores of the query against *compressed keys* (the mean of
  ``kernel_size`` keys every ``kernel_stride``), summed over the group's
  heads, pooled to blocks by the largest overlapping window, the first
  ``init_blocks`` and the blocks of the last ``window_size`` tokens
  always among them (InfLLM-V2). A ``lightning-attn`` layer is linear
  attention with a per-head decay: ``S_t = exp(-slope_h) S_{t-1} + k_t^T
  v_t``, ``o_t = d^-0.5 q_t S_t`` (RMSNorm and rope on q and k), an
  RMSNorm on every output head. Both end in a sigmoid output gate of the
  layer's input and the output projection; every layer has a dense
  SwiGLU feed-forward.
* muP scalings: the embedding times ``scale_emb``, every residual
  branch times ``scale_depth / sqrt(depth)`` with ``depth`` the PUBLISHED
  number of layers (a cut of the depth keeps it), the logits divided by
  ``hidden / dim_model_base``.
* The selection's sizes are not in the published file; they are the
  family's (MiniCPM4's ``sparse_config``). The decay slopes are a leaf
  of the weight tree (``slopes``, (Lightning layer, head)), filled here
  with the Lightning Attention code's ALiBi schedule.

This module is the configuration and the weight tree; the serving model
is ``serving/llm/model_sala.py::PagedMiniCpmSalaModel``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"
# the q_norm gain of a sparse layer in init_minicpm_sala_params
SPARSE_Q_GAIN = 3.0

# the layer leaves that are only ever dot operands
SALA_DOT_LEAVES = ("wq", "wk", "wv", "w_g", "wo",
                   "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class MiniCpmSalaConfig:
    vocab: int = 73448
    hidden: int = 4096
    mixer_types: Tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 3
    depth: int = 32                  # the published depth (muP residual)
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    intermediate: int = 16384
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    kernel_size: int = 32
    kernel_stride: int = 16
    sparse_block: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    tie_embeddings: bool = False

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if bad or not self.mixer_types:
            raise ValueError(f"unknown mixer types {sorted(bad)} "
                             f"({SPARSE} / {LIGHTNING})")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if self.sparse_block % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError("a selection block and a window are whole "
                             "strides")

    @property
    def n_block(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse(self) -> int:
        return sum(1 for m in self.mixer_types if m == SPARSE)

    @property
    def n_lightning(self) -> int:
        return self.n_block - self.n_sparse

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / float(self.depth) ** 0.5

    @property
    def logit_divisor(self) -> float:
        return self.hidden / float(self.dim_model_base)

    @classmethod
    def from_published(cls, cfg: dict) -> "MiniCpmSalaConfig":
        """From the keys of the published ``config.json`` plus the
        family's ``sparse_config`` (and ``published.num_hidden_layers``
        where the depth was cut)."""
        if cfg.get("attn_use_rope") or not cfg.get("lightning_use_rope",
                                                   True):
            raise ValueError("rope on the sparse layers / none on the "
                             "Lightning layers is not built")
        if cfg.get("lightning_nkv") != cfg.get("lightning_nh"):
            raise ValueError("grouped Lightning heads are not built "
                             "(lightning_nkv must equal lightning_nh)")
        for k in ("qk_norm", "use_output_gate", "use_output_norm",
                  "attn_use_output_gate"):
            if not cfg.get(k, True):
                raise ValueError(f"{k} false is not built")
        sp = cfg["sparse_config"]
        return cls(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            mixer_types=tuple(cfg["mixer_types"]),
            depth=cfg.get("published", {}).get(
                "num_hidden_layers", cfg["num_hidden_layers"]),
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            lightning_heads=cfg["lightning_nh"],
            lightning_head_dim=cfg["lightning_head_dim"],
            intermediate=cfg["intermediate_size"],
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            scale_emb=float(cfg["scale_emb"]),
            scale_depth=float(cfg["scale_depth"]),
            dim_model_base=cfg["dim_model_base"],
            kernel_size=sp["kernel_size"],
            kernel_stride=sp["kernel_stride"],
            sparse_block=sp["block_size"], init_blocks=sp["init_blocks"],
            window_size=sp["window_size"], topk=sp["topk"],
            dense_len=sp["dense_len"],
            tie_embeddings=bool(cfg["tie_word_embeddings"]))


def tiny_minicpm_sala_config(vocab: int = 256) -> MiniCpmSalaConfig:
    """Test/dryrun config: the same topology at toy widths, the
    selection's sizes scaled so that it really cuts (under 32
    tokens of context a query is dense; from there it attends 6 blocks
    of 8, 3 or 4 of them forced)."""
    return MiniCpmSalaConfig(
        vocab=vocab, hidden=64,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE), depth=4,
        n_head=4, n_kv_head=2, head_dim=16, lightning_heads=4,
        lightning_head_dim=16, intermediate=128, dim_model_base=32,
        kernel_size=4, kernel_stride=2, sparse_block=8, init_blocks=1,
        window_size=16, topk=6, dense_len=32)


def lightning_slopes(heads: int) -> jnp.ndarray:
    """The Lightning Attention code's ALiBi slopes ``2^(-8 (h+1) /
    heads)``: what fills the ``slopes`` leaf."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                   / heads)


def minicpm_sala_leaf_shapes(cfg: MiniCpmSalaConfig, kind: str) -> dict:
    """name → shape of one layer's matrices (fan-in first)."""
    h = cfg.hidden
    if kind == SPARSE:
        nq, nkv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    else:
        nq = nkv = cfg.lightning_heads * cfg.lightning_head_dim
    return {"wq": (h, nq), "wk": (h, nkv), "wv": (h, nkv), "w_g": (h, nq),
            "wo": (nq, h), "w_gate": (h, cfg.intermediate),
            "w_up": (h, cfg.intermediate), "w_down": (cfg.intermediate, h)}


def init_minicpm_sala_params(cfg: MiniCpmSalaConfig, rng,
                             dtype=jnp.float32) -> dict:
    """Deterministic weights: normal, std ``fan_in^-0.5``; the embedding's
    std is ``1 / scale_emb`` (the scaled embedding has unit components,
    so the layers and not the last token's embedding make the logits);
    gains 1, but a sparse layer's ``q_norm`` is :data:`SPARSE_Q_GAIN`
    (random q and k at gain 1 attend their selected tokens nearly
    uniformly and the mixer's output is the mean of as many random v
    rows, a few percent of a Lightning layer's; a trained attention is
    peaked).
    ``blocks`` is the list of the layers in ``mixer_types``' order, a
    dict of leaves each (the serving model unrolls its layers: they are
    of two kinds); ``slopes`` (Lightning layer, head). Gains and slopes
    are float32 whatever ``dtype`` is."""
    h = cfg.hidden

    def layer(key, kind):
        d = cfg.head_dim if kind == SPARSE else cfg.lightning_head_dim
        p = {}
        for i, (name, shape) in enumerate(sorted(
                minicpm_sala_leaf_shapes(cfg, kind).items())):
            p[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                       * shape[0] ** -0.5).astype(dtype)
        p.update(attn_norm=jnp.ones((h,), jnp.float32),
                 mlp_norm=jnp.ones((h,), jnp.float32),
                 q_norm=jnp.full(
                     (d,), SPARSE_Q_GAIN if kind == SPARSE else 1.0,
                     jnp.float32),
                 k_norm=jnp.ones((d,), jnp.float32))
        if kind == LIGHTNING:
            p["o_norm"] = jnp.ones((d,), jnp.float32)
        return p

    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    return {"embed": (jax.random.normal(k_embed, (cfg.vocab, h), jnp.float32)
                      / cfg.scale_emb).astype(dtype),
            "blocks": [layer(jax.random.fold_in(k_layers, i), kind)
                       for i, kind in enumerate(cfg.mixer_types)],
            "slopes": jnp.tile(lightning_slopes(cfg.lightning_heads)[None],
                               (cfg.n_lightning, 1)),
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": (jax.random.normal(k_head, (h, cfg.vocab), jnp.float32)
                     * h ** -0.5).astype(dtype)}
