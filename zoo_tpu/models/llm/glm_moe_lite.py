"""The ``glm4_moe_lite`` decoder (GLM-4.7-Flash): latent attention and a
dropless mixture of experts.

The published architecture (``config.json`` of
``zai-org/GLM-4.7-Flash``), in the repo's names:

* **Latent attention (MLA).** Queries go through a low-rank pair
  (``w_qa`` → RMSNorm → ``w_qb``) to ``n_head`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim``; keys and values come from ONE
  latent row a token, ``[c_kv ‖ k_rope]`` of ``kv_lora_rank +
  qk_rope_head_dim`` values (``w_kva``, RMSNorm on ``c_kv``, rope on the
  one shared ``k_rope`` head), which is all the cache holds. ``w_kvb``
  is kept split by head as ``w_uk`` (H, rank, nope) and ``w_uv``
  (H, rank, v): the *expanded* form re-makes K and V from the latent
  rows, the *absorbed* form folds ``w_uk`` into the query and ``w_uv``
  into the output and attends the latent rows themselves (the same
  function; ``serving/llm/model_mla.py`` runs one for decode and
  measures both for prefill).
* **Feed-forward.** The first ``first_k_dense`` layers are dense SwiGLU
  (``intermediate``); the rest route every token to
  ``num_experts_per_tok`` of ``n_routed_experts`` SwiGLU experts
  (``moe_intermediate`` wide) by sigmoid scores with a selection bias
  (``topk_method: noaux_tc``), weights renormalised and scaled by
  ``routed_scaling_factor``, plus ``n_shared_experts`` shared experts
  every token passes (``ops/moe.py::moe_ffn_dropless``).
* The multi-token-prediction layer (``num_nextn_predict_layers``) is
  **not built**: the published forward pass leaves it out too, and the
  engine drafts by n-gram only (``serving/llm/speculative.py``).

This module is the configuration and the weight tree; the serving model
is ``serving/llm/model_mla.py::PagedGlmMoeLiteModel``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

# the block leaves that are only ever dot operands (the router is not
# among them: it is multiplied in float32)
GLM_DOT_LEAVES = ("w_qa", "w_qb", "w_kva", "w_uk", "w_uv", "wo",
                  "w_gate", "w_up", "w_down",
                  "ws_gate", "ws_up", "ws_down")


@dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab: int = 154880
    hidden: int = 2048
    n_block: int = 47
    n_head: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate: int = 10240
    moe_intermediate: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of the one cache row a token costs in a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def from_published(cls, cfg: dict) -> "GlmMoeLiteConfig":
        """From the keys of the published ``config.json``."""
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing is not built "
                             "(n_group / topk_group must be 1)")
        if cfg.get("rope_scaling") is not None \
                or cfg.get("partial_rotary_factor", 1) != 1:
            raise ValueError("rope scaling / partial rotary is not built")
        return cls(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            n_block=cfg["num_hidden_layers"],
            n_head=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            intermediate=cfg["intermediate_size"],
            moe_intermediate=cfg["moe_intermediate_size"],
            n_routed_experts=cfg["n_routed_experts"],
            n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            first_k_dense=cfg["first_k_dense_replace"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            tie_embeddings=bool(cfg["tie_word_embeddings"]))


def tiny_glm_moe_lite_config(vocab: int = 256) -> GlmMoeLiteConfig:
    """Test/dryrun config: same topology, toy widths."""
    return GlmMoeLiteConfig(
        vocab=vocab, hidden=64, n_block=3, n_head=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate=128, moe_intermediate=32,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        first_k_dense=1, rope_theta=10000.0)


def glm_moe_lite_leaf_shapes(cfg: GlmMoeLiteConfig) -> dict:
    """``{"attn": ..., "dense": ..., "moe": ...}``: name → (shape,
    fan_in) of one layer's matrices (norm gains and the selection bias
    apart)."""
    h, H = cfg.hidden, cfg.n_head
    E, f = cfg.n_routed_experts, cfg.moe_intermediate
    fs = cfg.moe_intermediate * cfg.n_shared_experts
    attn = {"w_qa": ((h, cfg.q_lora_rank), h),
            "w_qb": ((cfg.q_lora_rank, H * cfg.qk_head_dim),
                     cfg.q_lora_rank),
            "w_kva": ((h, cfg.latent_dim), h),
            "w_uk": ((H, cfg.kv_lora_rank, cfg.qk_nope_head_dim),
                     cfg.kv_lora_rank),
            "w_uv": ((H, cfg.kv_lora_rank, cfg.v_head_dim),
                     cfg.kv_lora_rank),
            "wo": ((H * cfg.v_head_dim, h), H * cfg.v_head_dim)}
    dense = {"w_gate": ((h, cfg.intermediate), h),
             "w_up": ((h, cfg.intermediate), h),
             "w_down": ((cfg.intermediate, h), cfg.intermediate)}
    moe = {"router": ((h, E), h),
           "w_gate": ((E, h, f), h), "w_up": ((E, h, f), h),
           "w_down": ((E, f, h), f),
           "ws_gate": ((h, fs), h), "ws_up": ((h, fs), h),
           "ws_down": ((fs, h), fs)}
    return {"attn": attn, "dense": dense, "moe": moe}


def init_glm_moe_lite_params(cfg: GlmMoeLiteConfig, rng,
                             dtype=jnp.float32) -> dict:
    """Deterministic weights: normal, std ``fan_in^-0.5``; gains 1; the
    selection bias normal std 0.02. ``lead`` is the list of the leading
    dense layers, ``blocks`` the list of the expert layers, a dict of
    leaves each (the serving model unrolls its layers: a grouped
    product reads a layer's experts where they lie). Router, bias and
    gains are float32 whatever ``dtype`` is."""
    shapes = glm_moe_lite_leaf_shapes(cfg)
    h = cfg.hidden

    def norms():
        return {"attn_norm": jnp.ones((h,), jnp.float32),
                "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "mlp_norm": jnp.ones((h,), jnp.float32)}

    def mats(key, table):
        out = {}
        for i, (name, (shape, fan)) in enumerate(sorted(table.items())):
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * fan ** -0.5
            out[name] = w if name == "router" else w.astype(dtype)
        return out

    def layer(key, ffn):
        p = {**mats(jax.random.fold_in(key, 0), shapes["attn"]),
             **mats(jax.random.fold_in(key, 1), shapes[ffn]), **norms()}
        if ffn == "moe":
            p["bias"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, 2), (cfg.n_routed_experts,),
                jnp.float32)
        return p

    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    n_moe = cfg.n_block - cfg.first_k_dense
    lead = [layer(jax.random.fold_in(k_layers, i), "dense")
            for i in range(cfg.first_k_dense)]
    blocks = [layer(jax.random.fold_in(k_layers, cfg.first_k_dense + i),
                    "moe") for i in range(n_moe)]
    return {"embed": jax.random.normal(k_embed, (cfg.vocab, h),
                                       jnp.float32).astype(dtype),
            "lead": lead, "blocks": blocks,
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": (jax.random.normal(k_head, (h, cfg.vocab), jnp.float32)
                     * h ** -0.5).astype(dtype)}
