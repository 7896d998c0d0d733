"""Operations and bytes that a step of the ``glm4_moe_lite`` decoder
(GLM-4.7-Flash) needs, from shapes alone: the functions the metric files
of its cells name (``decode_flops``, ``decode_bytes`` for the accepted
whole-step shares, ``mla_decode_bytes`` for the kernel's own).

As in ``formulas.py`` the counts are of what the algorithm needs, not of
what today's program does: a cache row counts its ``kv_lora_rank +
qk_rope_head_dim`` values (the program pads a row to whole 128-lane
tiles), and weights count at the precision the configuration states.
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "fp8": 1}


def attention_params(cfg: dict) -> int:
    """One layer's latent attention: q_a, q_b, kv_a, kv_b, o."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * qk
            + h * (r + cfg["qk_rope_head_dim"])
            + r * nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + nh * cfg["v_head_dim"] * h)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def expert_layer_params(cfg: dict) -> int:
    """A whole expert layer: attention, router, shared and every
    routed expert."""
    return (attention_params(cfg) + router_params(cfg)
            + expert_params(cfg) * (cfg["n_shared_experts"]
                                    + cfg["n_routed_experts"]))


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) \
        + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Every matrix the configuration as cut holds, the embedding
    included (norm gains and the selection bias apart)."""
    nd = cfg["first_k_dense_replace"]
    return (nd * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - nd) * expert_layer_params(cfg)
            + 2 * head_params(cfg))


def active_params(cfg: dict) -> int:
    """Weights one decoded token multiplies with: attention of every
    layer, the dense layers, and in an expert layer the router, the
    shared expert and ``num_experts_per_tok`` routed ones; the head
    (the embedding is a look-up)."""
    nd = cfg["first_k_dense_replace"]
    moe = (attention_params(cfg) + router_params(cfg)
           + expert_params(cfg) * (cfg["n_shared_experts"]
                                   + cfg["num_experts_per_tok"]))
    return (nd * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - nd) * moe + head_params(cfg))


def latent_row_values(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached position costs to read: one latent row a layer
    at the cache's stated type."""
    kind = cfg["precision"]["kv_cache"].split(",")[0].strip()
    return cfg["num_hidden_layers"] * latent_row_values(cfg) * _BYTES[kind]


def flops_per_token(cfg: dict, context: float) -> float:
    """2 FLOPs an active weight, plus the absorbed attention over
    ``context`` positions in every layer: scores over rank + rope,
    context over rank, for every head."""
    attn = 2.0 * cfg["num_attention_heads"] \
        * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * context * cfg["num_hidden_layers"]
    return 2.0 * active_params(cfg) + attn


def experts_read(cfg: dict, lanes: float) -> float:
    """Routed experts a tick of ``lanes`` tokens reads in one layer: the
    EXPECTATION under even routing, ``E * (1 - (1 - k/E)^lanes)``. The
    program's counter ``zoo_llm_moe_expert_visits_total`` is what a run
    really read (the metric ``moe_expert_visits.closed``); uneven
    routing reads fewer, so this could only count too many."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def decode_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Model FLOPs of the tokens the traced decode ticks produced."""
    tokens = census["decode_tokens"]
    if not tokens:
        return 0.0
    mean_ctx = census["attended_positions"] / tokens
    return flops_per_token(cfg, mean_ctx) * tokens


def decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the traced decode ticks had to move: per tick the
    weights every token multiplies with once at the stated type (the
    router at its own) and of the routed experts those that
    :func:`experts_read` expects; one row of logits per live lane; one
    latent row a layer per attended position."""
    ticks = census["decode_ticks"]
    if not ticks:
        return 0.0
    w = _BYTES[cfg["precision"]["weights"]]
    nd = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - nd
    lanes = census["decode_tokens"] / ticks
    per_tick = (
        (nd * dense_layer_params(cfg) + head_params(cfg)
         + n_moe * (attention_params(cfg)
                    + expert_params(cfg) * (cfg["n_shared_experts"]
                                            + experts_read(cfg, lanes)))
         ) * w
        + n_moe * router_params(cfg) * _BYTES[cfg["precision"]["router"]])
    logits = census["decode_tokens"] * cfg["vocab_size"] * 4
    return (per_tick * ticks + logits
            + kv_bytes_per_token(cfg) * census["attended_positions"])


def mla_decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the latent decode kernel had to read in the traced
    ticks: the latent row of every attended position in every layer.
    The queries it reads and the partial outputs it writes are left out
    (under 1% at a thousand positions a slot), and so are the lanes the
    program pads a row with."""
    return kv_bytes_per_token(cfg) * census["attended_positions"]


def moe_gmm_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the grouped product ``zoo_moe_gmm`` had to read in
    the traced decode ticks: the three matrices of the routed experts
    :func:`experts_read` expects a tick to visit, in every expert
    layer, at the stated type. The (token, choice) rows it reads and
    writes are left out (128 rows a tick against some 54 experts of
    9.4 M weights). The count is the expectation under even routing,
    as in :func:`decode_bytes`: the counter read 2% fewer (PERF.md
    section 5), so the share is flattered by that much and no more."""
    ticks = census["decode_ticks"]
    if not ticks:
        return 0.0
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    lanes = census["decode_tokens"] / ticks
    return (ticks * n_moe * experts_read(cfg, lanes) * expert_params(cfg)
            * _BYTES[cfg["precision"]["weights"]])
