"""Operations and bytes that a step of the Keye-VL-2.0 language model
needs, from shapes alone: the functions the metric files of its cells
name (``decode_flops``, ``decode_bytes`` for the accepted whole-step
shares, ``dsa_decode_bytes`` for the token-sparse decode kernel's own).

As in ``formulas.py`` the counts are of what the algorithm needs, not of
what today's program does: the LEAST a tick must move. An attention
counts the K and V rows of the tokens its selection attends (``topk`` of
them once a context holds more, every one under it) and the indexer the
keys it scores (every resident one once the context holds more than
``topk``, none under it: then the selection is every token), never the
rows a dense attention would read; an expert layer counts the experts a
tick is expected to visit.
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "fp8": 1}


def attention_params(cfg: dict) -> int:
    """One layer's q, k, v and o."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * d * (2 * nh + 2 * nkv)


def indexer_params(cfg: dict) -> int:
    """One layer's indexer: its queries, its key and its heads' weights."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (sa["indexer_num_heads"]
                                 * sa["indexer_head_dim"]
                                 + sa["indexer_head_dim"]
                                 + sa["indexer_num_heads"])


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict) -> int:
    """A whole layer: attention, indexer, router and every expert."""
    return (attention_params(cfg) + indexer_params(cfg)
            + router_params(cfg) + cfg["num_experts"] * expert_params(cfg))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Every matrix the configuration as cut holds, the embedding
    included (norm gains apart)."""
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + 2 * head_params(cfg)


def active_params(cfg: dict) -> int:
    """Weights one decoded token multiplies with: in every layer the
    attention, the indexer, the router and ``num_experts_per_tok``
    experts; the head (the embedding is a look-up)."""
    return cfg["num_hidden_layers"] * (
        attention_params(cfg) + indexer_params(cfg) + router_params(cfg)
        + cfg["num_experts_per_tok"] * expert_params(cfg)) \
        + head_params(cfg)


def selected(cfg: dict, context: float) -> float:
    """Tokens a query with ``context`` resident tokens attends."""
    return min(float(cfg["sa_config"]["topk"]), context)


def scored(cfg: dict, context: float) -> float:
    """Index keys that query scores: all of them once it has more than
    ``topk``, none under it."""
    return context if context > cfg["sa_config"]["topk"] else 0.0


def kv_row_bytes(cfg: dict) -> int:
    """A token's K and V of every K/V head, at the cache's stated type."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["precision"]["kv_cache"]]


def index_key_bytes(cfg: dict) -> int:
    return cfg["sa_config"]["indexer_head_dim"] \
        * _BYTES[cfg["precision"]["index_keys"]]


def flops_per_token(cfg: dict, context: float) -> float:
    """2 FLOPs an active weight, and in every layer the indexer's
    products with the keys it scores (every indexer head) and the
    attention over the selected tokens (scores and context, every query
    head)."""
    sa = cfg["sa_config"]
    index = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * scored(cfg, context)
    attend = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * selected(cfg, context)
    return 2.0 * active_params(cfg) \
        + cfg["num_hidden_layers"] * (index + attend)


def experts_read(cfg: dict, lanes: float) -> float:
    """Experts a tick of ``lanes`` tokens reads in one layer: the
    EXPECTATION under even routing, ``E * (1 - (1 - k/E)^lanes)``; the
    program's counter ``zoo_llm_moe_expert_visits_total`` is what a run
    read (``moe_expert_visits.closed``)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def _mean_context(census: dict) -> float:
    return census["attended_positions"] / census["decode_tokens"]


def decode_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Model FLOPs of the tokens the traced decode ticks produced."""
    if not census["decode_tokens"]:
        return 0.0
    return flops_per_token(cfg, _mean_context(census)) \
        * census["decode_tokens"]


def dsa_decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the token-sparse decode kernel ``zoo_dsa_decode`` had
    to read in the traced ticks: the K and V rows of every selected
    token, for every layer and every live token. The queries, the lists
    and the outputs are left out (under 1% at 2,048 tokens a slot)."""
    if not census["decode_tokens"]:
        return 0.0
    return (census["decode_tokens"] * cfg["num_hidden_layers"]
            * selected(cfg, _mean_context(census)) * kv_row_bytes(cfg))


def decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the traced decode ticks had to move: per tick the
    weights every token multiplies with once at the stated type (the
    router at its own) and of the experts those that
    :func:`experts_read` expects; one row of logits per live lane; per
    live token and layer the index keys scored and the selected tokens'
    K and V rows."""
    ticks, tokens = census["decode_ticks"], census["decode_tokens"]
    if not ticks or not tokens:
        return 0.0
    w = _BYTES[cfg["precision"]["weights"]]
    lanes = tokens / ticks
    per_tick = (cfg["num_hidden_layers"] * (
        (attention_params(cfg) + indexer_params(cfg)
         + experts_read(cfg, lanes) * expert_params(cfg)) * w
        + router_params(cfg) * _BYTES[cfg["precision"]["router"]])
        + head_params(cfg) * w)
    keys = (tokens * cfg["num_hidden_layers"]
            * scored(cfg, _mean_context(census)) * index_key_bytes(cfg))
    return (per_tick * ticks + tokens * cfg["vocab_size"] * 4 + keys
            + dsa_decode_bytes(cfg, traffic, census))
