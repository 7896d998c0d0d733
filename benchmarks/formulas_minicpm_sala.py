"""Operations and bytes that a step of the ``minicpm_sala`` decoder
(MiniCPM-SALA) needs, from shapes alone: the functions the metric files
of its cells name (``decode_flops``, ``decode_bytes`` for the accepted
whole-step shares, ``sparse_decode_bytes`` and ``lightning_decode_bytes``
for the two kernels' own).

As in ``formulas.py`` the counts are of what the algorithm needs, not of
what today's program does: the LEAST a tick must move. A sparse layer
counts the pages its selection attends (``topk`` of them from
``dense_len`` resident tokens on, every page under it) and the
compressed keys it scores, never the rows a dense attention would read;
a Lightning layer counts its state read once and written once for a live
slot, nothing for an idle one.
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "fp8": 1}
SPARSE = "minicpm4"


def _layers(cfg: dict):
    n_sparse = sum(1 for m in cfg["mixer_types"] if m == SPARSE)
    return n_sparse, len(cfg["mixer_types"]) - n_sparse


def ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse_layer_params(cfg: dict) -> int:
    """q, k, v, the output gate, o and the feed-forward of a
    ``minicpm4`` layer."""
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * (3 * nq + 2 * nkv) + ffn_params(cfg)


def lightning_layer_params(cfg: dict) -> int:
    """q, k, v, the output gate, o and the feed-forward of a
    ``lightning-attn`` layer."""
    n = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 5 * cfg["hidden_size"] * n + ffn_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Every matrix the configuration as cut holds, the embedding
    included (norm gains and the decay slopes apart)."""
    n_sparse, n_light = _layers(cfg)
    return (n_sparse * sparse_layer_params(cfg)
            + n_light * lightning_layer_params(cfg) + 2 * head_params(cfg))


def active_params(cfg: dict) -> int:
    """Weights one decoded token multiplies with: every layer and the
    head (the embedding is a look-up)."""
    return matmul_params(cfg) - head_params(cfg)


def pages_attended(cfg: dict, context: float) -> float:
    """Pages one K/V head of one sparse layer attends for a query with
    ``context`` resident tokens: every page under ``dense_len``,
    ``topk`` from there on."""
    sp = cfg["sparse_config"]
    pages = -(-context // sp["block_size"])
    return pages if context < sp["dense_len"] else min(sp["topk"], pages)


def rows_attended(cfg: dict, context: float) -> float:
    """K (and V) rows of one K/V head those pages hold: every resident
    row under ``dense_len``; from there on whole pages but the query's
    own, which holds, on average, half a page of rows at or before it."""
    sp = cfg["sparse_config"]
    if context < sp["dense_len"]:
        return context
    return min(context, (pages_attended(cfg, context) - 0.5)
               * sp["block_size"])


def windows_scored(cfg: dict, context: float) -> float:
    """Compressed keys a query scores in one K/V head of one sparse
    layer: the whole windows at or before it, none under
    ``dense_len``."""
    sp = cfg["sparse_config"]
    if context < sp["dense_len"]:
        return 0.0
    return max(0.0, (context - sp["kernel_size"]) // sp["kernel_stride"] + 1)


def state_values(cfg: dict) -> int:
    """float32 values of one slot's state in one Lightning layer."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def flops_per_token(cfg: dict, context: float) -> float:
    """2 FLOPs an active weight; in a sparse layer the scores against
    the compressed keys and the attention over the selected rows, for
    every query head; in a Lightning layer the state's decay-and-update
    and the output product."""
    n_sparse, n_light = _layers(cfg)
    hq, d = cfg["num_attention_heads"], cfg["head_dim"]
    sparse = 2.0 * hq * d * (windows_scored(cfg, context)
                             + 2 * rows_attended(cfg, context))
    return (2.0 * active_params(cfg) + n_sparse * sparse
            + n_light * 4.0 * state_values(cfg))


def _mean_context(census: dict) -> float:
    return census["attended_positions"] / census["decode_tokens"]


def decode_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Model FLOPs of the tokens the traced decode ticks produced."""
    if not census["decode_tokens"]:
        return 0.0
    return flops_per_token(cfg, _mean_context(census)) \
        * census["decode_tokens"]


def sparse_decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the block-sparse decode kernel had to read in the
    traced ticks: the K and V rows of the selected pages, for every
    K/V head of every sparse layer and every live token. The queries,
    the tables and the outputs are left out (under 1%)."""
    if not census["decode_tokens"]:
        return 0.0
    n_sparse, _ = _layers(cfg)
    kv = _BYTES[cfg["precision"]["kv_cache"]]
    row = 2 * cfg["head_dim"] * kv                       # K and V
    return (census["decode_tokens"] * n_sparse * cfg["num_key_value_heads"]
            * rows_attended(cfg, _mean_context(census)) * row)


def lightning_decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the Lightning decode kernel had to move in the traced
    ticks: every live slot's state read once and written once in every
    Lightning layer (q, k, v and the output rows are under 1%)."""
    _, n_light = _layers(cfg)
    state = _BYTES[cfg["precision"]["lightning_state"]]
    return (census["decode_tokens"] * n_light * 2 * state_values(cfg)
            * state)


def decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the traced decode ticks had to move: per tick the
    weights every token multiplies with, once, at the stated type; one
    row of logits per live lane; per live token the selected pages'
    K and V rows, the compressed keys scored, and the Lightning states
    read and written."""
    if not census["decode_ticks"] or not census["decode_tokens"]:
        return 0.0
    n_sparse, _ = _layers(cfg)
    w = _BYTES[cfg["precision"]["weights"]]
    ck = _BYTES[cfg["precision"]["compressed_keys"]]
    scored = (census["decode_tokens"] * n_sparse
              * cfg["num_key_value_heads"] * cfg["head_dim"] * ck
              * windows_scored(cfg, _mean_context(census)))
    return (active_params(cfg) * w * census["decode_ticks"]
            + census["decode_tokens"] * cfg["vocab_size"] * 4
            + sparse_decode_bytes(cfg, traffic, census) + scored
            + lightning_decode_bytes(cfg, traffic, census))
