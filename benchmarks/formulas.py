"""Operations and bytes that a step needs, from shapes alone.

Every function takes the configuration (the dict of its file under
``configs/``), the traffic file's dict and a ``census`` of what the
window did (counts the harness took itself), and returns one number.
A per-layer metric's file names one of them (``"formula":
"decode_flops"``), and the name resolves in the formulas module that the
cell's configuration names (``"formulas"``, by default this one): a
configuration of another architecture brings ``formulas_<arch>.py`` with
functions of the same names and lists its cells under the same metrics.

The counts are of what the algorithm needs, not of what today's program
does: recomputed activations do not count, and weights are counted at
the precision the configuration states.
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "fp8": 1}


# ----------------------------------------------------------- BERT (training)

def bert_train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward matmul FLOPs of one sample (``bench.py``
    ``bench_bert``'s formula): 3 * n_block * (8*H^2 + 4*H*I + 4*S*H) * S.

    Per token and block: QKV 6*H^2, output projection 2*H^2, the two
    feed-forward matmuls 4*H*I, scores and context 4*S*H; the backward
    pass costs twice the forward. Embedding look-ups, layer norms and
    the two-class head are left out (under 0.1%)."""
    h, i, n = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    return 3.0 * n * (8 * h * h + 4 * h * i + 4 * seq_len * h) * seq_len


def bert_param_count(cfg: dict) -> int:
    h, i, n = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    block = 4 * h * h + 4 * h + 2 * h * i + i + h + 4 * h
    emb = (cfg["vocab_size"] + cfg["max_position_embeddings"]
           + cfg["type_vocab_size"]) * h + 2 * h
    pooler = h * h + h
    head = h * cfg["train"]["num_labels"] + cfg["train"]["num_labels"]
    return n * block + emb + pooler + head


def train_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Model FLOPs of every sample the traced steps trained on."""
    return bert_train_flops_per_sample(cfg, traffic["seq_len"]) \
        * census["samples"]


# --------------------------------------------------------- decoder (serving)

def decoder_matmul_params(cfg: dict) -> int:
    """Weights every decoded token multiplies with: the blocks' seven
    matrices and the output head (the embedding is a look-up)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    block = h * q + 2 * h * kv + q * h + 3 * h * i
    return cfg["num_hidden_layers"] * block + h * cfg["vocab_size"]


def decoder_param_count(cfg: dict) -> int:
    h = cfg["hidden_size"]
    norms = cfg["num_hidden_layers"] * 2 * h + h
    return decoder_matmul_params(cfg) + cfg["vocab_size"] * h + norms


def decoder_flops_per_token(cfg: dict, context: float) -> float:
    """2 FLOPs a weight, plus scores and context over ``context``
    attended positions in every layer."""
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context \
        * cfg["num_hidden_layers"]
    return 2.0 * decoder_matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached position costs to read: K and V rows of every
    layer at the cache's stated type, and for int8 the two f32 scales
    of each row."""
    kind = cfg["precision"]["kv_cache"].split(",")[0].strip()
    rows = 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
    scales = rows * 4 if kind == "int8" else 0
    return rows * cfg["head_dim"] * _BYTES[kind] + scales


def decode_flops(cfg: dict, traffic: dict, census: dict) -> float:
    """Model FLOPs of the tokens the traced decode ticks produced."""
    tokens = census["decode_tokens"]
    if not tokens:
        return 0.0
    mean_ctx = census["attended_positions"] / tokens
    return decoder_flops_per_token(cfg, mean_ctx) * tokens


def decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the traced decode ticks had to move: per tick the
    matmul weights once at the stated weight type and one row of logits
    per live lane; per attended position its K and V rows."""
    w = _BYTES[cfg["precision"]["weights"]]
    per_tick = decoder_matmul_params(cfg) * w
    logits = census["decode_tokens"] * cfg["vocab_size"] * 4
    return (per_tick * census["decode_ticks"] + logits
            + kv_bytes_per_token(cfg) * census["attended_positions"])

