"""Operations and bytes of the tests' second served architecture, from
its own keys: the functions a metric file's bare ``decode_flops`` /
``decode_bytes`` resolve to for a cell of this configuration."""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def matmul_params(cfg: dict) -> int:
    w, d = cfg["width"], cfg["head_width"]
    fused = w * (cfg["heads"] + 2 * cfg["kv_heads"]) * d \
        + cfg["heads"] * d * w + 3 * w * cfg["ffn_width"]
    return cfg["depth"] * fused + w * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict) -> float:
    kind = cfg["precision"]["kv_cache"].split(",")[0].strip()
    rows = 2 * cfg["depth"] * cfg["kv_heads"]
    return rows * (cfg["head_width"] * _BYTES[kind]
                   + (4 if kind == "int8" else 0))


def decode_flops(cfg: dict, traffic: dict, census: dict) -> float:
    attended = 4.0 * cfg["heads"] * cfg["head_width"] * cfg["depth"] \
        * census["attended_positions"]
    return 2.0 * matmul_params(cfg) * census["decode_tokens"] + attended


def decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    weights = matmul_params(cfg) * _BYTES[cfg["precision"]["weights"]]
    return (weights * census["decode_ticks"]
            + census["decode_tokens"] * cfg["vocab_size"] * 4
            + kv_bytes_per_token(cfg) * census["attended_positions"])
