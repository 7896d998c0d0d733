"""Plain reference of the tests' second served architecture: the same
mathematics as ``reference/decoder_lm.py`` under a configuration that
states its sizes by other names (``width``, ``depth``, ``heads``,
``kv_heads``, ``head_width``, ``ffn_width``, ``rope_base``, ``norm_eps``)
and a tree laid out another way: a LIST of layers, each with the three
attention projections fused into ``qkv`` and the two feed-forward inputs
into ``gate_up``. It exists to show that the harness takes an
architecture by files alone; it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import decoder_lm as plain


def _sizes(cfg: dict):
    q = cfg["heads"] * cfg["head_width"]
    kv = cfg["kv_heads"] * cfg["head_width"]
    return q, kv, cfg["ffn_width"]


def make_params(seed: int, cfg: dict):
    w, v, (q, kv, f) = cfg["width"], cfg["vocab_size"], _sizes(cfg)
    keys = jax.random.split(plain.seed_key(seed), 2 + 4 * cfg["depth"])

    def mat(k, shape):
        return plain._normal_bf16(k, shape, shape[0])

    stack = []
    for i in range(cfg["depth"]):
        k = keys[2 + 4 * i:6 + 4 * i]
        stack.append({"qkv": mat(k[0], (w, q + 2 * kv)),
                      "out": mat(k[1], (q, w)),
                      "gate_up": mat(k[2], (w, 2 * f)),
                      "down": mat(k[3], (f, w)),
                      "norm1": jnp.ones((w,), jnp.float32),
                      "norm2": jnp.ones((w,), jnp.float32)})
    return {"tok": plain._normal_bf16(keys[0], (v, w), 1.0), "stack": stack,
            "norm": jnp.ones((w,), jnp.float32),
            "unembed": mat(keys[1], (w, v))}


def _plain_cfg(cfg: dict) -> dict:
    return {"hidden_size": cfg["width"], "intermediate_size": cfg["ffn_width"],
            "num_attention_heads": cfg["heads"],
            "num_key_value_heads": cfg["kv_heads"],
            "head_dim": cfg["head_width"], "num_hidden_layers": cfg["depth"],
            "vocab_size": cfg["vocab_size"], "rms_norm_eps": cfg["norm_eps"],
            "rope_theta": cfg["rope_base"]}


def _plain_tree(params: dict, cfg: dict) -> dict:
    q, kv, f = _sizes(cfg)

    def layer(p):
        return {"wq": p["qkv"][:, :q], "wk": p["qkv"][:, q:q + kv],
                "wv": p["qkv"][:, q + kv:], "wo": p["out"],
                "w_gate": p["gate_up"][:, :f], "w_up": p["gate_up"][:, f:],
                "w_down": p["down"], "attn_norm": p["norm1"],
                "mlp_norm": p["norm2"]}

    layers = [layer(p) for p in params["stack"]]
    return {"embed": params["tok"], "final_norm": params["norm"],
            "head": params["unembed"],
            "layers": {k: jnp.stack([lay[k] for lay in layers])
                       for k in layers[0]}}


def served_gaps(params, cfg: dict, prompt, served, pad_to: int = 256,
                lower_too: bool = False):
    return plain.served_gaps(_plain_tree(params, cfg), _plain_cfg(cfg),
                             prompt, served, pad_to=pad_to,
                             lower_too=lower_too)


def free(params):
    plain.free(params)
