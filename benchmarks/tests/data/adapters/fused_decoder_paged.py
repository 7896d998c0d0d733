"""The tests' second served architecture (``reference/fused_decoder.py``)
as the program serves it: the paged model the engine has, built from a
configuration that states its sizes under its own names, with the
reference's list of fused layers split and stacked into the tree that
model takes."""

from __future__ import annotations

from adapters import llama_paged

free = llama_paged.free            # the same model class


def weights(seed: int, cfg: dict, ref_mod):
    import jax
    import jax.numpy as jnp
    ref = ref_mod.make_params(seed, cfg)
    q = cfg["heads"] * cfg["head_width"]
    kv = cfg["kv_heads"] * cfg["head_width"]
    f = cfg["ffn_width"]

    def stacked(pick):
        return jnp.stack([pick(layer) for layer in ref["stack"]])

    blocks = {"wq": stacked(lambda p: p["qkv"][:, :q]),
              "wk": stacked(lambda p: p["qkv"][:, q:q + kv]),
              "wv": stacked(lambda p: p["qkv"][:, q + kv:]),
              "wo": stacked(lambda p: p["out"]),
              "w_gate": stacked(lambda p: p["gate_up"][:, :f]),
              "w_up": stacked(lambda p: p["gate_up"][:, f:]),
              "w_down": stacked(lambda p: p["down"]),
              "attn_norm": stacked(lambda p: p["norm1"]),
              "mlp_norm": stacked(lambda p: p["norm2"])}
    params = {"embed": ref["tok"], "blocks": blocks,
              "final_norm": ref["norm"], "head": ref["unembed"]}
    jax.block_until_ready(params)
    return params


def model(cfg: dict, weights):
    """The paged model the engine has, through ``llama_paged.model``,
    with this configuration's sizes under the names that one reads."""
    return llama_paged.model(
        {"engine": cfg["engine"], "vocab_size": cfg["vocab_size"],
         "hidden_size": cfg["width"], "num_hidden_layers": cfg["depth"],
         "num_attention_heads": cfg["heads"],
         "num_key_value_heads": cfg["kv_heads"],
         "head_dim": cfg["head_width"],
         "intermediate_size": cfg["ffn_width"],
         "rope_theta": cfg["rope_base"], "rms_norm_eps": cfg["norm_eps"],
         "tie_word_embeddings": False}, weights)
