"""A Llama-shaped decoder (``reference/decoder_lm.py``) as the program
serves it: ``PagedLlamaModel`` from a ``LlamaConfig``, built with the
calls ``build_llm_engine`` makes and not from a ``llama:`` spec string,
because the spec grammar has no ``rope_theta`` key.

The weights are the reference's own, made on the device in one call and
handed over as they are (float32, every value exact in bfloat16); the
program narrows what it holds narrow.
"""

from __future__ import annotations


def weights(seed: int, cfg: dict, ref_mod):
    """The tree ``PagedLlamaModel`` takes, from the reference's."""
    import jax
    ref = ref_mod.make_params(seed, cfg)
    params = {"embed": ref["embed"], "blocks": ref["layers"],
              "final_norm": ref["final_norm"], "head": ref["head"]}
    jax.block_until_ready(params)
    return params


def model(cfg: dict, weights):
    """The object ``LLMEngine`` drives, from ``cfg["engine"]``."""
    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import PagedLlamaModel

    eng = cfg["engine"]
    lcfg = LlamaConfig(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_block=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        intermediate=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])
    if lcfg.head_dim != cfg["head_dim"]:
        raise ValueError("the program derives head_dim = hidden/heads "
                         f"= {lcfg.head_dim}, the configuration "
                         f"states {cfg['head_dim']}")
    return PagedLlamaModel(
        lcfg, params=weights, num_slots=eng["num_slots"],
        block_size=eng["block_size"], num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["max_blocks_per_seq"],
        prefill_buckets=(eng["prefill_chunk"],),
        prefill_chunk=eng["prefill_chunk"],
        kv_dtype=eng["kv_dtype"], spec_k=eng["spec_k"],
        eos_id=eng["eos_id"])


def free(model):
    """Delete the device arrays the model holds: its weights and its
    cache."""
    import jax
    leaves = jax.tree_util.tree_leaves((model.params, model._cache))
    model.params = model._cache = None
    for leaf in leaves:
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()
