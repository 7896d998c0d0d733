"""The ``glm4_moe_lite`` decoder (``reference/glm4_moe_lite.py``) as the
program serves it: ``PagedGlmMoeLiteModel`` from the published keys of
the configuration's file.

The weights are the reference's own, made on the device leaf by leaf in
bfloat16 (the float32 tree of this configuration is larger than the
chip) and handed over AS THE SAME ARRAYS under the program's names: the
expert stacks are never copied. Only ``kv_b`` is re-laid, split by head
into the two halves the absorbed form multiplies with (9 MB a layer).
"""

from __future__ import annotations

_ATTN = {"q_a": "w_qa", "q_a_norm": "q_norm", "q_b": "w_qb",
         "kv_a": "w_kva", "kv_a_norm": "kv_norm", "o": "wo",
         "input_norm": "attn_norm", "post_norm": "mlp_norm"}
_DENSE = {"gate": "w_gate", "up": "w_up", "down": "w_down"}
_MOE = {"router": "router", "e_score_correction_bias": "bias",
        "experts_gate": "w_gate", "experts_up": "w_up",
        "experts_down": "w_down", "shared_gate": "ws_gate",
        "shared_up": "ws_up", "shared_down": "ws_down"}


def _layer(ref: dict, cfg: dict, names: dict) -> dict:
    """One layer of the reference under the program's names."""
    import jax.numpy as jnp
    nh, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    out = {new: ref[old] for old, new in {**_ATTN, **names}.items()}
    kv_b = ref["kv_b"]                  # (..., rank, heads * (nope + v))
    by_head = jnp.moveaxis(
        kv_b.reshape(kv_b.shape[:-1] + (nh, dn + cfg["v_head_dim"])),
        -2, -3)                          # (..., heads, rank, nope + v)
    out["w_uk"], out["w_uv"] = by_head[..., :dn], by_head[..., dn:]
    return out


def to_program_tree(ref: dict, cfg: dict) -> dict:
    """The tree ``PagedGlmMoeLiteModel`` takes, from the reference's."""
    return {"embed": ref["embed"],
            "lead": [_layer(p, cfg, _DENSE) for p in ref["dense"]],
            "blocks": [_layer(p, cfg, _MOE) for p in ref["moe"]],
            "final_norm": ref["final_norm"], "head": ref["head"]}


def weights(seed: int, cfg: dict, ref_mod):
    import jax
    # a program without this architecture fails here, at once, before
    # nine gigabytes of weights are made
    from zoo_tpu.serving.llm import model_mla  # noqa: F401
    ref = ref_mod.make_params(seed, cfg)
    params = to_program_tree(ref, cfg)
    if jax.default_backend() != "tpu":
        # a rehearsal at toy widths: the CPU multiplies no bfloat16
        # pair into float32, so the same values go over widened
        import jax.numpy as jnp
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
    jax.block_until_ready(params)
    for layer in ref["moe"] + ref["dense"]:
        layer["kv_b"].delete()
    return params


def model(cfg: dict, weights):
    """The object ``LLMEngine`` drives, from ``cfg["engine"]``."""
    from zoo_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig
    from zoo_tpu.serving.llm.model_mla import PagedGlmMoeLiteModel

    eng = cfg["engine"]
    return PagedGlmMoeLiteModel(
        GlmMoeLiteConfig.from_published(cfg), params=weights,
        num_slots=eng["num_slots"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["max_blocks_per_seq"],
        prefill_buckets=(eng["prefill_chunk"],),
        prefill_chunk=eng["prefill_chunk"], kv_dtype=eng["kv_dtype"],
        spec_k=eng["spec_k"], eos_id=eng["eos_id"])


def free(model):
    """Delete the device arrays the model holds: its weights and its
    cache."""
    import jax
    leaves = jax.tree_util.tree_leaves((model.params, model._cache))
    model.params = model._cache = None
    for leaf in leaves:
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()
