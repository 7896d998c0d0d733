"""The Keye-VL-2.0 language model (``reference/keye_vl2.py``) as the
program serves it: ``PagedKeyeModel`` from the published keys of the
configuration's file.

The weights are the reference's own, made on the device leaf by leaf in
bfloat16 (the float32 tree of this configuration, 12.5 GB, does not fit
beside the cache) and handed over AS THE SAME ARRAYS under the program's
names: nothing is copied or re-laid.
"""

from __future__ import annotations

_LAYER = {"q": "wq", "k": "wk", "v": "wv", "o": "wo", "iq": "iq",
          "ik": "ik", "iw": "iw", "router": "router",
          "experts_gate": "w_gate", "experts_up": "w_up",
          "experts_down": "w_down", "input_norm": "attn_norm",
          "post_norm": "mlp_norm", "q_norm": "q_norm", "k_norm": "k_norm",
          "ik_norm": "ik_norm", "ik_bias": "ik_bias"}


def to_program_tree(ref: dict) -> dict:
    """The tree ``PagedKeyeModel`` takes, from the reference's."""
    return {"embed": ref["embed"],
            "blocks": [{_LAYER[name]: leaf for name, leaf in p.items()}
                       for p in ref["layers"]],
            "final_norm": ref["final_norm"], "head": ref["head"]}


def weights(seed: int, cfg: dict, ref_mod):
    import jax
    # a program without this architecture fails here, at once, before
    # six gigabytes of weights are made
    from zoo_tpu.serving.llm import model_keye  # noqa: F401
    params = to_program_tree(ref_mod.make_params(seed, cfg))
    if jax.default_backend() != "tpu":
        # a rehearsal at toy widths: the CPU multiplies no bfloat16
        # pair into float32, so the same values go over widened
        import jax.numpy as jnp
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
    jax.block_until_ready(params)
    return params


def model(cfg: dict, weights):
    """The object ``LLMEngine`` drives, from ``cfg["engine"]``."""
    from zoo_tpu.serving.llm.model_keye import KeyeConfig, PagedKeyeModel

    eng = cfg["engine"]
    return PagedKeyeModel(
        KeyeConfig.from_published(cfg), params=weights,
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
