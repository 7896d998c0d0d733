"""The ``minicpm_sala`` decoder (``reference/minicpm_sala.py``) as the
program serves it: ``PagedMiniCpmSalaModel`` from the published keys of
the configuration's file.

The weights are the reference's own, made on the device leaf by leaf in
bfloat16 (the float32 tree of this configuration, 15.7 GB, is as large
as the chip) and handed over AS THE SAME ARRAYS under the program's
names: nothing is copied or re-laid.
"""

from __future__ import annotations

_LAYER = {"q": "wq", "k": "wk", "v": "wv", "o_gate": "w_g", "o": "wo",
          "gate": "w_gate", "up": "w_up", "down": "w_down",
          "input_norm": "attn_norm", "post_norm": "mlp_norm",
          "q_norm": "q_norm", "k_norm": "k_norm", "o_norm": "o_norm"}


def to_program_tree(ref: dict) -> dict:
    """The tree ``PagedMiniCpmSalaModel`` takes, from the reference's."""
    return {"embed": ref["embed"],
            "blocks": [{_LAYER[name]: leaf for name, leaf in p.items()}
                       for p in ref["layers"]],
            "slopes": ref["slopes"],
            "final_norm": ref["final_norm"], "head": ref["head"]}


def weights(seed: int, cfg: dict, ref_mod):
    import jax
    # a program without this architecture fails here, at once, before
    # eight gigabytes of weights are made
    from zoo_tpu.serving.llm import model_sala  # noqa: F401
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
    from zoo_tpu.models.llm.minicpm_sala import MiniCpmSalaConfig
    from zoo_tpu.serving.llm.model_sala import PagedMiniCpmSalaModel

    eng = cfg["engine"]
    return PagedMiniCpmSalaModel(
        MiniCpmSalaConfig.from_published(cfg), params=weights,
        num_slots=eng["num_slots"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"],
        max_blocks_per_seq=eng["max_blocks_per_seq"],
        prefill_buckets=(eng["prefill_chunk"],),
        prefill_chunk=eng["prefill_chunk"], kv_dtype=eng["kv_dtype"],
        spec_k=eng["spec_k"], eos_id=eng["eos_id"])


def free(model):
    """Delete the device arrays the model holds: its weights and its
    cache (the paged leaves and the per-slot state)."""
    import jax
    leaves = jax.tree_util.tree_leaves((model.params, model._cache))
    model.params = model._cache = None
    for leaf in leaves:
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()
