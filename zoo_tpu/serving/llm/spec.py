# zoo-lint: jax-free
"""``llama:...`` model specs: mount the LLM engine behind a replica.

The HA layer launches replicas from a model STRING (``ReplicaGroup``
passes ``--model`` through to ``zoo_tpu.serving.replica``); this module
is the llm half of that resolution:

* ``llama:tiny`` — the test-topology config (``tiny_llama_config``),
  deterministic weights from seed 0: every replica of a group builds
  bit-identical params, which is what makes greedy decode reproducible
  across the group and the HA client's mid-stream failover-with-resume
  seamless.
* ``llama:tiny:seed=3,slots=4,block=8,blocks=64,buckets=16/64`` —
  key=value overrides after the preset (also ``chunk=N`` for chunked
  prefill, ``spec_k=N`` / ``spec_ngram=N`` for speculative decoding,
  and ``prefill_impl=`` for the chunk/verify attention kernel).
* ``llama:vocab=256,hidden=64,n_block=2,n_head=4,n_kv_head=2,``
  ``intermediate=128`` — explicit architecture, no preset.

* ``glm_moe_lite:tiny:slots=4,block=8,blocks=96,tables=8,chunk=8`` —
  the second architecture (``glm4_moe_lite``: latent attention, a
  dropless mixture of experts; ``serving/llm/model_mla.py``): the same
  engine keys, its own architecture keys (``vocab``, ``hidden``,
  ``n_block``, ``n_head``, ``q_rank``, ``kv_rank``, ``nope``, ``rope``,
  ``v_dim``, ``intermediate``, ``moe_intermediate``, ``experts``,
  ``shared``, ``top_k``, ``dense``).

* ``minicpm_sala:tiny:slots=4,block=8,blocks=96,tables=24,chunk=16`` —
  the third architecture (``minicpm_sala``: block-sparse attention that
  selects pages inside the paged cache among Lightning linear-attention
  layers whose per-slot state lives beside it;
  ``serving/llm/model_sala.py``): the same engine keys, its own
  architecture keys (``vocab``, ``hidden``, ``n_head``, ``n_kv_head``,
  ``head_dim``, ``l_heads``, ``l_head_dim``, ``intermediate``, ``depth``,
  ``mixers`` as a string of ``s`` (sparse) and ``l`` (Lightning), one
  letter a layer, and the selection's ``kernel``, ``stride``,
  ``sel_block``, ``init_blocks``, ``window``, ``topk``, ``dense_len``).
  ``block`` must equal ``sel_block`` (a page is one selection block).
  Two kinds of per-sequence memory: paged K/V and compressed keys for
  the sparse layers, one float32 state a slot for the Lightning layers.
  Refused for this model, with a ``ValueError`` that says so:
  ``prefix_cache=1``, ``spec_k>0``, ``kv=int8``, ``tp>1`` / ``mesh=``, a
  ``role`` other than ``mixed`` (KV migration).

Engine knobs resolve env (``ZOO_LLM_*``) < spec < explicit kwargs —
the env is the deployment-wide default, an explicit spec component
overrides it; the env names are documented in docs/llm_serving.md.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

LLM_PREFIX = "llama:"
GLM_PREFIX = "glm_moe_lite:"
SALA_PREFIX = "minicpm_sala:"
# spec key → MiniCpmSalaConfig field (``mixers`` apart: a string)
_SALA_ARCH_KEYS = {"vocab": "vocab", "hidden": "hidden",
                   "n_head": "n_head", "n_kv_head": "n_kv_head",
                   "head_dim": "head_dim", "l_heads": "lightning_heads",
                   "l_head_dim": "lightning_head_dim",
                   "intermediate": "intermediate", "depth": "depth",
                   "kernel": "kernel_size", "stride": "kernel_stride",
                   "sel_block": "sparse_block",
                   "init_blocks": "init_blocks", "window": "window_size",
                   "topk": "topk", "dense_len": "dense_len"}
_SALA_MIXERS = {"s": "minicpm4", "l": "lightning-attn"}
# spec key → GlmMoeLiteConfig field
_GLM_ARCH_KEYS = {"vocab": "vocab", "hidden": "hidden",
                  "n_block": "n_block", "n_head": "n_head",
                  "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
                  "nope": "qk_nope_head_dim", "rope": "qk_rope_head_dim",
                  "v_dim": "v_head_dim", "intermediate": "intermediate",
                  "moe_intermediate": "moe_intermediate",
                  "experts": "n_routed_experts",
                  "shared": "n_shared_experts",
                  "top_k": "num_experts_per_tok", "dense": "first_k_dense"}
# jax-free deterministic engine (chaos smokes / transport benches):
# synthllm:slots=2,block=4,blocks=64,tables=8 — the generate-path twin
# of the predict path's synthetic:double (see llm/synthetic.py)
SYNTH_LLM_PREFIX = "synthllm:"
_SYNTH_KEYS = {"slots": "num_slots", "block": "block_size",
               "blocks": "num_blocks", "tables": "max_blocks_per_seq",
               "max_prompt": "max_prompt_len", "eos": "eos_id",
               "chunk": "prefill_chunk"}

_ARCH_KEYS = ("vocab", "hidden", "n_block", "n_head", "n_kv_head",
              "intermediate")
_ENGINE_KEYS = {"slots": "num_slots", "block": "block_size",
                "blocks": "num_blocks", "tables": "max_blocks_per_seq",
                "seed": "seed", "eos": "eos_id", "tp": "tp",
                "chunk": "prefill_chunk", "prefix_cache": "prefix_cache",
                "spec_k": "spec_k", "spec_ngram": "spec_ngram"}
# string-valued engine/model keys (everything in _ENGINE_KEYS is int)
_STR_KEYS = {"kv": "kv_dtype", "prefill_impl": "prefill_impl",
             "role": "role"}


def is_llm_spec(spec) -> bool:
    return isinstance(spec, str) and spec.startswith(
        (LLM_PREFIX, GLM_PREFIX, SALA_PREFIX, SYNTH_LLM_PREFIX))


def _parse_kv(parts) -> Dict[str, str]:
    out = {}
    for part in parts:
        for kv in part.split(","):
            if not kv:
                continue
            if "=" not in kv:
                raise ValueError(
                    f"malformed llama spec component {kv!r} "
                    "(expected key=value)")
            k, v = kv.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def parse_llm_spec(spec: str) -> Tuple[Dict, Dict]:
    """``(config_kwargs, engine_kwargs)`` from a ``llama:...``,
    ``glm_moe_lite:...`` or ``minicpm_sala:...`` spec."""
    if not is_llm_spec(spec):
        raise ValueError(f"not an llm spec: {spec!r}")
    glm = spec.startswith(GLM_PREFIX)
    sala = spec.startswith(SALA_PREFIX)
    body = spec[len(GLM_PREFIX if glm else SALA_PREFIX if sala
                    else LLM_PREFIX):]
    parts = body.split(":") if body else [""]
    preset = parts[0] if parts[0] and "=" not in parts[0] else None
    kvs = _parse_kv(parts[1:] if preset else parts)
    arch_keys = _GLM_ARCH_KEYS if glm else _SALA_ARCH_KEYS if sala \
        else {k: k for k in _ARCH_KEYS}

    cfg_kwargs: Dict = {}
    if preset == "tiny" or preset is None and not any(
            k in kvs for k in arch_keys):
        if glm:
            from zoo_tpu.models.llm.glm_moe_lite import (
                tiny_glm_moe_lite_config as tiny_config,
            )
        elif sala:
            from zoo_tpu.models.llm.minicpm_sala import (
                tiny_minicpm_sala_config as tiny_config,
            )
        else:
            from zoo_tpu.models.llm.llama import (
                tiny_llama_config as tiny_config,
            )
        cfg_kwargs = dict(tiny_config().__dict__)
        cfg_kwargs.pop("tie_embeddings", None)
    elif preset is not None and preset != "tiny":
        raise ValueError(f"unknown llm preset {preset!r} "
                         "(supported: tiny, or explicit key=value dims)")
    for k, field in arch_keys.items():
        if k in kvs:
            cfg_kwargs[field] = int(kvs.pop(k))
    if sala and "mixers" in kvs:
        letters = kvs.pop("mixers")
        if not letters or set(letters) - set(_SALA_MIXERS):
            raise ValueError(f"mixers={letters!r}: one letter a layer, "
                             "s (sparse) or l (Lightning)")
        cfg_kwargs["mixer_types"] = tuple(_SALA_MIXERS[x] for x in letters)

    eng: Dict = {}
    for short, name in _ENGINE_KEYS.items():
        if short in kvs:
            eng[name] = int(kvs.pop(short))
    for short, name in _STR_KEYS.items():
        if short in kvs:
            eng[name] = kvs.pop(short)
    if "buckets" in kvs:
        eng["prefill_buckets"] = tuple(
            int(b) for b in kvs.pop("buckets").split("/"))
    if kvs:
        raise ValueError(f"unknown llm spec keys {sorted(kvs)}")
    return cfg_kwargs, eng


def _env_engine_defaults() -> Dict:  # zoo-lint: config-parse
    """ZOO_LLM_* env knobs (the per-replica deployment surface — a
    ReplicaGroup passes env to every replica it spawns)."""
    out: Dict = {}
    pairs = (("ZOO_LLM_SLOTS", "num_slots"),
             ("ZOO_LLM_BLOCK_SIZE", "block_size"),
             ("ZOO_LLM_KV_BLOCKS", "num_blocks"),
             ("ZOO_LLM_MAX_BLOCKS_PER_SEQ", "max_blocks_per_seq"),
             ("ZOO_LLM_SEED", "seed"),
             ("ZOO_LLM_EOS", "eos_id"),
             ("ZOO_LLM_TP", "tp"),
             ("ZOO_LLM_PREFILL_CHUNK", "prefill_chunk"))
    for env, name in pairs:
        v = os.environ.get(env)
        if v:
            out[name] = int(v)
    v = os.environ.get("ZOO_LLM_PREFILL_BUCKETS")
    if v:
        out["prefill_buckets"] = tuple(int(b) for b in v.split("/"))
    return out


def build_synthetic_engine(spec: str, start: bool = True, **overrides):
    """A jax-free :class:`LLMEngine` over a deterministic
    :class:`~zoo_tpu.serving.llm.synthetic.SyntheticLLMModel` from a
    ``synthllm:...`` spec — real allocator, scheduler, deadlines and
    dedup; pure-function tokens."""
    from zoo_tpu.serving.llm.engine import LLMEngine
    from zoo_tpu.serving.llm.synthetic import SyntheticLLMModel

    kvs = _parse_kv(spec[len(SYNTH_LLM_PREFIX):].split(":"))
    kwargs = {}
    for short, name in _SYNTH_KEYS.items():
        if short in kvs:
            kwargs[name] = int(kvs.pop(short))
    role = kvs.pop("role", None) or overrides.pop("role", None)
    if kvs:
        raise ValueError(f"unknown synthllm spec keys {sorted(kvs)}")
    kwargs.update({k: v for k, v in overrides.items()
                   if k != "max_waiting"})
    model = SyntheticLLMModel(**kwargs)
    engine = LLMEngine(model, max_waiting=overrides.get("max_waiting"),
                       role=role)
    return engine.start() if start else engine


def build_llm_engine(spec: str, start: bool = True, **overrides):
    """An :class:`LLMEngine` (started unless ``start=False``) from a
    ``llama:...``, ``glm_moe_lite:...``, ``minicpm_sala:...`` or
    ``synthllm:...`` spec. ``overrides`` are engine/model kwargs that
    win over both the spec and the env."""
    if spec.startswith(SYNTH_LLM_PREFIX):
        return build_synthetic_engine(spec, start=start, **overrides)
    from zoo_tpu.serving.llm.engine import LLMEngine
    if spec.startswith(GLM_PREFIX):
        from zoo_tpu.models.llm.glm_moe_lite import (
            GlmMoeLiteConfig as Config,
        )
        from zoo_tpu.serving.llm.model_mla import (
            PagedGlmMoeLiteModel as Model,
        )
    elif spec.startswith(SALA_PREFIX):
        from zoo_tpu.models.llm.minicpm_sala import (
            MiniCpmSalaConfig as Config,
        )
        from zoo_tpu.serving.llm.model_sala import (
            PagedMiniCpmSalaModel as Model,
        )
    else:
        from zoo_tpu.models.llm.llama import LlamaConfig as Config
        from zoo_tpu.serving.llm.model import PagedLlamaModel as Model

    cfg_kwargs, eng_kwargs = parse_llm_spec(spec)
    merged = dict(_env_engine_defaults())
    merged.update(eng_kwargs)
    merged.update({k: v for k, v in overrides.items()
                   if k != "max_waiting"})
    # prefix_cache is an ENGINE knob (content-hash block reuse), not a
    # model shape: spec `prefix_cache=0/1` < its ZOO_LLM_* env
    # resolution in the engine itself
    prefix_cache = merged.pop("prefix_cache", None)
    if prefix_cache is not None:
        prefix_cache = bool(int(prefix_cache))
    # spec_k is a MODEL shape (the fixed verify-executable width) and
    # stays in `merged`; spec_ngram is pure scheduler policy
    spec_ngram = merged.pop("spec_ngram", None)
    if spec_ngram is not None:
        spec_ngram = int(spec_ngram)
    # role is a SCHEDULER policy (prefill parks / decode adopts), not a
    # model shape: spec `role=` < ZOO_LLM_ROLE env in the engine
    role = merged.pop("role", None)
    cfg = Config(**cfg_kwargs)
    # tensor-parallel serving: `tp=N` (spec) / ZOO_LLM_TP (env) / a
    # `mesh=` override span ONE model over N local devices instead of
    # replicating it (docs/multichip.md)
    tp = int(merged.pop("tp", 0) or 0)
    if tp > 1 and "mesh" not in merged:
        import jax

        from zoo_tpu.parallel import build_mesh
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(
                f"llama spec asks for tp={tp} but only {len(devs)} "
                "local device(s) are visible")
        merged["mesh"] = build_mesh(devs[:tp], axis_sizes={"model": tp})
    model = Model(cfg, **merged)
    engine = LLMEngine(model, max_waiting=overrides.get("max_waiting"),
                       prefix_cache=prefix_cache,
                       spec_ngram=spec_ngram, role=role)
    return engine.start() if start else engine
