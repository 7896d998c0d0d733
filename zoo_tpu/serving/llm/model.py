"""Prefill/decode split over one set of Llama weights, paged KV.

The compilation contract that makes autoregressive serving viable on an
XLA device:

* **Prefill** — a full causal forward over the (padded) prompt, one
  compiled executable per *prompt-length bucket* (a handful of shapes,
  e.g. 32/128/512), reusing the training attention stack — the Pallas
  flash kernel at long buckets on TPU, the fused dense path otherwise
  (``resolve_attention_impl``). The prompt's K/V are scattered into the
  paged cache through the sequence's block table as part of the same
  executable. With ``prefill_chunk=N`` (``ZOO_LLM_PREFILL_CHUNK``) the
  bucket census collapses to ONE chunk executable: prompts are fed in
  fixed-size N-token chunks that attend over everything already
  resident in the cache, so a 4k prompt costs many short ticks the
  scheduler interleaves with decode instead of one long stall.
* **Decode** — exactly ONE fixed-shape executable: ``num_slots``
  sequences x 1 token. Every iteration it writes the incoming token's
  K/V through the block tables, runs **paged attention** over the
  cache, and **samples the next token on device** (greedy argmax or
  temperature/top-k/top-p with per-slot parameter lanes and per-slot
  PRNG keys), so only ``slots x 1`` int32 ids ever cross to the host —
  never the ``slots x vocab`` logits. Slot count, table width and block
  count are fixed at construction, so the decode loop NEVER recompiles
  — request churn only changes the *contents* of the operands (the
  Orca iteration-level scheduling precondition).

Decode attention has two implementations behind
:func:`resolve_decode_impl`:

* ``"flash"`` (TPU default) — the paged flash-decode Pallas kernel
  (:mod:`zoo_tpu.ops.pallas.paged_decode`): K/V blocks are read
  directly through the block table with online softmax and split-KV
  parallelism, never materializing the gathered per-sequence cache;
* ``"dense"`` (off-TPU default, and the correctness reference) — the
  PR 7 ``cache[block_table]`` gather + masked softmax.

Token identity between the two is asserted by the test suite; a decode
tick's sampled ids are also a pure function of (weights, prompt,
sampling params, seed, token index) — the PRNG key for token *i* is
``fold_in(seed, i)``, independent of scheduling history — so
preempt-resume and HA failover-with-resume replay byte-identically.

Inactive slots point their block table at the reserved trash block 0
and are masked by position, so the executable has no liveness branch.

The cache lives here as two device arrays
``(n_layer, num_blocks, n_kv_head, block_size, head_dim)`` —
``(block_size, head_dim)`` minor, so one table entry is a slab the TPU
kernels DMA whole and index by head — donated through every
prefill/decode call so XLA updates them in place: the layer loop
carries them whole, appends at ``[layer, block, head, row]`` and hands
them whole to the paged kernels with the layer's index, so no call
copies, relays or slices a layer's slab out of them. The
sampled token batch of a decode tick is likewise returned as a DEVICE
array that :meth:`decode_step` accepts back as the next tick's input —
the engine's overlapped pipeline chains ticks without a host round
trip, and only the async readback thread ever blocks on a transfer.

**Tensor-parallel serving** (``mesh=``): ONE set of weights and ONE
paged KV cache span every device of the mesh's ``model`` axis instead
of the model being cloned per replica — attention/MLP weights follow
the megatron plan (``zoo_tpu.parallel.plans``), the KV cache is sharded
on its ``n_kv_head`` axis (each device owns its heads' K/V for every
block), and both executables are jitted with explicit NamedSharding
in/out shardings. The flash kernel runs under ``shard_map`` over the
``model`` axis (each device decodes its own KV heads; attention is
head-local so no collective is needed before the output projection).
The donation aliasing keeps the in-place cache update, so the
single-decode-executable and zero-recompile invariants hold unchanged
on the mesh; per-device weight+cache memory drops to ~1/tp of the
replicated model.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from zoo_tpu.models.llm.llama import (
    Llama,
    LlamaConfig,
    _rms_norm,
    apply_rope,
    resolve_attention_impl,
    rope_frequencies,
)
from zoo_tpu.common.knobs import value as knob_value
from zoo_tpu.obs.metrics import counter
from zoo_tpu.obs.tracing import span
from zoo_tpu.ops.attention import dot_product_attention
from zoo_tpu.util.quantize import absmax_scale, narrow_int8

DEFAULT_PREFILL_BUCKETS = (32, 128, 512)

# chunk-executable width used to feed the novel SUFFIX of a
# prefix-cache hit when chunked prefill is off (a cache-hit prompt must
# start prefill at its first uncached token, and the bucket executable
# can only start at 0); any fixed width works — it compiles once
SUFFIX_CHUNK_DEFAULT = 64

# the host-transfer audit: everything the decode hot path moves across
# the device boundary per tick (tokens out). The acceptance contract —
# slots x 1 int32 ids, never slots x vocab logits — is asserted against
# this counter's per-tick delta.
_host_transfer = counter(
    "zoo_llm_host_transfer_bytes_total",
    "Bytes read back from the device (device -> host only; the other "
    "direction is zoo_llm_operand_transfers_total) by the LLM serving "
    "hot path, by payload kind (tokens = the per-tick slots x 1 id "
    "batch)",
    labels=("kind",))

# the other direction: what a dispatch hands the device. One a call.
_operand_transfers = counter(
    "zoo_llm_operand_transfers_total",
    "Host -> device operand hand-offs made by the LLM serving "
    "executables, by call (decode / prefill_chunk / verify / prefill): "
    "one packed word buffer a dispatch",
    labels=("call",))


class OperandLayout:
    """One device call's host operands as ONE buffer of int32 words.

    Every operand of a serving executable but the cache and the
    previous tick's on-device tokens is small and made on the host:
    token ids, table rows, positions, sampling lanes. Handed over one
    by one, each is a transfer of its own and a trip through the
    interpreter lock, which the connection threads contend for at the
    very moment a tick's tokens land. So a call's operands lie in one
    contiguous buffer, field after field in the order given, and cross
    in one hand-off: :meth:`pack` on the host, :meth:`unpack` at the top
    of the jitted body.

    A field is ``(name, shape, dtype)``; ``int32`` words are stored as
    they are, ``float32`` and ``uint32`` by their BITS (a ``view`` here,
    ``bitcast_convert_type`` there: no value is converted, so nothing
    can round and a seed over 2**31 survives), ``bool`` as 0 / 1.

    **The buffer is fresh every call, never a ring.** The copy to the
    device is asynchronous and the CPU backend may alias numpy memory
    outright, and two ticks and a chunk are in flight by design: a
    staging buffer filled again for tick N+1 while tick N's copy is
    under way would corrupt N. jax holds a reference to the array it
    was handed until the copy is done, so a buffer nobody writes twice
    is safe on every backend (``tests/test_llm_operands.py``:
    ``test_operands_in_flight_keep_their_own``)."""

    def __init__(self, fields):
        self.fields = []
        at = 0
        for name, shape, dtype in fields:
            size = int(np.prod(shape, dtype=np.int64))
            self.fields.append(
                (name, tuple(shape), np.dtype(dtype), at, size))
            at += size
        self.words = at

    def pack(self, **values) -> np.ndarray:
        buf = np.empty((self.words,), np.int32)
        for name, shape, dtype, at, size in self.fields:
            value = np.asarray(values[name])
            if value.size != size:
                raise ValueError(
                    f"operand {name!r} has shape {value.shape}, the "
                    f"executable's is {shape}")
            lane = buf[at:at + size]
            if dtype != np.bool_:
                lane = lane.view(dtype)
            lane[:] = value.reshape(-1)
        return buf

    def unpack(self, words) -> dict:
        """Inside a jitted body: the fields back out of the word buffer
        by static slices, which the compiler fuses into their users."""
        out = {}
        for name, shape, dtype, at, size in self.fields:
            lane = words[at:at + size].reshape(shape)
            if dtype == np.bool_:
                lane = lane != 0
            elif dtype != np.int32:
                lane = jax.lax.bitcast_convert_type(lane, dtype)
            out[name] = lane
        return out

    def aval(self, sharding=None):
        """The packed operand's shape, for lowering without data."""
        return jax.ShapeDtypeStruct((self.words,), jnp.int32,
                                    sharding=sharding)


def resolve_decode_impl(impl: Optional[str] = "auto") -> str:
    """Concrete decode-attention kernel for this process.

    ``"auto"`` (default) picks the paged flash-decode Pallas kernel
    when jax's backend is the TPU (``pallas.on_tpu()``) and the
    dense-gather reference off TPU, where the kernel would run under
    the slow interpreter. The pick is recorded
    (``decode_attention_impl``, ``llm_stats``) so a run can assert
    it. ``ZOO_LLM_DECODE_IMPL`` force-overrides for
    A/B runs and for asserting token identity on CPU
    (``dense`` / ``flash``)."""
    if impl in (None, "auto"):
        impl = knob_value("ZOO_LLM_DECODE_IMPL") or "auto"
    if impl != "auto":
        if impl not in ("dense", "flash"):
            raise ValueError(f"unknown decode impl {impl!r} "
                             "(dense / flash / auto)")
        return impl
    from zoo_tpu.ops.pallas import on_tpu
    return "flash" if on_tpu() else "dense"


def resolve_prefill_impl(impl: Optional[str] = "auto") -> str:
    """Concrete chunk-prefill/verify attention kernel for this process.

    ``"auto"`` (default) picks the paged flash-prefill Pallas kernel
    (:mod:`zoo_tpu.ops.pallas.paged_prefill`) on TPU hardware and the
    dense ``cache[block_table]`` gather off TPU — the gather is the
    correctness anchor the kernel is asserted token-identical against.
    ``ZOO_LLM_PREFILL_IMPL`` force-overrides (``dense`` / ``flash``)
    for A/B runs and for asserting identity on CPU via the
    interpreter. Applies to the CHUNK executable (chunked prefill,
    prefix-cache suffix feeds) and the speculative-decode VERIFY
    executable; the bucketed whole-prompt prefill keeps the training
    attention stack (:func:`resolve_attention_impl`)."""
    if impl in (None, "auto"):
        impl = knob_value("ZOO_LLM_PREFILL_IMPL") or "auto"
    if impl != "auto":
        if impl not in ("dense", "flash"):
            raise ValueError(f"unknown prefill impl {impl!r} "
                             "(dense / flash / auto)")
        return impl
    from zoo_tpu.ops.pallas import on_tpu
    return "flash" if on_tpu() else "dense"


KV_DTYPES = ("f32", "bf16", "int8")


def resolve_kv_dtype(dtype: Optional[str] = None) -> str:
    """Concrete KV-cache storage dtype for this process.

    ``None``/empty reads ``ZOO_LLM_KV_DTYPE`` (default ``f32``, the
    pre-quantization layout). ``auto`` picks ``int8`` on TPU hardware —
    decode is HBM-bound there and int8 halves the bytes the roofline
    charges per token — and ``f32`` off TPU where bandwidth is not the
    wall and the reference numerics are worth keeping. The selection is
    recorded (model attr, engine stats, bench line), never silent."""
    if dtype in (None, ""):
        dtype = knob_value("ZOO_LLM_KV_DTYPE") or "f32"
    dtype = {"fp32": "f32", "float32": "f32",
             "bfloat16": "bf16"}.get(dtype, dtype)
    if dtype == "auto":
        from zoo_tpu.ops.pallas import on_tpu
        return "int8" if on_tpu() else "f32"
    if dtype not in KV_DTYPES:
        raise ValueError(f"unknown KV cache dtype {dtype!r} "
                         f"({'/'.join(KV_DTYPES)}/auto)")
    return dtype


def _pick_bucket(buckets: Sequence[int], n: int) -> Optional[int]:
    for b in buckets:
        if n <= b:
            return b
    return None


# ---------------------------------------------------------------- weights

# the stacked block leaves that are consumed ONLY as dot operands (an
# untied ``head`` is the eighth); norm gains multiply elementwise and
# ``embed`` is gathered — and, tied, is the head too — so they stay f32
DOT_BLOCK_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def narrow_dot_weights(params, platform: str,
                       dot_leaves: Sequence[str] = None):
    """The weight tree as the serving executables should hold it on
    ``platform`` (docs/llm_serving.md, "Weights").

    On a TPU at the platform's own matmul precision
    (``jax_default_matmul_precision`` unset / ``default`` /
    ``bfloat16``) an f32 x f32 dot is ONE bf16 pass on the MXU: the
    compiler rounds both operands to bf16 and accumulates in f32. The
    weight operand is loop-invariant, so it hoists the convert of each
    whole ``(n_layer, ...)`` stack out of the layer scan and runs it
    once per call — every decode tick and prefill chunk re-derives the
    same bf16 weights. So there, and only there, the f32 dot leaves are
    rounded to bf16 ONCE, here: no value the step computes changes, the
    per-call converts and half of the weights' bytes go. Everything
    else comes back as the SAME object: norm gains, ``embed``, a leaf
    that is already narrow (a bf16 checkpoint), every leaf on CPU/GPU
    (there an f32 dot is an f32 dot) and every leaf when a higher
    matmul precision was asked for. Cast leaf by leaf — the transient
    is one leaf, not a second model — and the caller's arrays are
    never deleted. ``dot_leaves`` names an architecture's own dot
    leaves (default: the Llama block's seven); ``params["blocks"]`` (and
    ``params["lead"]``, layers that differ from the rest) may each be
    one dict of stacked leaves or a list of per-layer dicts."""
    if platform != "tpu" or jax.config.jax_default_matmul_precision \
            not in (None, "default", "bfloat16"):
        return params

    def narrow(leaf):
        return leaf.astype(jnp.bfloat16) \
            if leaf.dtype == jnp.float32 else leaf

    names = DOT_BLOCK_LEAVES if dot_leaves is None else dot_leaves

    def narrow_block(block):
        return {name: narrow(leaf) if name in names else leaf
                for name, leaf in block.items()}

    out = dict(params)
    for key in ("blocks", "lead"):
        if key in params:
            # one dict of stacked leaves, or a list of per-layer dicts
            out[key] = narrow_block(params[key]) \
                if isinstance(params[key], dict) \
                else [narrow_block(b) for b in params[key]]
    if "head" in params:
        out["head"] = narrow(params["head"])
    return out


def _weight_dot(x, w):
    """``x @ w`` for a weight operand, multiplied in the dtype the
    weight is HELD in: activations drop to a narrower weight's dtype
    (what the MXU pass does to them anyway), the product accumulates
    and comes back in f32. A weight that is not narrower than the
    activations is the plain ``x @ w``."""
    if w.dtype.itemsize < x.dtype.itemsize:
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)
    return x @ w


# ------------------------------------------------------ on-device sampling

GREEDY = (0.0, 0, 1.0, 0)  # (temperature, top_k, top_p, seed)


def _sample_one(logits: jnp.ndarray, temp, topk, topp, key):
    """Sample ONE token id from a (vocab,) logit row on device.

    ``temp <= 0`` is greedy argmax (the seed is never consulted, so
    greedy streams stay reproducible without PRNG bookkeeping).
    Otherwise: temperature-scale, keep the top-k logits (``topk <= 0``
    disables), keep the top-p nucleus of the remaining mass
    (``topp >= 1`` disables), then draw via Gumbel-max with the given
    key — the draw is a pure function of (logits, params, key), which
    is what makes preempt/failover replay byte-identical."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, 1e-4)
    desc = jnp.sort(scaled)[::-1]
    kth = desc[jnp.clip(topk, 1, v) - 1]
    masked = jnp.where(jnp.logical_or(topk <= 0, scaled >= kth),
                       scaled, -jnp.inf)
    probs = jax.nn.softmax(masked)
    sp = jnp.sort(probs)[::-1]
    # nucleus: the smallest prefix of the sorted probs reaching topp;
    # a token is in it iff the mass STRICTLY BEFORE it is < topp
    included = (jnp.cumsum(sp) - sp) < topp
    thresh = jnp.min(jnp.where(included, sp, jnp.inf))
    masked = jnp.where(probs >= thresh, masked, -jnp.inf)
    sampled = jnp.argmax(
        masked + jax.random.gumbel(key, (v,), jnp.float32)
    ).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _slot_keys(seeds: jnp.ndarray, token_index: jnp.ndarray):
    """Per-slot PRNG key for sampling the token at ``token_index``:
    ``fold_in(PRNGKey(seed), index)``. Stateless by construction — the
    key depends only on the stream's seed and the token's position in
    the sequence, never on scheduling history, so a preempted stream
    re-prefilled on this (or any) replica redraws identical tokens."""
    base = jnp.stack([jnp.zeros_like(seeds), seeds],
                     axis=-1).astype(jnp.uint32)          # raw threefry
    return jax.vmap(jax.random.fold_in)(base, token_index)


@jax.named_scope("zoo.sample")
def _sample_row(logits, temp, topk, topp, seed, token_index):
    """Single-row sampling for the prefill executables' first generated
    token: same greedy ``lax.cond`` fast path as the decode batch."""
    def drawn(_):
        key = _slot_keys(jnp.asarray([seed], jnp.uint32),
                         jnp.asarray([token_index]))[0]
        return _sample_one(logits, temp, topk, topp, key)

    return jax.lax.cond(
        temp > 0.0, drawn,
        lambda _: jnp.argmax(logits).astype(jnp.int32), None)


@jax.named_scope("zoo.sample")
def _sample_tokens(logits, temps, topks, topps, seeds, token_index):
    """(S, vocab) logits -> (S,) int32 ids, all lanes independent.

    Greedy-only batches (the default deployment) take a
    ``lax.cond`` fast path that skips the whole sampling pipeline —
    two O(V log V) vocab sorts, a softmax/cumsum, and a Gumbel draw
    per lane would otherwise run every tick just to be discarded by
    the temperature select. One executable either way."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        keys = _slot_keys(seeds, token_index)
        sampled = jax.vmap(_sample_one)(logits, temps, topks, topps,
                                        keys)
        return jnp.where(temps <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temps > 0.0), drawn,
                        lambda _: greedy, None)


class _TickBatch:
    """A dispatched decode tick whose executable counted something: the
    on-device token batch and, beside it, the ``aux`` arrays of
    ``_layers``. The engine hands it back untouched as ``prev_batch``
    and to :meth:`PagedDecoderModel.read_tokens`."""

    __slots__ = ("tokens", "aux")

    def __init__(self, tokens, aux):
        self.tokens, self.aux = tokens, aux


class PagedDecoderModel:
    """Decoder weights + a paged cache + the serving executables: the
    skeleton every served architecture runs.

    What is shared lives here: the four jitted bodies (``_decode_fn``,
    ``_prefill_fn``, ``_prefill_chunk_fn``, ``_verify_fn``: token
    select, embedding, the step's addressing through the block tables,
    the head and on-device sampling), cache donation, operand transfer,
    the host spans, ``copy_block`` and KV migration. What an
    architecture varies is a handful of hooks a subclass gives:

    * ``_init_params`` / ``DOT_LEAVES`` / ``_weight_probe`` — its
      weight tree, the leaves that are only ever dot operands, and one
      of them (``weight_dtype`` reports its dtype);
    * ``_rope_dim`` — the rotated width of a head;
    * ``_init_cache`` / ``_cache_shardings`` — the cache pytree and its
      bytes per token. Every PAGED leaf has its block axis at position
      1; ``UNPAGED_LEAVES`` names the leaves that are not paged (a
      per-slot recurrent state beside the paged K/V: slot axis at
      position 1, addressed by a tick's row or a prefill's ``slot``),
      which ``copy_block`` and KV migration leave alone;
    * ``_layers(params, cache, h, attend, at)`` — the layer stack: the
      scan (and any leading layer outside it), the attention half
      through ``attend`` — one of ``_attend_decode`` / ``_attend_bucket``
      / ``_attend_chunk`` / ``_attend_verify`` — and the feed-forward
      half; returns ``(h, cache, aux)`` where ``aux`` is a (possibly
      empty) tuple of small arrays, per-tick device counts that ride
      back with a decode tick's tokens (:meth:`_apply_tick_aux`).

    ``params=None`` builds deterministic weights from ``seed`` — every
    replica of a spec holds bit-identical params, so decode (greedy or
    seeded sampling) is reproducible across the group (the property
    the HA client's failover-resume leans on).
    """

    DOT_LEAVES = DOT_BLOCK_LEAVES
    UNPAGED_LEAVES: Tuple[str, ...] = ()

    def __init__(self, config, *,
                 params=None, seed: int = 0,
                 num_slots: int = 8,
                 block_size: int = 16,
                 num_blocks: int = 128,
                 max_blocks_per_seq: int = 32,
                 prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
                 prefill_chunk: Optional[int] = None,
                 decode_impl: str = "auto",
                 prefill_impl: str = "auto",
                 kv_dtype: Optional[str] = None,
                 spec_k: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 mesh=None):
        self.cfg = config
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.prefill_buckets = tuple(sorted(int(b) for b in
                                            prefill_buckets))
        if prefill_chunk is None:
            prefill_chunk = int(knob_value("ZOO_LLM_PREFILL_CHUNK"))
        self.prefill_chunk_size = int(prefill_chunk)
        self.decode_attention_impl = resolve_decode_impl(decode_impl)
        self.prefill_attention_impl = resolve_prefill_impl(prefill_impl)
        # speculative decoding: the VERIFY executable's fixed candidate
        # width is spec_k + 1 (the incoming token plus up to spec_k
        # drafted continuations); 0 = no verify path, the engine runs
        # plain 1-token decode
        if spec_k is None:
            # default owned by the knob registry: spec.py, the
            # engine and this model resolve the SAME definition
            spec_k = int(knob_value("ZOO_LLM_SPEC_K"))
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 = off)")
        # KV storage dtype (docs/llm_serving.md): f32 (reference), bf16
        # (half the bytes), int8 + per-(block,kv-head,row) absmax
        # scales (half again). Both the requested and resolved values
        # are recorded so an `auto` pick is visible in stats/bench.
        self.kv_cache_dtype_requested = kv_dtype if kv_dtype not in (
            None, "") else (knob_value("ZOO_LLM_KV_DTYPE") or "f32")
        self.kv_cache_dtype = resolve_kv_dtype(kv_dtype)
        self.eos_id = eos_id
        if self.num_slots < 1 or self.num_blocks < 2:
            raise ValueError("need >= 1 slot and >= 2 KV blocks")
        self.max_context = self.max_blocks_per_seq * self.block_size
        if self.prefill_buckets[-1] > self.max_context:
            raise ValueError(
                f"largest prefill bucket {self.prefill_buckets[-1]} "
                f"exceeds the block-table context capacity "
                f"{self.max_context}")
        self.max_prompt_len = self.prefill_buckets[-1] \
            if not self.prefill_chunk_size else self.max_context
        if self.prefill_chunk_size < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = off)")

        self.mesh = mesh if mesh is not None \
            and getattr(mesh, "size", 1) > 1 else None
        self.tp = self.mesh.shape.get("model", 1) if self.mesh is not None \
            else 1
        c = config
        self._check_config()
        self.params = self._init_params(params, seed)
        # held in the dtype the device's dot reads them in (bf16 on a
        # TPU, untouched elsewhere), before the mesh placement below
        self.params = narrow_dot_weights(
            self.params, (self.mesh.devices.flat[0] if self.mesh is not None
                          else jax.devices()[0]).platform, self.DOT_LEAVES)
        # what the dot leaves are held as and what the whole tree costs
        # in HBM — ``llm_stats`` and the zoo_llm_weight_bytes gauge
        self.weight_dtype = str(self._weight_probe().dtype)
        self.weight_bytes = int(sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params)))
        # rope tables over the whole pageable context, closed over by
        # every executable (f32, tiny: max_context x rotated width/2)
        self._cos, self._sin = rope_frequencies(
            self._rope_dim(), self.max_context, c.rope_theta)
        # the cache pytree and the HBM bytes ONE cached token costs
        # over every layer — the engine republishes the latter as the
        # zoo_llm_kv_bytes_per_token gauge and the bench's byte model
        # reads it instead of hardcoding a layout
        self._cache, self.kv_bytes_per_token = self._init_cache()
        # chunk-executable width: the scheduling chunk when chunked
        # prefill is on, else the fixed suffix-feed width prefix-cache
        # hits use (compiles at most ONE chunk executable either way)
        self.suffix_chunk_size = self.prefill_chunk_size or min(
            SUFFIX_CHUNK_DEFAULT, self.prefill_buckets[-1])
        # one call at a time: prefill/decode donate + replace the cache
        # arrays, so interleaved calls would race the handoff. (The
        # lock covers DISPATCH only — decode_step returns a device
        # future, and chaining the donated caches sequences the actual
        # executions on the device stream.)
        self._lock = threading.Lock()
        # the chain seed for prev_tokens on an idle restart — placed
        # exactly like a decode output so the executable census stays
        # at one (a default-device zeros array would be a distinct
        # sharding layout and compile a second entry under a mesh)
        self._zero_tokens = jnp.zeros((self.num_slots,), jnp.int32)
        # each executable's host operands as one word buffer: widths
        # known here, nothing about them is a knob
        S, W = self.num_slots, self.max_blocks_per_seq
        lanes = [("temps", (S,), np.float32), ("topks", (S,), np.int32),
                 ("topps", (S,), np.float32), ("seeds", (S,), np.uint32)]
        # one sequence's: its table row, its sampling, its slot (read
        # by an architecture with a per-slot state, a spare word else)
        row = [("length", (), np.int32), ("block_table", (W,), np.int32),
               ("temp", (), np.float32), ("topk", (), np.int32),
               ("topp", (), np.float32), ("seed", (), np.uint32),
               ("slot", (), np.int32)]
        batch = [("block_tables", (S, W), np.int32),
                 ("positions", (S,), np.int32)] + lanes
        self._layouts = {
            "decode": OperandLayout(
                [("host_tokens", (S,), np.int32),
                 ("use_host", (S,), np.bool_)] + batch),
            "verify": OperandLayout(
                [("tokens", (S, self.spec_k + 1), np.int32)] + batch),
            "prefill_chunk": OperandLayout(
                [("ids", (1, self.suffix_chunk_size), np.int32),
                 ("start", (), np.int32)] + row)}
        # a bucket executable finds its layout by its operand's length
        self._row_words = OperandLayout(row).words
        self._prefill_layouts = {
            b: OperandLayout([("ids", (1, b), np.int32)] + row)
            for b in self.prefill_buckets}
        self.operand_transfers = dict.fromkeys(
            ("decode", "prefill_chunk", "verify", "prefill"), 0)
        if self.mesh is None:
            # the cache pytree is arg 1 → donated: XLA aliases it in
            # place, leaf for leaf, through the layer loop's carry (K/V
            # blocks and, under int8, their scale rows)
            self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
            self._prefill = jax.jit(self._prefill_fn,
                                    donate_argnums=(1,))
            self._prefill_chunked = jax.jit(self._prefill_chunk_fn,
                                            donate_argnums=(1,))
            self._verify = jax.jit(self._verify_fn, donate_argnums=(1,))
            self._copy = jax.jit(self._copy_block_fn,
                                 donate_argnums=(0,))
        else:
            from zoo_tpu.parallel.mesh import (
                publish_mesh_metrics,
                replicated_sharding,
            )
            from zoo_tpu.parallel.plans import place_params, shardings_of

            publish_mesh_metrics(self.mesh)
            self.params = place_params(self.params, self.mesh)
            rep = replicated_sharding(self.mesh)
            self._zero_tokens = jax.device_put(self._zero_tokens, rep)
            cache_sh = self._cache_shardings()
            self._cache = {name: jax.device_put(arr, cache_sh[name])
                           for name, arr in self._cache.items()}
            p_sh = shardings_of(self.params, self.mesh)
            # identical donated in/out cache shardings keep the in-place
            # alias on the mesh; the packed operand buffer (and the
            # previous tick's tokens) and the emitted token ids are
            # replicated (the host round trip stays slots x 1)
            self._decode = jax.jit(
                self._decode_fn, donate_argnums=(1,),
                in_shardings=(p_sh, cache_sh, rep, rep),
                out_shardings=(rep, cache_sh))
            self._prefill = jax.jit(
                self._prefill_fn, donate_argnums=(1,),
                in_shardings=(p_sh, cache_sh, rep),
                out_shardings=(rep, cache_sh))
            self._prefill_chunked = jax.jit(
                self._prefill_chunk_fn, donate_argnums=(1,),
                in_shardings=(p_sh, cache_sh, rep),
                out_shardings=(rep, cache_sh))
            self._verify = jax.jit(
                self._verify_fn, donate_argnums=(1,),
                in_shardings=(p_sh, cache_sh, rep),
                out_shardings=(rep, cache_sh))
            self._copy = jax.jit(
                self._copy_block_fn, donate_argnums=(0,),
                in_shardings=(cache_sh, rep, rep),
                out_shardings=cache_sh)

        # where the paged cache (and so the executables) actually lives,
        # as jax reports it — ``llm_stats`` publishes this, so a replica
        # that came up on the host CPU, or a tp=N cache that landed on
        # one device, is visible from outside the process
        devs = sorted(next(iter(self._cache.values())).devices(),
                      key=lambda d: d.id)
        self.device_info = {"platform": devs[0].platform,
                            "kind": devs[0].device_kind,
                            "ids": [int(d.id) for d in devs]}

    def _copy_block_fn(self, cache, src, dst):
        """Block ``src`` -> ``dst`` across every layer of every PAGED
        leaf (K, V and scale rows alike): the device half of
        copy-on-write — the allocator forks the table entry, this moves
        the bytes. A leaf that is not paged passes through."""
        return {name: arr if name in self.UNPAGED_LEAVES
                else arr.at[:, dst].set(arr[:, src])
                for name, arr in cache.items()}

    def _paged(self) -> dict:
        """The paged leaves of the cache pytree."""
        return {name: arr for name, arr in self._cache.items()
                if name not in self.UNPAGED_LEAVES}

    @jax.named_scope("zoo.lm_head")
    def _lm_head(self, params, h):
        c = self.cfg
        h = _rms_norm(h, params["final_norm"], c.rms_eps)
        head = (params["embed"].T if c.tie_embeddings
                else params["head"])
        return _weight_dot(h, head)

    # -- compiled bodies: the skeleton ---------------------------------------
    def _decode_fn(self, params, cache, prev_tokens, operands):
        """One token for every slot; ``operands`` is the tick's packed
        host operands (``_layouts["decode"]``). The incoming token per
        slot is
        either ``host_tokens`` (freshly admitted stream: the prefill's
        first token) or ``prev_tokens`` — the PREVIOUS tick's on-device
        output, so back-to-back ticks chain without a host round trip.
        ``positions`` (S,) is the cache index the incoming token's
        cache row is written at. Returns the SAMPLED next tokens
        (device) and the updated cache pytree, and after them the
        arrays of the tick's ``aux`` counts where the architecture has
        any."""
        op = self._layouts["decode"].unpack(operands)
        block_tables, positions = op["block_tables"], op["positions"]
        tokens = jnp.where(op["use_host"], op["host_tokens"], prev_tokens)
        h = jnp.take(params["embed"], tokens, axis=0)        # (S, hidden)
        at = {"cos": jnp.take(self._cos, positions, axis=0),  # (S, D/2)
              "sin": jnp.take(self._sin, positions, axis=0),
              "blk": jnp.take_along_axis(
                  block_tables, (positions // self.block_size)[:, None],
                  axis=1)[:, 0],                              # (S,)
              "off": positions % self.block_size,
              "tables": block_tables, "pos": positions, "real": None,
              # a tick's row IS its slot
              "slot": None}
        h, cache, aux = self._layers(params, cache, h,
                                     self._attend_decode, at)
        logits = self._lm_head(params, h)                     # (S, vocab)
        # the token being drawn sits at sequence index position+1
        nxt = _sample_tokens(logits, op["temps"], op["topks"],
                             op["topps"], op["seeds"], positions + 1)
        return (nxt, cache, *aux)

    def _prefill_fn(self, params, cache, operands):
        """Causal forward over one padded prompt (1, L_bucket): scatter
        the prompt's cache rows into the paged cache and return the
        sampled first generated token. ``operands`` is the packed
        buffer of the bucket's layout, found by its length. ``length``
        is the true prompt length (dynamic); pad positions write to the
        trash block and are never attended by real tokens (they sit in
        the causal future). ``slot`` is the sequence's slot, read by an
        architecture that keeps a per-slot state beside the paged
        leaves and by no other."""
        op = self._prefill_layouts[
            operands.shape[0] - self._row_words].unpack(operands)
        ids, length, block_table = op["ids"], op["length"], \
            op["block_table"]
        L = ids.shape[1]
        pos = jnp.arange(L)
        # pad positions → trash block 0 (their rows must not land in the
        # sequence's real blocks: block ``pos // bs`` may be unallocated
        # past the prompt's last block)
        at = {"cos": self._cos[:L], "sin": self._sin[:L],
              "blk": jnp.where(pos < length,
                               block_table[pos // self.block_size], 0),
              "off": pos % self.block_size,
              "tables": block_table[None], "pos": pos[None],
              "real": (pos < length)[None], "slot": self._slot_of(op)}
        h = jnp.take(params["embed"], ids, axis=0)
        h, cache, _ = self._layers(params, cache, h,
                                   self._attend_bucket, at)
        logits = self._lm_head(params, h)                  # (1, L, vocab)
        last = jnp.take(logits[0], length - 1, axis=0)     # (vocab,)
        # first generated token = sequence index ``length``
        tok = _sample_row(last, op["temp"], op["topk"], op["topp"],
                          op["seed"], length)
        return tok, cache

    def _slot_of(self, op):
        """A prefill's slot operand, for an architecture that has a use
        for it."""
        return op["slot"] if self.UNPAGED_LEAVES else None

    def _prefill_chunk_fn(self, params, cache, operands):
        """One fixed-size CHUNK of a prompt (``operands``: the packed
        buffer of ``_layouts["prefill_chunk"]``): write the chunk's cache
        rows through the block table at positions ``start..start+C-1``
        and attend each chunk token causally over everything already
        resident (earlier chunks included) — the same math as the
        bucket prefill, just fed through the cache in N-token slices.
        Returns the sampled first generated token, meaningful only on
        the chunk that contains the prompt's last real token (earlier
        chunks sample from a mid-prompt row the engine discards)."""
        op = self._layouts["prefill_chunk"].unpack(operands)
        ids, start, length, block_table = op["ids"], op["start"], \
            op["length"], op["block_table"]
        C = ids.shape[1]
        ctx = self.max_blocks_per_seq * self.block_size
        pos = start + jnp.arange(C)                       # (C,)
        real = pos < length
        # pad rows past the pageable context must still take FINITE
        # rope rows: jnp.take fills out-of-bounds with NaN, and a NaN
        # K/V written to the trash block poisons every later layer
        # through 0 * NaN in the masked attention. Real rows always
        # sit below max_context, so the clamp never moves them.
        pos = jnp.minimum(pos, ctx - 1)
        # causal over the CACHE index space: chunk row i attends every
        # resident position <= start+i (all real writes — earlier
        # chunks plus this chunk's own prefix)
        at = {"cos": jnp.take(self._cos, pos, axis=0),    # (C, D/2)
              "sin": jnp.take(self._sin, pos, axis=0),
              "blk": jnp.where(real,
                               block_table[pos // self.block_size], 0),
              "off": pos % self.block_size,
              "tables": block_table[None], "pos": pos[None],
              "real": real[None], "slot": self._slot_of(op)}
        h = jnp.take(params["embed"], ids, axis=0)
        h, cache, _ = self._layers(params, cache, h,
                                   self._attend_chunk, at)
        logits = self._lm_head(params, h)                 # (1, C, vocab)
        last = jnp.take(logits[0],
                        jnp.clip(length - 1 - start, 0, C - 1), axis=0)
        tok = _sample_row(last, op["temp"], op["topk"], op["topp"],
                          op["seed"], length)
        return tok, cache

    def _verify_fn(self, params, cache, operands):
        """Speculative-decode VERIFY: score ``spec_k + 1`` candidate
        tokens per slot in ONE device call (``operands``: the packed
        buffer of ``_layouts["verify"]``). Row 0 of ``tokens`` (S, T)
        is the slot's incoming token (the last emitted one), rows 1..
        are the drafter's proposals; row ``j`` is written through the
        block table at cache index ``positions[s] + j`` and attends
        everything ``<= its position`` — so its logits are exactly what
        sequential decode would compute after accepting rows ``< j``.
        Each row then samples with the SAME stateless per-position key
        non-speculative decode would use (``fold_in(seed, pos + j +
        1)``), which is what makes the host's longest-accepted-prefix
        emission byte-identical to plain decode, greedy and seeded
        alike. Rejected rows' cache rows stay in place as garbage the
        position mask hides until the next append overwrites them —
        rollback is a pure length reset. Rows past the pageable
        context write to the trash block (their outputs are never
        accepted; the engine caps draft length to owned blocks)."""
        op = self._layouts["verify"].unpack(operands)
        tokens, block_tables, positions = op["tokens"], \
            op["block_tables"], op["positions"]
        S, T = tokens.shape
        ctx = self.max_blocks_per_seq * self.block_size
        raw = positions[:, None] + jnp.arange(T)[None, :]     # (S, T)
        real = raw < ctx
        # same finite-rope clamp as the chunk executable (a NaN K/V in
        # the trash block would poison later layers through 0 * NaN)
        pos = jnp.minimum(raw, ctx - 1)
        at = {"cos": jnp.take(self._cos, pos, axis=0),    # (S, T, D/2)
              "sin": jnp.take(self._sin, pos, axis=0),
              "blk": jnp.where(
                  real,
                  jnp.take_along_axis(block_tables,
                                      pos // self.block_size, axis=1),
                  0),                                         # (S, T)
              "off": pos % self.block_size,
              "tables": block_tables, "pos": pos, "real": real,
              "slot": None}
        h = jnp.take(params["embed"], tokens, axis=0)   # (S, T, hidden)
        h, cache, _ = self._layers(params, cache, h,
                                   self._attend_verify, at)
        logits = self._lm_head(params, h)               # (S, T, vocab)
        nxt = _sample_tokens(
            logits.reshape(S * T, -1),
            jnp.repeat(op["temps"], T), jnp.repeat(op["topks"], T),
            jnp.repeat(op["topps"], T), jnp.repeat(op["seeds"], T),
            (raw + 1).reshape(S * T)).reshape(S, T)
        return nxt, cache

    # -- host-facing API (what the engine calls) ---------------------------
    @staticmethod
    def _sampling_tuple(sampling) -> Tuple[float, int, float, int]:
        if sampling is None:
            return GREEDY
        t, k, p, s = sampling
        return float(t), int(k), float(p), int(s) & 0xFFFFFFFF

    def _hand_off(self, call: str, layout: OperandLayout, **values):
        """A dispatch's host operands, packed for their ONE hand-off:
        the numpy buffer goes to the jitted call as it is. Under the
        dispatch lock."""
        buf = layout.pack(**values)
        self.operand_transfers[call] += 1
        _operand_transfers.labels(call=call).inc()
        return buf

    def _row_operands(self, n, block_table_row, sampling, slot) -> dict:
        """The operands every prefill of one sequence has."""
        bt = np.asarray(block_table_row, np.int32)
        if bt.shape != (self.max_blocks_per_seq,):
            raise ValueError("block_table_row has the wrong width")
        t, k, p, s = self._sampling_tuple(sampling)
        return dict(length=n, block_table=bt, temp=t, topk=k, topp=p,
                    seed=s, slot=0 if slot is None else int(slot))

    def prefill(self, prompt: np.ndarray, block_table_row: np.ndarray,
                sampling=None, slot: Optional[int] = None) -> int:
        """Run one prompt through its bucket executable; the prompt's
        K/V land in the blocks listed in ``block_table_row``. Returns
        the first generated token (sampled per ``sampling`` =
        ``(temperature, top_k, top_p, seed)``; None = greedy). ``slot``:
        the sequence's slot, which the engine hands to a model with a
        per-slot state (``UNPAGED_LEAVES``) and to no other."""
        n = int(prompt.shape[0])
        bucket = _pick_bucket(self.prefill_buckets, n)
        if bucket is None:
            raise ValueError(
                f"prompt of {n} tokens exceeds the largest prefill "
                f"bucket ({self.prefill_buckets[-1]})")
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        row = self._row_operands(n, block_table_row, sampling, slot)
        with self._lock:
            tok, self._cache = self._prefill(
                self.params, self._cache, self._hand_off(
                    "prefill", self._prefill_layouts[bucket], ids=ids,
                    **row))
            out = int(tok)
        _host_transfer.labels(kind="prefill").inc(4)
        return out

    def prefill_chunk(self, chunk: np.ndarray, start: int,
                      total_len: int, block_table_row: np.ndarray,
                      sampling=None, slot: Optional[int] = None):
        """Dispatch ONE fixed-size chunk of a prompt (`start` = offset
        of ``chunk[0]`` in the sequence) WITHOUT a host sync. Every
        chunk call runs the same single executable regardless of prompt
        length (width = ``suffix_chunk_size``: the scheduling chunk when
        chunked prefill is on, the fixed suffix-feed width the prefix
        cache uses otherwise). Returns the sampled first generated
        token as a device scalar — meaningful only when this chunk
        contains the prompt's last real token, and ``int()`` of it
        blocks until the chunk has run: the caller takes it when it
        needs it, after it has given the device its next work."""
        C = self.suffix_chunk_size
        n = int(chunk.shape[0])
        if n < 1 or n > C:
            raise ValueError(f"chunk of {n} tokens (chunk size {C})")
        with span("llm.model.prefill_chunk"):
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = chunk
            row = self._row_operands(total_len, block_table_row,
                                     sampling, slot)
            with self._lock:
                tok, self._cache = self._prefill_chunked(
                    self.params, self._cache, self._hand_off(
                        "prefill_chunk", self._layouts["prefill_chunk"],
                        ids=ids, start=start, **row))
            _host_transfer.labels(kind="prefill").inc(4)
        return tok

    def copy_block(self, src: int, dst: int):
        """Device half of copy-on-write: duplicate block ``src`` into
        ``dst`` (K, V and int8 scale rows: every paged leaf, every
        layer) before a sequence writes into its forked copy. One tiny
        fixed-shape executable, compiled once."""
        with self._lock:
            self._cache = self._copy(self._cache, jnp.int32(src),
                                     jnp.int32(dst))

    # -- KV migration (docs/disaggregated_serving.md) ----------------------
    def export_kv_blocks(self, blocks) -> dict:
        """Host copies of the cache rows for ``blocks``, keyed like the
        cache pytree's PAGED leaves (``k``/``v`` and the int8 scale
        rows; a leaf that is not paged is no block's and stays), block
        axis at position 1 in the order given — exactly the bytes a decode
        replica's :meth:`import_kv_blocks` writes back, so a migrated
        sequence decodes from bit-identical cache state. Under int8 the
        wire pays 1 byte/row-element + the f32 scales (the on-device
        quantization IS the wire compression). The gather runs under
        the dispatch lock (the donated-cache arrays must not be
        consumed by a concurrent tick mid-read); the returned arrays
        are detached host copies."""
        idx = jnp.asarray(list(blocks), jnp.int32)
        with self._lock:
            parts = {name: arr[:, idx] for name, arr in
                     self._paged().items()}
        return {name: np.asarray(part) for name, part in parts.items()}

    def import_kv_blocks(self, blocks, data: dict, start: int = 0):
        """Write exported cache rows into local ``blocks``:
        ``data[name][:, start : start + len(blocks)]`` lands in block
        ``blocks[i]``, for every paged leaf — the adopting engine skips
        ``start`` leading
        blocks it aliased from its own prefix cache instead. Runs
        eagerly (plain scatters), so a pure-decode replica's traced
        executable census is untouched."""
        blocks = list(blocks)
        if not blocks:
            return
        missing = set(self._paged()) - set(data)
        if missing:
            raise ValueError(
                f"kv payload is missing cache planes {sorted(missing)} "
                f"(this cache is {self.kv_cache_dtype})")
        idx = jnp.asarray(blocks, jnp.int32)
        stop = start + len(blocks)
        with self._lock:
            for name, arr in self._paged().items():
                rows = jnp.asarray(np.asarray(data[name])[:, start:stop],
                                   arr.dtype)
                self._cache[name] = arr.at[:, idx].set(rows)

    def decode_step(self, prev_batch, host_tokens: np.ndarray,
                    use_host: np.ndarray, block_tables: np.ndarray,
                    positions: np.ndarray, sampling_lanes):
        """Dispatch ONE continuous-batching iteration WITHOUT a host
        sync: returns the on-device (S,) token batch, which the next
        tick accepts back as ``prev_batch`` (slots whose ``use_host``
        lane is set take ``host_tokens`` instead — fresh admissions).
        ``sampling_lanes`` = (temps, topks, topps, seeds) arrays, one
        lane per slot. The donated-cache chain sequences back-to-back
        dispatches on the device stream; only :meth:`read_tokens`
        blocks."""
        temps, topks, topps, seeds = sampling_lanes
        with self._lock:
            if prev_batch is None:
                prev_batch = self._zero_tokens
            elif isinstance(prev_batch, _TickBatch):
                prev_batch = prev_batch.tokens
            with span("llm.model.h2d"):
                operands = self._hand_off(
                    "decode", self._layouts["decode"],
                    host_tokens=host_tokens, use_host=use_host,
                    block_tables=block_tables, positions=positions,
                    temps=temps, topks=topks, topps=topps, seeds=seeds)
            with span("llm.model.launch"):
                out, self._cache, *aux = self._decode(
                    self.params, self._cache, prev_batch, operands)
            # the tick's device counts travel with its token batch and
            # are read with it (:meth:`read_tokens`); a tick that is
            # dropped unread takes them along
            return _TickBatch(out, aux) if aux else out

    def verify_step(self, tokens: np.ndarray,
                    block_tables: np.ndarray, positions: np.ndarray,
                    sampling_lanes):
        """Dispatch ONE speculative verify pass WITHOUT a host sync:
        ``tokens`` (num_slots, spec_k + 1) candidate rows per slot
        (row 0 = the incoming token, rows 1.. = drafted continuations,
        zero-padded), written through the block tables starting at each
        slot's ``positions`` entry. Returns the on-device
        (num_slots, spec_k + 1) batch of per-position canonical tokens
        — :meth:`read_tokens` blocks on it and the engine emits the
        longest accepted prefix. ONE fixed shape, compiled once."""
        tokens = np.asarray(tokens, np.int32)
        if self.spec_k < 1:
            raise RuntimeError("verify_step needs spec_k >= 1 at "
                               "model construction")
        if tokens.shape != (self.num_slots, self.spec_k + 1):
            raise ValueError(
                f"verify batch {tokens.shape} != the fixed "
                f"{(self.num_slots, self.spec_k + 1)} census shape")
        temps, topks, topps, seeds = sampling_lanes
        with self._lock:
            out, self._cache = self._verify(
                self.params, self._cache, self._hand_off(
                    "verify", self._layouts["verify"], tokens=tokens,
                    block_tables=block_tables, positions=positions,
                    temps=temps, topks=topks, topps=topps, seeds=seeds))
            return out

    def read_tokens(self, batch) -> np.ndarray:
        """Block until a dispatched tick's token batch is on the host.
        This is the ONLY device->host transfer of the decode hot path:
        slots x 1 int32 ids (the logits never leave the device)."""
        aux = None
        if isinstance(batch, _TickBatch):
            batch, aux = batch.tokens, batch.aux
        arr = np.asarray(batch)
        _host_transfer.labels(kind="tokens").inc(int(arr.nbytes))
        if aux is not None:
            # same executable as the tokens: already on its way, no
            # further wait for the device
            self._apply_tick_aux(jax.device_get(aux))
        return arr

    def _apply_tick_aux(self, aux):
        """What a decode tick counted on the device, now on the host:
        an architecture that returns ``aux`` from ``_layers`` adds it
        to its counters here (the readback thread calls this)."""

    def decode(self, tokens: np.ndarray, block_tables: np.ndarray,
               positions: np.ndarray, sampling_lanes=None) -> np.ndarray:
        """One decode tick for a caller outside the engine (the
        benchmark's tools drive the model alone with it): every slot's
        incoming token comes from the host, the sampled batch is read
        straight back."""
        S = self.num_slots
        if sampling_lanes is None:
            sampling_lanes = (np.zeros(S, np.float32),
                              np.zeros(S, np.int32),
                              np.ones(S, np.float32),
                              np.zeros(S, np.uint32))
        batch = self.decode_step(None, tokens, np.ones(S, bool),
                                 block_tables, positions, sampling_lanes)
        return self.read_tokens(batch)

    def donated_cache_leaves(self) -> int:
        """Leaves of the donated cache pytree — every one must appear
        in a compiled executable's ``input_output_alias`` table (the
        zoo-lint HLO-DONATION contract: a dropped donation doubles
        resident KV bytes and is invisible at runtime)."""
        return len(jax.tree_util.tree_leaves(self._cache))

    def compiled_hlo(self, which: str = "decode") -> Optional[str]:
        """Optimized HLO text of the ``decode`` or ``verify``
        executable, lowered with this model's exact census signature:
        weights, the donated cache, (decode) the previous tick's
        ``(slots,)`` tokens and the ONE packed operand buffer of
        ``_layouts[which]`` (and explicit shardings under tp=N) — the
        input to the zoo-lint donation / host-transfer / sharding
        checks. Returns None when the executable does not exist
        (``verify`` with spec_k=0)."""
        def avals(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
                tree)

        if which == "decode":
            args = (avals(self.params), avals(self._cache),
                    avals(self._zero_tokens),
                    self._layouts["decode"].aval())
            fn = self._decode
        elif which == "verify":
            if self.spec_k < 1:
                return None
            args = (avals(self.params), avals(self._cache),
                    self._layouts["verify"].aval())
            fn = self._verify
        else:
            raise ValueError(f"unknown executable {which!r} "
                             "(decode / verify)")
        return fn.lower(*args).compile().as_text()

    def compile_counts(self) -> dict:
        """Executable counts per compiled function — the no-recompile
        guarantee is asserted against these (decode must stay at 1
        after warmup; prefill at <= len(buckets); the chunked prefill
        at <= 1)."""
        def size(fn):
            try:
                return int(fn._cache_size())
            except Exception:  # noqa: BLE001 — private API moved
                return -1
        return {"decode": size(self._decode),
                "prefill": size(self._prefill),
                "prefill_chunk": size(self._prefill_chunked),
                "verify": size(self._verify),
                "copy_block": size(self._copy)}




class PagedLlamaModel(PagedDecoderModel):
    """Llama-shaped blocks under the skeleton: pre-norm, grouped-query
    attention over a per-head K/V paged cache
    ``(n_layer, num_blocks, n_kv_head, block, head_dim)`` (int8 with
    per-row scale planes ``(n_layer, num_blocks, 1, n_kv_head * block)``,
    bf16 or f32), a dense SwiGLU feed-forward, every block alike under
    one ``lax.scan`` whose ``xs`` are the stacked weights and the
    layer's index and whose carry holds the whole cache beside ``h``."""

    # -- the hooks -----------------------------------------------------------
    def _check_config(self):
        c = self.cfg
        if self.tp > 1:
            if c.n_kv_head % self.tp or c.n_head % self.tp:
                raise ValueError(
                    f"tensor-parallel serving shards the KV cache on the "
                    f"kv-head axis: n_kv_head ({c.n_kv_head}) and n_head "
                    f"({c.n_head}) must divide by the model-axis size "
                    f"({self.tp})")

    def _init_params(self, params, seed):
        if params is not None:
            return params
        return Llama(self.cfg, lm_head=True).build(
            jax.random.PRNGKey(seed), (None, self.prefill_buckets[-1]))

    def _weight_probe(self):
        return self.params["blocks"]["wq"]

    def _rope_dim(self) -> int:
        return self.cfg.head_dim

    def _init_cache(self):
        c = self.cfg
        shape = (c.n_block, self.num_blocks, c.n_kv_head,
                 self.block_size, c.head_dim)
        cache_np = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                    "int8": jnp.int8}[self.kv_cache_dtype]
        cache = {"k": jnp.zeros(shape, cache_np),
                 "v": jnp.zeros(shape, cache_np)}
        if self.kv_cache_dtype == "int8":
            # absmax scale per written cache ROW, stored block-indexed
            # right beside the K/V blocks (the block table routes
            # both): a block's scales are ONE row, head-major
            # (``[h * block + r]``). With 128 of them (8 kv heads x 16
            # rows) the TPU holds the plane as it is declared and the
            # kernels read it where it lies; a (..., n_kv, block) plane
            # it holds block-minor, and every call relays it for them.
            sshape = (c.n_block, self.num_blocks, 1,
                      c.n_kv_head * self.block_size)
            cache["ks"] = jnp.zeros(sshape, jnp.float32)
            cache["vs"] = jnp.zeros(sshape, jnp.float32)
        # K+V rows over every layer, plus the scale rows for int8
        item = {"f32": 4, "bf16": 2, "int8": 1}[self.kv_cache_dtype]
        per_token = (2 * c.n_block * c.n_kv_head * c.head_dim * item
                     + (2 * c.n_block * c.n_kv_head * 4
                        if self.kv_cache_dtype == "int8" else 0))
        return cache, per_token

    def _cache_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # K/V blocks shard on the kv-head axis; an int8 scale row is
        # head-major, so its shards are the same heads' scales
        # (docs/multichip.md: the tp=N layout quantization keeps)
        kv_sh = NamedSharding(
            self.mesh, P(None, None, "model", None, None))
        scale_sh = NamedSharding(
            self.mesh, P(None, None, None, "model"))
        cache_sh = {"k": kv_sh, "v": kv_sh}
        if self.kv_cache_dtype == "int8":
            cache_sh["ks"] = cache_sh["vs"] = scale_sh
        return cache_sh

    def _layers(self, params, cache, h, attend, at):
        """Every block alike under one scan over the stacked weights:
        norm, q/k/v, the step's ``attend`` (rope, append, attention),
        output projection, MLP. The cache pytree rides the CARRY beside
        ``h`` and is written and read at the layer's index where it
        lies, so the loop holds no slice, copy or write-back of a
        cache leaf."""
        c = self.cfg
        if self.kv_cache_dtype == "int8":
            # the same for every layer: worked out once, ahead of the loop
            at = dict(at, cells=self._scale_cells(at))

        def layer(carry, xs):
            h, cache = carry
            p, i = xs
            x = _rms_norm(h, p["attn_norm"], c.rms_eps)
            q, k, v = self._attn_proj(p, x)
            a, cache = attend(q, k, v, cache, i, at)
            h = h + _weight_dot(a, p["wo"])
            return (self._mlp(p, h), cache), None

        (h, cache), _ = jax.lax.scan(
            layer, (h, cache), (params["blocks"], jnp.arange(c.n_block)))
        return h, cache, ()

    def _attend_decode(self, q, k, v, cache, layer, at):
        # rope at each slot's own position (per-slot angle rows)
        q = _rope_rows(q, at["cos"], at["sin"])
        k = _rope_rows(k, at["cos"], at["sin"])
        # write this token's k/v through the block table (narrowed per
        # the cache dtype), THEN attend — the token attends to itself
        # like any other
        cache = self._append_rows(cache, layer, at, k, v)
        return self._paged_attend(q, cache, layer, at["tables"],
                                  at["pos"]), cache

    def _attend_bucket(self, q, k, v, cache, layer, at):
        c = self.cfg
        L = q.shape[1]
        q = apply_rope(q.transpose(0, 2, 1, 3), at["cos"], at["sin"])
        k = apply_rope(k.transpose(0, 2, 1, 3), at["cos"], at["sin"])
        v = v.transpose(0, 2, 1, 3)
        a = dot_product_attention(
            q, k, v, causal=True, impl=resolve_attention_impl("auto", L),
            mesh=self.mesh)
        a = a.transpose(0, 2, 1, 3).reshape(1, L, c.n_head * c.head_dim)
        return a, self._append_rows(cache, layer, at,
                                    k.transpose(0, 2, 1, 3)[0],
                                    v.transpose(0, 2, 1, 3)[0])

    def _attend_chunk(self, q, k, v, cache, layer, at):
        q = _rope_rows(q[0], at["cos"], at["sin"])[None]
        k = _rope_rows(k[0], at["cos"], at["sin"])
        cache = self._append_rows(cache, layer, at, k, v[0])
        # flash streams the table, dense gathers it
        return self._prefill_attend(q, cache, layer, at["tables"],
                                    at["pos"]), cache

    def _attend_verify(self, q, k, v, cache, layer, at):
        q = _rope_rows(q, at["cos"], at["sin"])
        k = _rope_rows(k, at["cos"], at["sin"])
        cache = self._append_rows(cache, layer, at, k, v)
        return self._prefill_attend(q, cache, layer, at["tables"],
                                    at["pos"]), cache

    # test/debug views of the cache arrays (the canonical home is the
    # donated ``self._cache`` pytree)
    @property
    def _kc(self):
        return self._cache["k"]

    @property
    def _vc(self):
        return self._cache["v"]

    # -- cache append and quantization (traced inside the executables) -----
    @jax.named_scope("zoo.kv_append")
    def _append_rows(self, cache, layer, at, k, v):
        """Write f32 K and V rows (..., n_kv, D) into the stacked cache
        at ``[layer, blk, :, off]``, quantizing per the cache dtype:
        int8 rows store ``clip(rint(x/scale))`` with their own absmax
        scale (a row is written once and never requantized, so
        bucketed, chunked and decode-appended writes of the same token
        are bit-identical cache bytes); bf16 narrows; f32 passes
        through. Every leading index is explicit, the head's too: the
        update window is then a row's D values, contiguous in the
        cache's own layout, and the scatter runs in place (with the
        head axis left as a window between two scattered ones the
        compiler relays the whole stacked array, once a layer)."""
        blk, off = at["blk"][..., None], at["off"][..., None]
        heads = jnp.arange(self.cfg.n_kv_head)
        cache = dict(cache)
        for name, x in (("k", k), ("v", v)):
            if self.kv_cache_dtype == "int8":
                s = absmax_scale(x, axis=-1, keepdims=True, xp=jnp)
                cache[name + "s"] = self._append_scales(
                    cache[name + "s"], layer, at["cells"], s[..., 0])
                x = narrow_int8(x, s, xp=jnp)
            cache[name] = cache[name].at[layer, blk, heads, off].set(
                x.astype(cache[name].dtype))
        return cache

    def _scale_cells(self, at):
        """Where a step's rows fall in the scale planes, block by
        block. The rows of a sequence sit at consecutive cache
        positions from its first, so R of them touch at most ``nb``
        table entries; for each: the block's id (the trash block 0
        where no real row falls in it), the row that lands in each of
        its ``block`` cells, and, laid out as the block's head-major
        scale row, whether one does. Shapes (B, nb), (B, nb, block),
        (B, nb, 1, n_kv * block)."""
        bs = self.block_size
        tables = at["tables"]                                 # (B, W)
        B, W = tables.shape
        pos = at["pos"].reshape(B, -1)                        # (B, R)
        R = pos.shape[1]
        first = pos[:, :1]
        nb = (R + bs - 2) // bs + 1
        col = first // bs + jnp.arange(nb)                    # (B, nb)
        row = (col * bs)[..., None] + jnp.arange(bs) - first[..., None]
        held = (row >= 0) & (row < R) & (col < W)[..., None]
        row = jnp.clip(row, 0, R - 1)
        if at["real"] is not None:
            held &= jnp.take_along_axis(
                at["real"].reshape(B, R), row.reshape(B, -1),
                axis=1).reshape(row.shape)
        ids = jnp.where(
            jnp.any(held, axis=-1),
            jnp.take_along_axis(tables, jnp.minimum(col, W - 1), axis=1),
            0)
        return ids, row, jnp.tile(held, (1, 1, self.cfg.n_kv_head))[
            :, :, None]

    def _append_scales(self, plane, layer, cells, s):
        """Write the rows' scales ``s`` (..., n_kv) into a scale plane
        ``(n_layer, num_blocks, 1, n_kv * block)``: read the blocks'
        rows the step's rows fall in, set their cells and write them
        back. The scatter's window is then a whole row of the plane,
        in the layout the kernels read; scattered a value at a time
        the compiler lays the plane out for the scatter and copies it
        back for the kernel, every layer. A live block has one writer a
        call (idle slots and pad rows all write the trash block, where
        any may win)."""
        ids, row, held = cells
        B, nb, bs = row.shape
        n_kv = s.shape[-1]
        s = s.reshape(B, -1, n_kv)                            # (B, R, n_kv)
        new = jnp.take_along_axis(
            s, row.reshape(B, nb * bs, 1), axis=1).reshape(
                B, nb, bs, n_kv).transpose(0, 1, 3, 2)        # head-major
        return plane.at[layer, ids].set(jnp.where(
            held, new.reshape(B, nb, 1, -1), plane[layer, ids]))

    def _widen_gather(self, cache, name, layer, idx):
        """Gather one layer's K or V blocks (``name``) by table ``idx``
        (B, W), widen to f32 (int8 rows times their scales; bf16/f32 a
        plain cast) and lay the result out token-major, (B, W * block,
        n_kv, D) — the dense reference for exactly what the flash
        kernel does in VMEM."""
        g = cache[name][layer, idx].astype(jnp.float32)
        B, W, n_kv, bs, D = g.shape
        if name + "s" in cache:
            g = g * cache[name + "s"][layer, idx].reshape(
                B, W, n_kv, bs, 1)
        return g.transpose(0, 1, 3, 2, 4).reshape(B, W * bs, n_kv, D)

    @jax.named_scope("zoo.attn_proj")
    def _attn_proj(self, p, x):
        """Shared q/k/v projection + head split for every executable."""
        c = self.cfg
        q = _weight_dot(x, p["wq"]).reshape(
            *x.shape[:-1], c.n_head, c.head_dim)
        k = _weight_dot(x, p["wk"]).reshape(
            *x.shape[:-1], c.n_kv_head, c.head_dim)
        v = _weight_dot(x, p["wv"]).reshape(
            *x.shape[:-1], c.n_kv_head, c.head_dim)
        return q, k, v

    @jax.named_scope("zoo.mlp")
    def _mlp(self, p, h):
        c = self.cfg
        x = _rms_norm(h, p["mlp_norm"], c.rms_eps)
        return h + _weight_dot(jax.nn.silu(_weight_dot(x, p["w_gate"]))
                               * _weight_dot(x, p["w_up"]), p["w_down"])

    def _on_model_axis(self, kernel, q, q_spec, cache, layer,
                       block_tables, positions, pos_spec, scale):
        """tp: run a paged kernel under ``shard_map`` over the mesh's
        ``model`` axis — each device streams ITS kv heads' shard of the
        stacked cache (and, under int8, their scale rows) against the
        query heads of those groups. Attention is head-local, so the
        only communication after the kernel is the row-parallel ``wo``
        matmul GSPMD already inserts."""
        from jax.sharding import PartitionSpec as P

        kv = P(None, None, "model", None, None)
        scales = tuple(cache[n] for n in ("ks", "vs") if n in cache)

        def local(q_, k_, v_, bt_, pos_, lay_, *sc):
            kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
            return kernel(q_, k_, v_, bt_, pos_, layer=lay_, scale=scale,
                          **kw)

        return jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(q_spec, kv, kv, P(None, None), pos_spec, P())
            + (P(None, None, None, "model"),) * len(scales),
            out_specs=q_spec, check_vma=False,
        )(q, cache["k"], cache["v"], block_tables, positions, layer,
          *scales)

    @jax.named_scope("zoo.paged_attend")
    def _paged_attend(self, q, cache, layer, block_tables, positions):
        """Single-query attention over the paged cache: (S, H, D) q
        against layer ``layer`` of the stacked (n_layer, blocks, n_kv,
        block, D) cache, routed by the block tables and masked to each
        slot's live length. Dispatches to the paged flash-decode Pallas
        kernel or the dense-gather reference per
        ``decode_attention_impl``; an int8 cache hands the kernel its
        scale planes (in-register dequant) and the dense path widens
        the gathered blocks the same way, so token parity between the
        two stays testable off-TPU."""
        c = self.cfg
        S = self.num_slots
        scale = 1.0 / float(c.head_dim) ** 0.5
        if self.decode_attention_impl == "flash":
            from zoo_tpu.ops.pallas.paged_decode import paged_flash_decode
            if self.mesh is None:
                return paged_flash_decode(
                    q, cache["k"], cache["v"], block_tables, positions,
                    layer=layer, k_scale=cache.get("ks"),
                    v_scale=cache.get("vs"),
                    scale=scale).reshape(S, c.n_head * c.head_dim)
            from jax.sharding import PartitionSpec as P
            out = self._on_model_axis(
                paged_flash_decode, q, P(None, "model", None), cache,
                layer, block_tables, positions, P(None), scale)
            return out.reshape(S, c.n_head * c.head_dim)
        # dense-gather reference: materialize cache[layer, block_table],
        # widen and mask — the PR 7 path, kept as the off-TPU fallback
        # and the token-identity anchor for the kernel
        ctx = self.max_blocks_per_seq * self.block_size
        live = jnp.arange(ctx)[None, :] <= positions[:, None]  # (S, ctx)
        keys = self._widen_gather(cache, "k", layer, block_tables)
        vals = self._widen_gather(cache, "v", layer, block_tables)
        return self._masked_gather_attention(q, keys, vals, live)

    @jax.named_scope("zoo.paged_attend")
    def _prefill_attend(self, q, cache, layer, block_tables, positions):
        """Chunk-of-rows attention over the resident paged cache:
        ``q`` (B, R, H, D) rows at cache ``positions`` (B, R), routed by
        per-sequence ``block_tables`` (B, W) — each row attends every
        resident column of layer ``layer`` ``<= its position`` (causal
        within the chunk plus everything earlier ticks wrote; the
        chunk's own K/V land in the cache before this runs). B is 1 for
        a prefill chunk and ``num_slots`` for a verify pass. Dispatches
        to the paged flash-prefill Pallas kernel or the dense gather per
        ``prefill_attention_impl``; both widen an int8 cache the same
        way, so token parity stays testable off-TPU. Returns
        (B, R, n_head * head_dim)."""
        c = self.cfg
        B, R = q.shape[0], q.shape[1]
        scale = 1.0 / float(c.head_dim) ** 0.5
        if self.prefill_attention_impl == "flash":
            from zoo_tpu.ops.pallas.paged_prefill import (
                paged_flash_prefill,
            )
            if self.mesh is None:
                out = paged_flash_prefill(
                    q, cache["k"], cache["v"], block_tables, positions,
                    layer=layer, k_scale=cache.get("ks"),
                    v_scale=cache.get("vs"), scale=scale)
                return out.reshape(B, R, c.n_head * c.head_dim)
            from jax.sharding import PartitionSpec as P
            out = self._on_model_axis(
                paged_flash_prefill, q, P(None, None, "model", None),
                cache, layer, block_tables, positions, P(None, None),
                scale)
            return out.reshape(B, R, c.n_head * c.head_dim)
        # dense anchor: materialize cache[layer, block_table] per
        # sequence, widen, broadcast over the rows, and run the shared
        # masked attention body — exactly what the kernel streams in VMEM
        ctx = self.max_blocks_per_seq * self.block_size
        kv = (B, ctx, c.n_kv_head, c.head_dim)
        keys = self._widen_gather(cache, "k", layer, block_tables)
        vals = self._widen_gather(cache, "v", layer, block_tables)
        keys = jnp.broadcast_to(keys[:, None], (B, R) + kv[1:]).reshape(
            (B * R,) + kv[1:])
        vals = jnp.broadcast_to(vals[:, None], (B, R) + kv[1:]).reshape(
            (B * R,) + kv[1:])
        live = jnp.arange(ctx)[None, :] <= positions.reshape(-1)[:, None]
        return self._masked_gather_attention(
            q.reshape(B * R, c.n_head, c.head_dim), keys, vals,
            live).reshape(B, R, c.n_head * c.head_dim)

    def _masked_gather_attention(self, q, keys, vals, live):
        """The shared dense paged-attention math: ``q`` (R, H, D) rows
        against cache-gathered ``keys``/``vals`` (R, ctx, n_kv, D)
        under a (R, ctx) liveness mask — GQA grouped, f32 scores.
        Rows are decode slots or prefill-chunk positions; both callers
        must stay numerically identical (chunked prefill is asserted
        byte-identical to the bucket path)."""
        c = self.cfg
        R = q.shape[0]
        group = c.n_head // c.n_kv_head
        scale = 1.0 / float(c.head_dim) ** 0.5
        qg = q.reshape(R, c.n_kv_head, group, c.head_dim)
        s = jnp.einsum("rkgd,rtkd->rkgt", qg, keys).astype(
            jnp.float32) * scale
        s = jnp.where(live[:, None, None, :], s,
                      jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
        return jnp.einsum("rkgt,rtkd->rkgd", probs, vals).reshape(
            R, c.n_head * c.head_dim)


def _rope_rows(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate (..., H, D) by per-ROW angles (..., D/2) — the
    decode-step variant of :func:`apply_rope`, where every row sits at
    its own position instead of sharing a 0..T ramp (the verify
    executable feeds (S, T, H, D) rows with (S, T, D/2) angles)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
