"""The Keye-VL-2.0 language model (a Qwen3-MoE decoder with a DeepSeek
Sparse Attention indexer) under the serving skeleton: a learned indexer
whose keys live beside the paged K/V, every query attending only the
``topk`` cached tokens it picks, and softmax-routed experts.

What differs from :class:`~zoo_tpu.serving.llm.model.PagedLlamaModel` is
what the skeleton's hooks name:

* **The cache** has two paged leaves under the ONE block table: ``kv``
  ``(n_layer, num_blocks, block, R, C)``, a token's K heads then V heads
  in one row (:func:`~zoo_tpu.ops.pallas.dsa_decode.kv_row_shape`: at 4
  K/V heads of 128 one ``(8, 128)`` tile, so a selected token is ONE
  copy), and ``ki`` ``(n_layer, num_blocks, block, 128)``, the
  indexer's key of every token (``index_head_dim`` values, the rest of
  the 128 lanes zero: with a narrower minor dimension the TPU lays the
  leaf out with its blocks minor and relays all of it for every gather
  by the table, once a layer and tick; it pads 64 lanes to 128 anyway).
  ``copy_block``, KV export and a preemption's re-prefill carry both
  like any paged leaf. ``kv_bytes_per_token`` is what they hold.
* **The layer**, on ``x = RMSNorm(h)``: q (``n_head``) and k, v
  (``n_kv_head``) of ``head_dim``, q and k under a per-head RMSNorm, rope
  over the whole head. The indexer: ``q_I = x W_qI`` (``index_heads`` of
  ``index_head_dim``), ``k_I = LayerNorm(x W_kI)`` (one head), both roped
  over the whole head, ``w = x W_w H_I^-1/2 d_I^-1/2``; a query at ``t``
  scores every cached ``s <= t`` by ``I[t, s] = sum_j w_j ReLU(q_I,j .
  k_I,s)`` in float32 and attends the ``index_topk`` of largest score
  (every one while it has fewer), the same set for all its heads. Then
  the experts: softmax routing over all of them, the ``experts_per_tok``
  largest renormalised (``norm_topk_prob``), SwiGLU experts through
  :func:`zoo_tpu.ops.moe.moe_ffn_dropless`; no shared expert.
* **A decode tick** appends the token's K/V row and index key, scores
  every resident token of each slot (``zoo.dsa_index``), takes the exact
  top ``index_topk`` (``zoo.dsa_select``: ``jax.lax.top_k`` on the
  float32 scores) and attends those rows with ``zoo_dsa_decode`` (a TPU;
  its plain-XLA twin elsewhere, no option chooses) under
  ``zoo.paged_attend`` / ``zoo.dsa_attend``. When no live slot holds
  more than ``index_topk`` tokens the selection is every token and the
  scoring is skipped (``lax.cond``): that list goes to the same kernel.
* **A chunk of rows** scores its rows against the resident context plus
  the chunk in tiles of the table, finds each row's exact
  ``index_topk``-th largest score by bisection over the float32 bits,
  marks the scores over it and, of those equal to it, the earliest (the
  set ``top_k`` names), and attends the context in tiles under those
  masks with an online softmax. Under ``index_topk`` rows the scoring is
  skipped likewise.
* Counters ``zoo_llm_dsa_tokens_scored_total`` /
  ``zoo_llm_dsa_tokens_selected_total`` (decode ticks, summed over
  layers and live slots) ride back with a tick's tokens, beside the
  expert visits and rows (``zoo_llm_moe_expert_visits_total`` /
  ``zoo_llm_moe_rows_total``, as the latent-attention model counts
  them).

Not built, and refused at construction: an int8 cache, tensor-parallel
serving (``mesh=``), speculative decoding (``spec_k``), a prefix cache
and KV migration (``REFUSED``: the engine reads it). The vision tower of
the published model is not built either: text only, so the MRoPE
sections carry one position and are ordinary rope.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from zoo_tpu.models.llm.llama import _rms_norm
from zoo_tpu.obs.metrics import counter
from zoo_tpu.ops.moe import moe_ffn_dropless
from zoo_tpu.ops.pallas.dsa_decode import (
    dsa_decode_reference,
    dsa_paged_decode,
    kv_row_shape,
)
from zoo_tpu.serving.llm.model import (
    PagedDecoderModel,
    _rope_rows,
    _weight_dot,
)
from zoo_tpu.serving.llm.model_mla import _moe_rows, _moe_visits

# context rows one step of the chunk-prefill loops scores or attends
PREFILL_TILE_ROWS = 1024

KEYE_DOT_LEAVES = ("wq", "wk", "wv", "wo", "iq", "ik", "iw",
                   "w_gate", "w_up", "w_down")

_tokens_scored = counter(
    "zoo_llm_dsa_tokens_scored_total",
    "Cached tokens the sparse-attention indexer scored in the decode "
    "ticks (resident tokens of the live slots, summed over layers; 0 "
    "for a tick whose every slot was under the selection's size)")
_tokens_selected = counter(
    "zoo_llm_dsa_tokens_selected_total",
    "Cached tokens the decode ticks' sparse attention attended (selected "
    "tokens of the live slots, summed over layers)")


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The language model's sizes under the serving skeleton's names."""

    vocab: int = 151936
    hidden: int = 2048
    n_block: int = 4
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_experts: int = 128
    moe_intermediate: int = 768
    experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    rope_theta: float = 1e7
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    tie_embeddings: bool = False

    @classmethod
    def from_published(cls, cfg: dict) -> "KeyeConfig":
        """From the published ``config.json`` keys (``KeyeVL2``'s text
        model): every layer an expert layer, one indexer key head."""
        sa = cfg["sa_config"]
        if cfg.get("decoder_sparse_step", 1) != 1 \
                or cfg.get("mlp_only_layers"):
            raise ValueError("dense feed-forward layers among the expert "
                             "layers are not built (decoder_sparse_step 1, "
                             "mlp_only_layers [])")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("an indexer with more than one key head is "
                             "not built")
        if cfg.get("tie_word_embeddings"):
            raise ValueError("a tied head is not built")
        return cls(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            n_block=cfg["num_hidden_layers"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            n_experts=cfg["num_experts"],
            moe_intermediate=cfg["moe_intermediate_size"],
            experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            index_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"])


def init_keye_params(cfg: KeyeConfig, key) -> dict:
    """Random float32 weights, std ``fan_in^-0.5``, gains 1, layer
    leaves in a list (the grouped product reads a layer's experts where
    they lie)."""
    c = cfg
    h, d = c.hidden, c.head_dim

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    blocks = []
    for kl in jax.random.split(k_layers, c.n_block):
        ks = jax.random.split(kl, 11)
        e, f = c.n_experts, c.moe_intermediate
        blocks.append({
            "attn_norm": jnp.ones((h,), jnp.float32),
            "wq": normal(ks[0], (h, c.n_head * d), h),
            "wk": normal(ks[1], (h, c.n_kv_head * d), h),
            "wv": normal(ks[2], (h, c.n_kv_head * d), h),
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
            "wo": normal(ks[3], (c.n_head * d, h), c.n_head * d),
            "iq": normal(ks[4], (h, c.index_heads * c.index_head_dim), h),
            "ik": normal(ks[5], (h, c.index_head_dim), h),
            "ik_norm": jnp.ones((c.index_head_dim,), jnp.float32),
            "ik_bias": jnp.zeros((c.index_head_dim,), jnp.float32),
            "iw": normal(ks[6], (h, c.index_heads), h),
            "mlp_norm": jnp.ones((h,), jnp.float32),
            "router": normal(ks[7], (h, e), h),
            "w_gate": normal(ks[8], (e, h, f), h),
            "w_up": normal(ks[9], (e, h, f), h),
            "w_down": normal(ks[10], (e, f, h), f)})
    return {"embed": normal(k_embed, (c.vocab, h), 1.0), "blocks": blocks,
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": normal(k_head, (h, c.vocab), h)}


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def _angles(pos, dim: int, theta: float):
    """cos and sin (N, dim / 2) of rope at positions ``pos`` (N,), the
    rotate-half convention's frequencies (``rope_frequencies``')."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def kth_largest(x, k: int):
    """The exact ``k``-th largest value of every row of ``x`` (R, N)
    float32 (no NaN; ``-inf`` where a row holds fewer than ``k`` finite
    values among ``-inf``): the largest threshold that ``k`` values of
    the row reach, found bit by bit over the values' order-preserving
    unsigned images, 32 counting passes over the rows."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(0x80000000)
    neg = (b & top) != 0
    u = jnp.where(neg, ~b, b | top)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1),
                                  (31 - i).astype(jnp.uint32))
        reach = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:1], jnp.uint32))
    back = jnp.where((t & top) != 0, t & jnp.uint32(0x7FFFFFFF), ~t)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


class PagedKeyeModel(PagedDecoderModel):
    """Keye-VL-2.0's language model + the paged K/V and index-key cache
    + the serving executables (see the module docstring)."""

    DOT_LEAVES = KEYE_DOT_LEAVES
    # what the engine must refuse for this model, and why
    REFUSED = {
        "prefix_cache": "a prefix cache under the token selection is not "
                        "built; prefix_cache must be off",
        "kv_migrate": "kv_migrate of the index-key cache is not built: "
                      "no handoff from, no adoption into this model, and "
                      "the replica role must be mixed"}

    def __init__(self, config: KeyeConfig, **kwargs):
        self.dsa_tokens_scored = 0
        self.dsa_tokens_selected = 0
        self.moe_expert_visits = 0
        self.moe_rows = 0
        super().__init__(config, **kwargs)

    # -- the hooks -----------------------------------------------------------
    def _check_config(self):
        c = self.cfg
        if self.mesh is not None:
            raise ValueError("tensor-parallel serving of the token "
                             "selection is not built (mesh= must be None)")
        if self.kv_cache_dtype == "int8":
            raise ValueError("an int8 cache under the token selection is "
                             "not built (kv_dtype bf16 / f32)")
        if self.spec_k > 0:
            raise ValueError("speculative decoding under the token "
                             "selection is not built: spec_k must be 0")
        if c.n_head % c.n_kv_head:
            raise ValueError("query heads must divide among the K/V heads")

    def _init_params(self, params, seed):
        if params is not None:
            return params
        return init_keye_params(self.cfg, jax.random.PRNGKey(seed))

    def _weight_probe(self):
        return self.params["blocks"][0]["w_gate"]

    def _rope_dim(self) -> int:
        return self.cfg.head_dim

    def _init_cache(self):
        c = self.cfg
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[
            self.kv_cache_dtype]
        self.kv_row = kv_row_shape(c.n_kv_head, c.head_dim)
        self.ki_width = -(-c.index_head_dim // 128) * 128
        lead = (c.n_block, self.num_blocks, self.block_size)
        cache = {"kv": jnp.zeros(lead + self.kv_row, dtype),
                 "ki": jnp.zeros(lead + (self.ki_width,), dtype)}
        per_token = c.n_block * (2 * c.n_kv_head * c.head_dim
                                 + self.ki_width) \
            * jnp.dtype(dtype).itemsize
        return cache, per_token

    def _apply_tick_aux(self, aux):
        scored, selected, visits, rows = (int(v) for v in aux[0])
        self.dsa_tokens_scored += scored
        self.dsa_tokens_selected += selected
        self.moe_expert_visits += visits
        self.moe_rows += rows
        _tokens_scored.inc(scored)
        _tokens_selected.inc(selected)
        _moe_visits.inc(visits)
        _moe_rows.inc(rows)

    # -- the layer stack -----------------------------------------------------
    def _layers(self, params, cache, h, attend, at):
        """The layers one after the other over per-layer leaves (the
        grouped product reads a layer's experts where they lie), each
        reading and writing the ONE cache at its own index."""
        c = self.cfg
        h = h.astype(jnp.float32)
        # the rows' rope angles, made from their positions here: the
        # skeleton's tables over the whole context (and the indexer's)
        # would be constants of the executable, 17 MB each as the TPU
        # lays them out, and the skeleton's go unused (and out of it)
        pos = at["pos"].reshape(-1)
        cos, sin = _angles(pos, c.head_dim, c.rope_theta)
        icos, isin = _angles(pos, c.index_head_dim, c.rope_theta)
        at = dict(at, cos=cos, sin=sin, icos=icos, isin=isin)
        counts = jnp.zeros((4,), jnp.int32)
        for i, p in enumerate(params["blocks"]):
            x = _rms_norm(h, p["attn_norm"], c.rms_eps)
            a, cache, n = attend(p, x, cache, i, at)
            h, visits = self._moe(p, h + a, at)
            counts = counts + jnp.concatenate([n, visits])
        return h, cache, (counts,)

    @jax.named_scope("zoo.mlp")
    def _moe(self, p, h, at):
        c = self.cfg
        x = _rms_norm(h, p["mlp_norm"], c.rms_eps)
        live = at["real"]
        if live is None:
            # a decode tick: a slot with no table entry is idle
            live = jnp.any(at["tables"] != 0, axis=-1)
        y, counts = moe_ffn_dropless(
            p, x.reshape(-1, c.hidden), top_k=c.experts_per_tok,
            norm_topk=c.norm_topk_prob,
            valid=jnp.broadcast_to(live, x.shape[:-1]), routing="softmax")
        return h + y.reshape(h.shape), counts

    @jax.named_scope("zoo.attn_proj")
    def _proj(self, p, x, at):
        """Rows ``x`` (N, hidden) → q (N, n_head, D), k and v (N, n_kv,
        D): q and k normed by head and roped at the rows' positions."""
        c = self.cfg
        n, d = x.shape[0], c.head_dim
        q = _rms_norm(_weight_dot(x, p["wq"]).reshape(n, c.n_head, d),
                      p["q_norm"], c.rms_eps)
        k = _rms_norm(_weight_dot(x, p["wk"]).reshape(n, c.n_kv_head, d),
                      p["k_norm"], c.rms_eps)
        v = _weight_dot(x, p["wv"]).reshape(n, c.n_kv_head, d)
        return (_rope_rows(q, at["cos"], at["sin"]),
                _rope_rows(k, at["cos"], at["sin"]), v)

    def _index_proj(self, p, x, at):
        """The indexer's rows: queries (N, index_heads, d_I) and key (N,
        d_I), roped, and the heads' weights (N, index_heads) with the
        scales ``H_I^-1/2 d_I^-1/2`` folded in. float32."""
        c = self.cfg
        n, di = x.shape[0], c.index_head_dim
        qi = _weight_dot(x, p["iq"]).reshape(n, c.index_heads, di)
        ki = _layer_norm(_weight_dot(x, p["ik"]), p["ik_norm"],
                         p["ik_bias"], c.rms_eps)
        w = _weight_dot(x, p["iw"]) * (c.index_heads * di) ** -0.5
        return (_rope_rows(qi, at["icos"], at["isin"]),
                _rope_rows(ki[:, None], at["icos"], at["isin"])[:, 0], w)

    @jax.named_scope("zoo.kv_append")
    def _append(self, cache, layer, blk, off, k, v, ki):
        """Write the rows' K and V (N, n_kv, D), one row a token, and
        index keys (N, d_I), zero-padded to the leaf's lanes, at
        ``[layer, blk, off]``."""
        n = k.shape[0]
        row = jnp.concatenate([k.reshape(n, -1), v.reshape(n, -1)], axis=-1)
        ki = jnp.pad(ki, ((0, 0), (0, self.ki_width - ki.shape[-1])))
        kv, kix = cache["kv"], cache["ki"]
        return {"kv": kv.at[layer, blk, off].set(
                    row.reshape((n,) + self.kv_row).astype(kv.dtype)),
                "ki": kix.at[layer, blk, off].set(ki.astype(kix.dtype))}

    def _scores(self, qi, w, keys):
        """``I = sum_j w_j ReLU(q_I,j . k_I)``: queries (N, H_I, d_I)
        against keys (..., T, lanes), the first d_I of them → (N, T)
        float32; the products read the cache's dtype and sum in
        float32."""
        keys = keys[..., :qi.shape[-1]]
        s = jnp.einsum("nhd,ntd->nht" if keys.ndim == 3 else "nhd,td->nht",
                       qi.astype(keys.dtype), keys,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)

    # -- a decode tick --------------------------------------------------------
    def _attend_decode(self, p, x, cache, layer, at):
        c = self.cfg
        S = self.num_slots
        q, k, v = self._proj(p, x, at)
        with jax.named_scope("zoo.dsa_index"):
            qi, ki, w = self._index_proj(p, x, at)
        cache = self._append(cache, layer, at["blk"], at["off"], k, v, ki)
        tables, pos = at["tables"], at["pos"]
        live = jnp.any(tables != 0, axis=-1)
        rows, n_live, scored = self._select_tokens(
            qi, w, cache["ki"], layer, tables, pos, live)
        with jax.named_scope("zoo.paged_attend"), \
                jax.named_scope("zoo.dsa_attend"):
            if self.decode_attention_impl == "flash":
                o = dsa_paged_decode(q, cache["kv"], rows, n_live,
                                     layer=layer)
            else:
                o = dsa_decode_reference(q, cache["kv"], rows, n_live,
                                         layer=layer)
        counts = jnp.stack([scored, jnp.sum(n_live, dtype=jnp.int32)])
        return _weight_dot(o.reshape(S, c.n_head * c.head_dim),
                           p["wo"]), cache, counts

    def _select_tokens(self, qi, w, ki, layer, tables, pos, live):
        """A tick's lists: physical rows (S, K) with the live ones first,
        how many are live (S,), and how many tokens were scored. Every
        token while no live slot holds more than K, else the K of
        largest score (``jax.lax.top_k``: exact)."""
        bs = self.block_size
        ctx = tables.shape[1] * bs
        K = min(self.cfg.index_topk, ctx)
        resident = jnp.where(live, pos + 1, 0)

        def every(_):
            idx = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32),
                                   (tables.shape[0], K))
            return idx, jnp.minimum(resident, K), jnp.zeros((), jnp.int32)

        def best(_):
            with jax.named_scope("zoo.dsa_index"):
                keys = ki[layer, tables].reshape(tables.shape[0], ctx, -1)
                score = self._scores(qi, w, keys)                # (S, ctx)
                score = jnp.where(
                    jnp.arange(ctx)[None, :] <= pos[:, None], score,
                    -jnp.inf)
            with jax.named_scope("zoo.dsa_select"):
                top, idx = jax.lax.top_k(score, K)
                n = jnp.where(live, jnp.sum(top > -jnp.inf, axis=-1,
                                            dtype=jnp.int32), 0)
            return idx.astype(jnp.int32), n, jnp.sum(resident,
                                                      dtype=jnp.int32)

        idx, n, scored = jax.lax.cond(jnp.all(resident <= K), every, best,
                                      None)
        rows = jnp.take_along_axis(tables, idx // bs, axis=1) * bs \
            + idx % bs
        return rows, n, scored

    # -- a chunk of rows (a prompt, or a part of one) --------------------------
    def _attend_rows(self, p, x, cache, layer, at):
        """Rows ``x`` (1, R, hidden) of ONE sequence at cache positions
        ``at["pos"]`` (1, R), the real ones first."""
        c = self.cfg
        x = x[0]
        pos, table = at["pos"][0], at["tables"][0]
        q, k, v = self._proj(p, x, at)
        with jax.named_scope("zoo.dsa_index"):
            qi, ki, w = self._index_proj(p, x, at)
        cache = self._append(cache, layer, at["blk"], at["off"], k, v, ki)
        picked = self._select_mask(qi, w, cache["ki"], layer, table, pos)
        with jax.named_scope("zoo.paged_attend"), \
                jax.named_scope("zoo.dsa_attend"):
            o = self._rows_attend(q, cache["kv"], layer, table, pos, picked)
        return _weight_dot(o, p["wo"])[None], cache, \
            jnp.zeros((2,), jnp.int32)

    # a prompt in its bucket is one chunk that starts at 0; a verify
    # pass is refused at construction (spec_k)
    _attend_chunk = _attend_bucket = _attend_verify = _attend_rows

    def _tiles(self, table):
        """(table padded to whole tiles, table entries a tile, tiles)."""
        bs, W = self.block_size, table.shape[0]
        tb = max(1, min(PREFILL_TILE_ROWS // bs, W))
        tiles = -(-W // tb)
        return jnp.pad(table, (0, tiles * tb - W)), tb, tiles

    def _select_mask(self, qi, w, ki, layer, table, pos):
        """Rows of one sequence: which positions of its table each row
        attends, (R, tiles * tile) bool, before the causal bound: the K
        of largest score, of equal scores the earlier (as ``top_k``
        names them), by the row's exact K-th largest score; every
        position while none has more than K (then nothing is scored)."""
        bs = self.block_size
        table, tb, tiles = self._tiles(table)
        T = tb * bs
        K = min(self.cfg.index_topk, table.shape[0] * bs)
        R = pos.shape[0]

        def every(_):
            return jnp.ones((R, tiles * T), bool)

        def best(_):
            with jax.named_scope("zoo.dsa_index"):
                def tile(j, score):
                    ids = jax.lax.dynamic_slice_in_dim(table, j * tb, tb)
                    s = self._scores(qi, w, ki[layer, ids].reshape(T, -1))
                    col = j * T + jnp.arange(T)
                    s = jnp.where(col[None, :] <= pos[:, None], s, -jnp.inf)
                    return jax.lax.dynamic_update_slice_in_dim(
                        score, s, j * T, axis=1)

                n_live = jnp.minimum(jnp.max(pos) // T + 1, tiles)
                score = jax.lax.fori_loop(
                    0, n_live, tile,
                    jnp.full((R, tiles * T), -jnp.inf, jnp.float32))
            with jax.named_scope("zoo.dsa_select"):
                kth = kth_largest(score, K)[:, None]
                over = score > kth
                tied = score == kth
                room = K - jnp.sum(over, axis=1, keepdims=True,
                                   dtype=jnp.int32)
                return over | (tied & (jnp.cumsum(tied, axis=1,
                                                  dtype=jnp.int32) <= room))

        return jax.lax.cond(jnp.max(pos) + 1 <= K, every, best, None)

    def _rows_attend(self, q, kv, layer, table, pos, picked):
        """Causal attention of rows ``q`` (R, n_head, D) over the tokens
        of their sequence that ``picked`` (R, positions) marks, a tile
        of the block table at a time with an online softmax; the loop
        stops after the tile that holds the largest position. Returns
        (R, n_head * D)."""
        c = self.cfg
        R, H, D = q.shape
        G = c.n_kv_head
        bs = self.block_size
        table, tb, tiles = self._tiles(table)
        T = tb * bs
        dt = kv.dtype
        q = q.reshape(R, G, H // G, D).astype(dt)
        scale = D ** -0.5

        def tile(j, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(table, j * tb, tb)
            tok = kv[layer, ids].reshape(T, -1)
            keys = tok[:, :G * D].reshape(T, G, D)
            vals = tok[:, G * D:].reshape(T, G, D)
            s = jnp.einsum("rghd,tgd->ghrt", q, keys,
                           preferred_element_type=jnp.float32) * scale
            col = j * T + jnp.arange(T)
            live = jax.lax.dynamic_slice_in_dim(picked, j * T, T, axis=1) \
                & (col[None, :] <= pos[:, None])                 # (R, T)
            s = jnp.where(live[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pr = jnp.exp(s - safe[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
            l = corr * l + jnp.sum(pr, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "ghrt,tgd->ghrd", pr.astype(dt), vals,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        Hg = H // G
        init = (jnp.full((G, Hg, R), -jnp.inf, jnp.float32),
                jnp.zeros((G, Hg, R), jnp.float32),
                jnp.zeros((G, Hg, R, D), jnp.float32))
        n_live = jnp.minimum(jnp.max(pos) // T + 1, tiles)
        m, l, acc = jax.lax.fori_loop(0, n_live, tile, init)
        o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return o.transpose(2, 0, 1, 3).reshape(R, H * D)
