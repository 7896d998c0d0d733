"""The ``glm4_moe_lite`` decoder under the serving skeleton: a latent
(MLA) paged cache with an absorbed decode path and an expanded
chunk-prefill path, a leading dense layer before the expert
layers, and the dropless mixture of experts in the ONE decode executable.

What differs from :class:`~zoo_tpu.serving.llm.model.PagedLlamaModel`
is exactly what the skeleton's hooks name:

* **The cache** is ONE array ``lat`` of latent rows,
  ``(n_layer, num_blocks, block, row)`` in bf16 (or f32 off the TPU),
  ``row`` = ``kv_lora_rank + qk_rope_head_dim`` (576) rounded up to whole
  128-lane tiles (640): ``[c_kv ‖ k_rope ‖ 0]``. A 576-wide minor
  dimension makes the TPU pick a layout that scatters a block over the
  array (and the kernel then pays a whole-cache relayout copy a call),
  or pads it to 640 anyway; padding it ourselves costs the same bytes
  and keeps a block one contiguous slab. ``kv_bytes_per_token`` is what
  HBM holds (``n_layer * row * itemsize``).
* **The layer stack** is unrolled over per-layer leaves (``lead``: the
  ``first_k_dense`` dense layers, ``blocks``: a LIST of expert layers),
  because the grouped product is a custom call that would copy a
  layer's experts out of a stacked leaf on every call. Every layer
  writes a token's row into the one cache with a scatter at
  ``[layer, block, offset]`` and the kernel takes the whole array with
  the layer's index; nothing ever slices a layer's slab out of it, so
  the donated cache is updated in place.
* **Decode attention** is the absorbed form through the Pallas kernel
  ``zoo_mla_decode`` (:mod:`zoo_tpu.ops.pallas.mla_decode`) on the TPU
  and a dense gather elsewhere.
* **Chunk-prefill / verify attention** walks the resident context in
  tiles of the block table (a ``fori_loop`` whose trip count follows the
  live length) with an online softmax, in the expanded form: K and V
  of a tile are re-made from its latent rows. The absorbed form read
  1.3% slower a chunk at 8k of context on the chip (PERF.md section 6,
  PR 28) and is not built for rows.
* **The feed-forward half** is :func:`zoo_tpu.ops.moe.moe_ffn_dropless`;
  a decode tick's expert visits ride back with its tokens into
  ``zoo_llm_moe_expert_visits_total`` / ``zoo_llm_moe_rows_total``.

Not built: the multi-token-prediction layer, an int8 latent cache,
tensor-parallel serving (``mesh=``) of this architecture.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from zoo_tpu.models.llm.glm_moe_lite import (
    GLM_DOT_LEAVES,
    GlmMoeLiteConfig,
    init_glm_moe_lite_params,
)
from zoo_tpu.models.llm.llama import _rms_norm
from zoo_tpu.obs.metrics import counter
from zoo_tpu.ops.moe import moe_ffn_dropless
from zoo_tpu.serving.llm.model import (
    PagedDecoderModel,
    _rope_rows,
    _weight_dot,
)

_LANES = 128
# context rows one step of the chunk-prefill loop attends
PREFILL_TILE_ROWS = 1024

_moe_visits = counter(
    "zoo_llm_moe_expert_visits_total",
    "Experts that had at least one live token's row, summed over the "
    "expert layers of every decode tick (the weights a tick must read)")
_moe_rows = counter(
    "zoo_llm_moe_rows_total",
    "Live (token, choice) rows the expert layers of the decode ticks "
    "computed (live lanes x experts per token x expert layers)")


def _head_dot(x, w, spec):
    """An einsum against a per-head weight in the dtype it is held in,
    f32 out."""
    if w.dtype.itemsize < x.dtype.itemsize:
        x = x.astype(w.dtype)
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


class PagedGlmMoeLiteModel(PagedDecoderModel):
    """GLM-4.7-Flash-shaped weights + the latent paged cache + the
    serving executables (see the module docstring)."""

    DOT_LEAVES = GLM_DOT_LEAVES

    def __init__(self, config: GlmMoeLiteConfig, **kwargs):
        self.moe_expert_visits = 0
        self.moe_rows = 0
        super().__init__(config, **kwargs)

    # -- the hooks -----------------------------------------------------------
    def _check_config(self):
        if self.mesh is not None:
            raise ValueError("tensor-parallel serving of the latent "
                             "cache is not built (mesh= must be None)")
        if self.kv_cache_dtype == "int8":
            raise ValueError("an int8 latent cache is not built "
                             "(kv_dtype bf16 / f32)")

    def _init_params(self, params, seed):
        if params is not None:
            return params
        return init_glm_moe_lite_params(self.cfg, jax.random.PRNGKey(seed))

    def _weight_probe(self):
        return self.params["blocks"][0]["w_gate"]

    def _rope_dim(self) -> int:
        return self.cfg.qk_rope_head_dim

    def _init_cache(self):
        c = self.cfg
        self.latent_row = -(-c.latent_dim // _LANES) * _LANES
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[
            self.kv_cache_dtype]
        lat = jnp.zeros((c.n_block, self.num_blocks, self.block_size,
                         self.latent_row), dtype)
        return {"lat": lat}, c.n_block * self.latent_row * lat.dtype.itemsize

    def _layers(self, params, cache, h, attend, at):
        """Leading dense layers, then the expert layers, one after the
        other over per-layer leaves and ONE cache every layer reads and
        writes at its own index. No scan: a scan would hand each layer
        a slice of stacked expert weights, and the grouped product (a
        custom call, which cannot read through a slice) would copy
        1.2 GB of them a layer and call."""
        c = self.cfg
        h = h.astype(jnp.float32)
        lat = cache["lat"]
        counts = jnp.zeros((2,), jnp.int32)
        layers = [(p, self._dense_ffn) for p in params["lead"]] \
            + [(p, self._moe_ffn) for p in params["blocks"]]
        for i, (p, ffn) in enumerate(layers):
            x = _rms_norm(h, p["attn_norm"], c.rms_eps)
            a, lat = attend(p, x, lat, i, at)
            h, n = ffn(p, h + a, at)
            if n is not None:
                counts = counts + n
        return h, {"lat": lat}, (counts,)

    def _apply_tick_aux(self, aux):
        visits, rows = (int(v) for v in aux[0])
        self.moe_expert_visits += visits
        self.moe_rows += rows
        _moe_visits.inc(visits)
        _moe_rows.inc(rows)

    # -- the feed-forward halves ---------------------------------------------
    @jax.named_scope("zoo.mlp")
    def _dense_ffn(self, p, h, at):
        x = _rms_norm(h, p["mlp_norm"], self.cfg.rms_eps)
        return h + _weight_dot(
            jax.nn.silu(_weight_dot(x, p["w_gate"]))
            * _weight_dot(x, p["w_up"]), p["w_down"]), None

    @jax.named_scope("zoo.mlp")
    def _moe_ffn(self, p, h, at):
        c = self.cfg
        x = _rms_norm(h, p["mlp_norm"], c.rms_eps)
        live = at["real"]
        if live is None:
            # a decode tick: a slot with no table entry is idle
            live = jnp.any(at["tables"] != 0, axis=-1)
        y, counts = moe_ffn_dropless(
            p, x.reshape(-1, c.hidden), top_k=c.num_experts_per_tok,
            scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob,
            valid=jnp.broadcast_to(live, x.shape[:-1]))
        return h + y.reshape(h.shape), counts

    # -- the attention halves ------------------------------------------------
    @jax.named_scope("zoo.attn_proj")
    def _mla_proj(self, p, x, at):
        """``x`` (..., hidden) → the query's two halves, roped, and the
        token's cache row ``[c_kv ‖ k_rope ‖ 0]`` (..., row)."""
        c = self.cfg
        cq = _rms_norm(_weight_dot(x, p["w_qa"]), p["q_norm"], c.rms_eps)
        q = _weight_dot(cq, p["w_qb"]).reshape(
            *x.shape[:-1], c.n_head, c.qk_head_dim)
        q_nope = q[..., :c.qk_nope_head_dim]
        q_rope = _rope_rows(q[..., c.qk_nope_head_dim:], at["cos"],
                            at["sin"])
        kva = _weight_dot(x, p["w_kva"])
        ckv = _rms_norm(kva[..., :c.kv_lora_rank], p["kv_norm"], c.rms_eps)
        kr = _rope_rows(kva[..., None, c.kv_lora_rank:], at["cos"],
                        at["sin"])[..., 0, :]
        pad = jnp.zeros(x.shape[:-1] + (self.latent_row - c.latent_dim,),
                        ckv.dtype)
        return q_nope, q_rope, jnp.concatenate([ckv, kr, pad], axis=-1)

    @jax.named_scope("zoo.kv_append")
    def _append_row(self, lat, layer, at, row):
        """Write the rows through the block table at
        ``[layer, blk, off]``: a scatter into the carried cache."""
        return lat.at[layer, at["blk"], at["off"]].set(
            row.astype(lat.dtype))

    def _out_proj(self, p, o_lat):
        """Absorbed output: ``o_lat`` (..., H, rank) through ``W_uv`` and
        ``wo``."""
        c = self.cfg
        o = _head_dot(o_lat, p["w_uv"], "...hc,hcv->...hv")
        return _weight_dot(
            o.reshape(*o.shape[:-2], c.n_head * c.v_head_dim), p["wo"])

    def _attend_decode(self, p, x, lat, layer, at):
        c = self.cfg
        q_nope, q_rope, row = self._mla_proj(p, x, at)
        lat = self._append_row(lat, layer, at, row)
        with jax.named_scope("zoo.paged_attend"):
            q_lat = _head_dot(q_nope, p["w_uk"], "shd,hcd->shc")
            scale = float(c.qk_head_dim) ** -0.5
            if self.decode_attention_impl == "flash":
                from zoo_tpu.ops.pallas.mla_decode import mla_paged_decode
                o_lat = mla_paged_decode(
                    q_lat, q_rope, lat, layer, at["tables"], at["pos"],
                    scale=scale)
            else:
                from zoo_tpu.ops.pallas.mla_decode import (
                    mla_decode_reference,
                )
                o_lat = mla_decode_reference(
                    q_lat, q_rope, lat, layer, at["tables"], at["pos"],
                    scale=scale)
        return self._out_proj(p, o_lat), lat

    def _attend_rows(self, p, x, lat, layer, at):
        """Rows ``x`` (B, R, hidden) at cache positions ``at["pos"]``
        (B, R): append their rows, then attend everything resident
        ``<=`` each row's position."""
        q_nope, q_rope, row = self._mla_proj(p, x, at)
        lat = self._append_row(lat, layer, at, row.reshape(
            at["blk"].shape + row.shape[-1:]))
        with jax.named_scope("zoo.paged_attend"):
            a = self._rows_attend(p, q_nope, q_rope, lat, layer,
                                  at["tables"], at["pos"])
        return a, lat

    # a chunk, a verify pass and a whole prompt (one chunk starting at
    # 0) differ only in the shapes the skeleton hands over
    _attend_chunk = _attend_verify = _attend_bucket = _attend_rows

    def _rows_attend(self, p, q_nope, q_rope, lat, layer, tables, pos):
        """Causal attention of (B, R) rows over the resident latent
        rows of their sequences, a tile of the block table at a time
        with an online softmax; the loop stops after the tile that
        holds the largest position. Returns (B, R, hidden)."""
        c = self.cfg
        B, R, H = q_nope.shape[:3]
        rank, rope = c.kv_lora_rank, c.qk_rope_head_dim
        bs = self.block_size
        W = tables.shape[1]
        tb = max(1, min(PREFILL_TILE_ROWS // bs, W))    # blocks a tile
        tiles = -(-W // tb)
        tables = jnp.pad(tables, ((0, 0), (0, tiles * tb - W)))
        dt = lat.dtype
        scale = float(c.qk_head_dim) ** -0.5
        q_nope, q_rope = q_nope.astype(dt), q_rope.astype(dt)

        def tile(j, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(tables, j * tb, tb, axis=1)
            rows = lat[layer, ids].reshape(B, tb * bs, -1)
            ckv, kr = rows[..., :rank], rows[..., rank:rank + rope]
            keys = _head_dot(ckv, p["w_uk"], "btc,hcd->bthd").astype(dt)
            vals = _head_dot(ckv, p["w_uv"], "btc,hcv->bthv").astype(dt)
            s = jnp.einsum("brhd,bthd->bhrt", q_nope, keys,
                           preferred_element_type=jnp.float32)
            s = (s + jnp.einsum("brhd,btd->bhrt", q_rope, kr,
                                preferred_element_type=jnp.float32)
                 ) * scale
            col = j * tb * bs + jnp.arange(tb * bs)
            live = col[None, None, :] <= pos[:, :, None]    # (B, R, T)
            s = jnp.where(live[:, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pr = jnp.exp(s - safe[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
            l = corr * l + jnp.sum(pr, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhrt,bthv->bhrv", pr.astype(dt), vals,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((B, H, R), -jnp.inf, jnp.float32),
                jnp.zeros((B, H, R), jnp.float32),
                jnp.zeros((B, H, R, c.v_head_dim), jnp.float32))
        n_live = jnp.minimum(jnp.max(pos) // (tb * bs) + 1, tiles)
        m, l, acc = jax.lax.fori_loop(0, n_live, tile, init)
        o = (acc / jnp.where(l == 0.0, 1.0, l)[..., None]
             ).transpose(0, 2, 1, 3)                       # (B, R, H, v)
        return _weight_dot(o.reshape(B, R, H * c.v_head_dim), p["wo"])
