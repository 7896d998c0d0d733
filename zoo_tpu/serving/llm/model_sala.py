"""The ``minicpm_sala`` decoder under the serving skeleton: block-sparse
attention that selects pages INSIDE the paged cache, Lightning
linear-attention layers whose per-sequence state lives BESIDE it, two
kinds of per-sequence memory in the one donated cache pytree.

What differs from :class:`~zoo_tpu.serving.llm.model.PagedLlamaModel` is
what the skeleton's hooks name:

* **The cache** has paged leaves for the sparse layers alone — ``k`` and
  ``v`` ``(n_sparse, num_blocks, n_kv, block, D)`` and the compressed
  keys ``ck`` ``(n_sparse, num_blocks, n_kv * windows a page, D)``, all
  addressed through the sequence's ONE block table: a page is one
  selection block and holds the rows of the windows that START in it, so
  the allocator and ``copy_block`` need no second table — and one leaf
  that is NOT paged (``UNPAGED_LEAVES``): ``state`` ``(n_lightning,
  slots, heads, D, D)`` float32, a Lightning layer's recurrent state of
  every slot. ``kv_bytes_per_token`` and ``state_bytes_per_slot`` are
  what HBM holds.
* **The state's life.** A decode tick addresses it by row (a slot is a
  row of the tick); a slot that is not live in the tick (no table entry:
  empty, or still being prefilled) neither reads nor changes its state.
  ``prefill`` / ``prefill_chunk`` take the ``slot`` from the engine: the
  chunk that starts at 0 starts from a zero state, later chunks continue
  it, pad rows of a last chunk leave it untouched. Preemption keeps the
  engine's contract — blocks freed, prompt + generated re-prefilled from
  0, byte-identical resume — which here means the whole prefix is
  computed again: a state cannot be rebuilt from cached pages.
* **A sparse layer's attention.** Every tick appends the token's K/V
  row, and once every ``kernel_stride`` tokens the compressed key of the
  window that just filled (the mean of the cached keys of its
  ``kernel_size`` tokens, across a page boundary too). A query with
  fewer than ``dense_len`` resident tokens attends all of them; from
  there on it scores the compressed keys through the block table,
  softmax over the whole windows, summed over the group's heads, pooled
  to pages by the largest overlapping window, the first ``init_blocks``
  pages and the pages of the last ``window_size`` tokens forced, and
  takes the ``topk`` best: a per-(slot, K/V head) table of physical
  pages, which ``zoo_sparse_decode`` (a TPU; its plain-XLA twin
  elsewhere, no option chooses) attends with the token-level causal
  bound in the query's own page. Both branches go through the one
  table. A chunk of rows selects per row and attends the resident
  context in tiles under the rows' masks with an online softmax.
* **A Lightning layer** is ``zoo_lightning_decode`` for a tick (the
  state block read, decayed, updated and written where it lay) and the
  chunked scan for rows (:mod:`zoo_tpu.ops.pallas.lightning`).
* The muP scalings: embedding, residual branches, logits.

Not built, and refused at construction: a prefix cache over a state (a
shared prefix has no stored state), speculative decoding (a rejected
draft would need the state rolled back), an int8 cache, tensor-parallel
serving (``mesh=``), KV migration of a stateful sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from zoo_tpu.models.llm.llama import _rms_norm
from zoo_tpu.models.llm.minicpm_sala import (
    LIGHTNING,
    SALA_DOT_LEAVES,
    SPARSE,
    MiniCpmSalaConfig,
    init_minicpm_sala_params,
)
from zoo_tpu.obs.metrics import counter, gauge
from zoo_tpu.ops.pallas.lightning import (
    lightning_chunk,
    lightning_decode,
    lightning_decode_reference,
    write_state,
)
from zoo_tpu.ops.pallas.sparse_decode import (
    sparse_decode_reference,
    sparse_paged_decode,
)
from zoo_tpu.serving.llm.model import (
    PagedDecoderModel,
    _rope_rows,
    _weight_dot,
)

# context rows one step of the chunk-prefill loop attends
PREFILL_TILE_ROWS = 1024

_pages_attended = counter(
    "zoo_llm_sparse_pages_attended_total",
    "Pages the block-sparse decode attention read: selected pages "
    "summed over live slots, K/V heads and sparse layers of every "
    "decode tick")
_pages_resident = counter(
    "zoo_llm_sparse_pages_resident_total",
    "Pages those queries could have attended (resident pages of the "
    "live slots, times K/V heads and sparse layers)")
_state_steps = counter(
    "zoo_llm_state_steps_total",
    "Recurrent-state updates of the decode ticks (live slots x "
    "Lightning layers)")
_state_resets = counter(
    "zoo_llm_state_resets_total",
    "Prefill calls that started a sequence's recurrent state from zero "
    "(a chunk or a prompt that starts at position 0)")
_state_bytes = gauge(
    "zoo_llm_state_bytes",
    "HBM bytes of the per-slot recurrent state held beside the paged "
    "cache")


class PagedMiniCpmSalaModel(PagedDecoderModel):
    """MiniCPM-SALA-shaped weights + the paged sparse cache + the
    per-slot Lightning state + the serving executables (see the module
    docstring)."""

    DOT_LEAVES = SALA_DOT_LEAVES
    UNPAGED_LEAVES = ("state",)

    def __init__(self, config: MiniCpmSalaConfig, **kwargs):
        self.sparse_pages_attended = 0
        self.sparse_pages_resident = 0
        self.state_steps = 0
        self.state_resets = 0
        super().__init__(config, **kwargs)
        _state_bytes.set(float(self.state_bytes))

    # -- the hooks -----------------------------------------------------------
    def _check_config(self):
        c = self.cfg
        if self.mesh is not None:
            raise ValueError("tensor-parallel serving of the per-slot "
                             "state is not built (mesh= must be None)")
        if self.kv_cache_dtype == "int8":
            raise ValueError("an int8 cache under the block selection is "
                             "not built (kv_dtype bf16 / f32)")
        if self.spec_k > 0:
            raise ValueError(
                "speculative decoding over a recurrent state is not built "
                "(a rejected draft would need the state rolled back): "
                "spec_k must be 0")
        if self.block_size != c.sparse_block:
            raise ValueError(
                f"a page is one selection block: block_size "
                f"({self.block_size}) must be the configuration's "
                f"{c.sparse_block}")
        if not c.n_sparse or not c.n_lightning:
            raise ValueError("a stack without a sparse layer or without "
                             "a Lightning layer is not built")

    def _init_params(self, params, seed):
        if params is not None:
            return params
        return init_minicpm_sala_params(self.cfg, jax.random.PRNGKey(seed))

    def _weight_probe(self):
        return self.params["blocks"][0]["w_gate"]

    def _rope_dim(self) -> int:
        return self.cfg.lightning_head_dim

    def _init_cache(self):
        c = self.cfg
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[
            self.kv_cache_dtype]
        self.windows_per_page = c.sparse_block // c.kernel_stride
        # entries of a (slot, K/V head) table: the selection's, or the
        # dense branch's whole context
        self.select_width = min(self.max_blocks_per_seq, max(
            c.topk, -(-c.dense_len // self.block_size)))
        kv = (c.n_sparse, self.num_blocks, c.n_kv_head, self.block_size,
              c.head_dim)
        state = jnp.zeros((c.n_lightning, self.num_slots, c.lightning_heads,
                           c.lightning_head_dim, c.lightning_head_dim),
                          jnp.float32)
        cache = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                 "ck": jnp.zeros((c.n_sparse, self.num_blocks,
                                  c.n_kv_head * self.windows_per_page,
                                  c.head_dim), dtype),
                 "state": state}
        self.state_bytes = int(state.nbytes)
        self.state_bytes_per_slot = self.state_bytes // self.num_slots
        row = c.n_sparse * c.n_kv_head * c.head_dim \
            * jnp.dtype(dtype).itemsize
        # K and V rows, and a compressed key every ``kernel_stride``
        return cache, 2 * row + row // c.kernel_stride

    # -- host side: the state's resets ---------------------------------------
    def _note_reset(self, start: int):
        if start == 0:
            self.state_resets += 1
            _state_resets.inc()

    def prefill(self, prompt, block_table_row, sampling=None, slot=None):
        self._need_slot(slot)
        self._note_reset(0)
        return super().prefill(prompt, block_table_row, sampling, slot)

    def prefill_chunk(self, chunk, start, total_len, block_table_row,
                      sampling=None, slot=None):
        self._need_slot(slot)
        self._note_reset(int(start))
        return super().prefill_chunk(chunk, start, total_len,
                                     block_table_row, sampling, slot)

    def _need_slot(self, slot):
        if slot is None or not 0 <= int(slot) < self.num_slots:
            raise ValueError(
                f"a prefill of this model needs the sequence's slot "
                f"(0..{self.num_slots - 1}): its Lightning state lives "
                f"there (got {slot!r})")

    def _apply_tick_aux(self, aux):
        attended, resident, steps = (int(v) for v in aux[0])
        self.sparse_pages_attended += attended
        self.sparse_pages_resident += resident
        self.state_steps += steps
        _pages_attended.inc(attended)
        _pages_resident.inc(resident)
        _state_steps.inc(steps)

    # -- the layer stack -----------------------------------------------------
    def _layers(self, params, cache, h, attend, at):
        """The layers one after the other over per-layer leaves (they
        are of two kinds), each reading and writing the ONE cache at
        its own index among its kind."""
        c = self.cfg
        h = h.astype(jnp.float32) * c.scale_emb
        counts = jnp.zeros((3,), jnp.int32)
        index = {SPARSE: 0, LIGHTNING: 0}
        for p, kind in zip(params["blocks"], c.mixer_types):
            x = _rms_norm(h, p["attn_norm"], c.rms_eps)
            i = index[kind]
            index[kind] += 1
            slopes = params["slopes"][i] if kind == LIGHTNING else None
            a, cache, n = attend(p, x, cache, kind, i, slopes, at)
            h = self._mlp(p, h + c.residual_scale * a)
            counts = counts + n
        return h, cache, (counts,)

    @jax.named_scope("zoo.lm_head")
    def _lm_head(self, params, h):
        c = self.cfg
        h = _rms_norm(h, params["final_norm"], c.rms_eps)
        return _weight_dot(h, params["head"]) / c.logit_divisor

    @jax.named_scope("zoo.mlp")
    def _mlp(self, p, h):
        c = self.cfg
        x = _rms_norm(h, p["mlp_norm"], c.rms_eps)
        return h + c.residual_scale * _weight_dot(
            jax.nn.silu(_weight_dot(x, p["w_gate"]))
            * _weight_dot(x, p["w_up"]), p["w_down"])

    @jax.named_scope("zoo.attn_proj")
    def _proj(self, p, x, heads, kv_heads, dim):
        """q, k (RMSNorm on every head) and v of rows ``x`` (..., hidden)."""
        eps = self.cfg.rms_eps
        lead = x.shape[:-1]
        q = _rms_norm(_weight_dot(x, p["wq"]).reshape(*lead, heads, dim),
                      p["q_norm"], eps)
        k = _rms_norm(_weight_dot(x, p["wk"]).reshape(*lead, kv_heads, dim),
                      p["k_norm"], eps)
        v = _weight_dot(x, p["wv"]).reshape(*lead, kv_heads, dim)
        return q, k, v

    def _lightning_proj(self, p, x, at):
        """A Lightning layer's q and k (normed, roped at the rows' own
        positions) and v, (..., heads, D) each."""
        c = self.cfg
        q, k, v = self._proj(p, x, c.lightning_heads, c.lightning_heads,
                             c.lightning_head_dim)
        return (_rope_rows(q, at["cos"], at["sin"]),
                _rope_rows(k, at["cos"], at["sin"]), v)

    def _gated_out(self, p, x, o):
        """``o`` (..., heads * dim) under the sigmoid output gate of the
        layer's input, through the output projection."""
        return _weight_dot(o * jax.nn.sigmoid(_weight_dot(x, p["w_g"])),
                           p["wo"])

    # -- a decode tick --------------------------------------------------------
    def _attend_decode(self, p, x, cache, kind, i, slopes, at):
        c = self.cfg
        live = jnp.any(at["tables"] != 0, axis=-1)               # (S,)
        zero = jnp.zeros((), jnp.int32)
        if kind == LIGHTNING:
            q, k, v = self._lightning_proj(p, x, at)
            with jax.named_scope("zoo.lightning"):
                step = lightning_decode if self.decode_attention_impl \
                    == "flash" else lightning_decode_reference
                o, state = step(cache["state"], i, q, k, v,
                                jnp.exp(-slopes), live)
                o = _rms_norm(o, p["o_norm"], c.rms_eps)
            cache = dict(cache, state=state)
            counts = jnp.stack([zero, zero, jnp.sum(live, dtype=jnp.int32)])
            return self._gated_out(
                p, x, o.reshape(self.num_slots, -1)), cache, counts
        q, k, v = self._proj(p, x, c.n_head, c.n_kv_head, c.head_dim)
        cache = self._append_rows(cache, i, at["blk"], at["off"], k, v)
        cache = self._append_window(cache, i, at["tables"], at["pos"], live)
        q = q.reshape(self.num_slots, c.n_kv_head, -1, c.head_dim)
        with jax.named_scope("zoo.sparse_select"):
            tables, lens, n_live = self._select_pages(
                q, cache["ck"], i, at["tables"], at["pos"], live)
        with jax.named_scope("zoo.paged_attend"), \
                jax.named_scope("zoo.sparse_attend"):
            if self.decode_attention_impl == "flash":
                o = sparse_paged_decode(q, cache["k"], cache["v"], tables,
                                        lens, n_live, layer=i)
            else:
                o = sparse_decode_reference(q, cache["k"], cache["v"],
                                            tables, lens, layer=i)
        counts = jnp.stack([
            jnp.sum(n_live, dtype=jnp.int32),
            c.n_kv_head * jnp.sum(
                jnp.where(live, at["pos"] // self.block_size + 1, 0),
                dtype=jnp.int32), zero])
        return self._gated_out(
            p, x, o.reshape(self.num_slots, -1)), cache, counts

    # -- a chunk of rows (a prompt, or a part of one) --------------------------
    def _attend_rows(self, p, x, cache, kind, i, slopes, at):
        """Rows ``x`` (1, R, hidden) of ONE sequence at cache positions
        ``at["pos"]`` (1, R), the real ones first."""
        c = self.cfg
        x = x[0]
        pos, real = at["pos"][0], at["real"][0]
        zero = jnp.zeros((3,), jnp.int32)
        if kind == LIGHTNING:
            q, k, v = self._lightning_proj(p, x, at)
            with jax.named_scope("zoo.lightning"):
                state = cache["state"]
                # the chunk that starts at 0 starts from a zero state
                before = jnp.where(pos[0] == 0, 0.0, state[i, at["slot"]])
                o, after = lightning_chunk(
                    q, k, v, slopes, before,
                    jnp.sum(real, dtype=jnp.int32))
                if self.decode_attention_impl == "flash":
                    state = write_state(state, i, at["slot"], after)
                else:
                    state = state.at[i, at["slot"]].set(after)
                cache = dict(cache, state=state)
                o = _rms_norm(o, p["o_norm"], c.rms_eps)
            return self._gated_out(
                p, x, o.reshape(x.shape[0], -1))[None], cache, zero
        q, k, v = self._proj(p, x, c.n_head, c.n_kv_head, c.head_dim)
        cache = self._append_rows(cache, i, at["blk"], at["off"], k, v)
        table = at["tables"][0]
        cache = self._append_windows(cache, i, table, pos, real)
        q = q.reshape(x.shape[0], c.n_kv_head, -1, c.head_dim)
        with jax.named_scope("zoo.sparse_select"):
            pages = self._select_mask(q, cache["ck"], i, table, pos)
        with jax.named_scope("zoo.paged_attend"), \
                jax.named_scope("zoo.sparse_attend"):
            o = self._rows_attend(q, cache, i, table, pos, pages)
        return self._gated_out(p, x, o)[None], cache, zero

    # a prompt in its bucket is one chunk that starts at 0; a verify
    # pass is refused at construction (spec_k)
    _attend_chunk = _attend_bucket = _attend_verify = _attend_rows

    # -- the paged leaves ----------------------------------------------------
    @jax.named_scope("zoo.kv_append")
    def _append_rows(self, cache, layer, blk, off, k, v):
        """Write K and V rows (R, n_kv, D) at ``[layer, blk, :, off]``."""
        heads = jnp.arange(self.cfg.n_kv_head)
        cache = dict(cache)
        for name, rows in (("k", k), ("v", v)):
            cache[name] = cache[name].at[
                layer, blk[:, None], heads, off[:, None]].set(
                    rows.astype(cache[name].dtype))
        return cache

    def _window_rows(self, cache, layer, table, first):
        """The compressed keys of the windows that start at positions
        ``first`` (N,) of sequences whose block-table rows are ``table``
        (N, W) or (W,): the mean of the cached keys of each window's
        ``kernel_size`` tokens, read through the table. Returns the
        values (N, n_kv, D) and where each lands: the table entry of
        its page (N,) and its rows in that page (N, n_kv)."""
        c = self.cfg
        bs, W = self.block_size, self.max_blocks_per_seq
        tok = jnp.clip(first[:, None] + jnp.arange(c.kernel_size),
                       0, W * bs - 1)                             # (N, ks)
        blk = jnp.take_along_axis(table, tok // bs, axis=-1) \
            if table.ndim == 2 else table[tok // bs]
        # every leading index explicit, the head's too: the gather's
        # window is then a row's D values where they lie (a head axis
        # left as a slice between gathered ones makes the compiler
        # relay the whole K leaf for it, every sparse layer)
        rows = cache["k"][layer, blk[..., None], jnp.arange(c.n_kv_head),
                          (tok % bs)[..., None]]        # (N, ks, n_kv, D)
        mean = jnp.mean(rows.astype(jnp.float32), axis=1)
        j = first // c.kernel_stride
        col = jnp.arange(c.n_kv_head) * self.windows_per_page \
            + (j % self.windows_per_page)[:, None]
        return mean, jnp.minimum(j // self.windows_per_page, W - 1), col

    @jax.named_scope("zoo.ck_append")
    def _append_window(self, cache, layer, tables, pos, live):
        """A decode tick: the window that the token at ``pos`` fills,
        where it fills one (every ``kernel_stride`` tokens from
        ``kernel_size - 1`` on), for every live slot."""
        c = self.cfg
        first = pos - (c.kernel_size - 1)
        done = live & (first >= 0) & (first % c.kernel_stride == 0)
        first = jnp.maximum(first, 0)
        mean, entry, col = self._window_rows(cache, layer, tables, first)
        page = jnp.where(                       # else: the trash block
            done, jnp.take_along_axis(tables, entry[:, None], axis=1)[:, 0],
            0)
        ck = cache["ck"]
        return dict(cache, ck=ck.at[layer, page[:, None], col].set(
            mean.astype(ck.dtype)))

    @jax.named_scope("zoo.ck_append")
    def _append_windows(self, cache, layer, table, pos, real):
        """Rows of one sequence: every window whose LAST token is among
        the chunk's real rows (its first tokens may lie in an earlier
        chunk, on another page)."""
        c = self.cfg
        ks, st = c.kernel_size, c.kernel_stride
        n = pos.shape[0] // st + 2
        start = pos[0]
        last = start + jnp.sum(real, dtype=jnp.int32) - 1
        j = jnp.maximum(start - ks + st, 0) // st + jnp.arange(n)
        end = st * j + ks - 1
        done = (end >= start) & (end <= last)
        mean, entry, col = self._window_rows(cache, layer, table, st * j)
        page = jnp.where(done, table[entry], 0)
        ck = cache["ck"]
        return dict(cache, ck=ck.at[layer, page[:, None], col].set(
            mean.astype(ck.dtype)))

    # -- the selection -------------------------------------------------------
    def _page_scores(self, s, pos):
        """Every row's score of every page of its table, (R, n_kv, W),
        from its heads' scaled products with the compressed keys ``s``
        (R, n_kv, heads of a group, W * windows a page) in table order:
        softmax over the windows that lie wholly at or before the row's
        position, summed over the group's heads, a page scoring its
        largest overlapping window; forced pages +inf, pages after the
        row's own -inf. float32 throughout."""
        c = self.cfg
        bs, wpp, W = self.block_size, self.windows_per_page, \
            self.max_blocks_per_seq
        back = (c.kernel_size - 1) // c.kernel_stride
        R, G = s.shape[:2]
        whole = (c.kernel_stride * jnp.arange(W * wpp) + c.kernel_size - 1
                 )[None, :] <= pos[:, None]                      # (R, NW)
        s = jnp.where(whole[:, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        prob = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        total = jnp.where(whole[:, None, :], jnp.sum(prob, axis=2),
                          -jnp.inf)                              # (R, G, NW)
        score = jnp.max(total.reshape(R, G, W, wpp), axis=-1)
        total = jnp.pad(total, ((0, 0), (0, 0), (back, 0)),
                        constant_values=-jnp.inf)
        for d in range(1, back + 1):
            score = jnp.maximum(score, total[..., back - d::wpp][..., :W])
        b = jnp.arange(W)[None, :]
        own = (pos // bs)[:, None]
        near = (jnp.maximum(pos - c.window_size + 1, 0) // bs)[:, None]
        forced = (b < c.init_blocks) | (b >= near)
        score = jnp.where(forced[:, None, :], jnp.inf, score)
        return jnp.where((b <= own)[:, None, :], score, -jnp.inf)

    def _window_products(self, q, ck, layer, table):
        """``q`` (R, n_kv, Hg, D) against the compressed keys of the
        pages of ``table`` ((R, W): a row each; (W,): one sequence's),
        scaled: (R, n_kv, Hg, W * windows a page) float32."""
        c = self.cfg
        wpp = self.windows_per_page
        kc = ck[layer, table]
        kc = kc.reshape(kc.shape[:-2] + (c.n_kv_head, wpp, c.head_dim))
        spec = "rghd,rwgjd->rghwj" if table.ndim == 2 else \
            "rghd,wgjd->rghwj"
        s = jnp.einsum(spec, q.astype(kc.dtype), kc,
                       preferred_element_type=jnp.float32)
        return s.reshape(s.shape[:3] + (-1,)) * c.head_dim ** -0.5

    def _select_pages(self, q, ck, layer, tables, pos, live):
        """A decode tick's per-(slot, K/V head) tables: ``(physical
        pages (S, n_kv, E), rows attended of each (S, n_kv, E), live
        entries (S, n_kv))``, live entries first. A slot under
        ``dense_len`` lists every page it has; a slot that is not live
        lists none."""
        c = self.cfg
        bs, W, E = self.block_size, self.max_blocks_per_seq, \
            self.select_width
        G = c.n_kv_head
        score = self._page_scores(
            self._window_products(q, ck, layer, tables), pos)
        k = min(c.topk, W)
        top, idx = jax.lax.top_k(score, k)                       # (S, G, k)
        pad = ((0, 0), (0, 0), (0, E - k))
        chosen = jnp.pad(idx, pad)
        valid = jnp.pad(top > -jnp.inf, pad)
        own = pos // bs
        dense = (pos + 1 < c.dense_len)[:, None, None]
        every = jnp.broadcast_to(jnp.arange(E), chosen.shape)
        page = jnp.where(dense, every, chosen)
        valid = jnp.where(dense, every <= own[:, None, None], valid) \
            & live[:, None, None]
        physical = jnp.take_along_axis(
            jnp.broadcast_to(tables[:, None, :], (tables.shape[0], G, W)),
            jnp.minimum(page, W - 1), axis=-1)
        lens = jnp.where(page == own[:, None, None],
                         (pos % bs + 1)[:, None, None], bs)
        return (jnp.where(valid, physical, 0), jnp.where(valid, lens, 0),
                jnp.sum(valid, axis=-1, dtype=jnp.int32))

    def _select_mask(self, q, ck, layer, table, pos):
        """Rows of one sequence: which pages of its table each (row,
        K/V head) attends, (R, n_kv, W) bool."""
        c = self.cfg
        W = self.max_blocks_per_seq
        score = self._page_scores(
            self._window_products(q, ck, layer, table), pos)
        top, idx = jax.lax.top_k(score, min(c.topk, W))
        b = jnp.arange(W)
        chosen = jnp.any((idx[..., None] == b)
                         & (top > -jnp.inf)[..., None], axis=-2)
        every = (b[None, :] <= (pos // self.block_size)[:, None])[:, None, :]
        return jnp.where((pos + 1 < c.dense_len)[:, None, None],
                         every, chosen)

    def _rows_attend(self, q, cache, layer, table, pos, pages):
        """Causal attention of rows ``q`` (R, n_kv, Hg, D) over the
        pages ``pages`` (R, n_kv, W) of their sequence, a tile of the
        block table at a time with an online softmax; the loop stops
        after the tile that holds the largest position. Returns (R,
        heads * D)."""
        c = self.cfg
        R, G, Hg, D = q.shape
        bs, W = self.block_size, self.max_blocks_per_seq
        tb = max(1, min(PREFILL_TILE_ROWS // bs, W))       # pages a tile
        tiles = -(-W // tb)
        table = jnp.pad(table, (0, tiles * tb - W))
        pages = jnp.pad(pages, ((0, 0), (0, 0), (0, tiles * tb - W)))
        dt = cache["k"].dtype
        q = q.astype(dt)
        scale = c.head_dim ** -0.5

        def tile(j, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(table, j * tb, tb)

            def rows_of(name):
                # (tb, G, bs, D) -> (G, tb * bs, D)
                return cache[name][layer, ids].transpose(
                    1, 0, 2, 3).reshape(G, tb * bs, D)

            s = jnp.einsum("rghd,gtd->ghrt", q, rows_of("k"),
                           preferred_element_type=jnp.float32) * scale
            col = j * tb * bs + jnp.arange(tb * bs)
            live = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                pages, j * tb, tb, axis=2), bs, axis=-1) \
                & (col[None, :] <= pos[:, None])[:, None, :]     # (R, G, T)
            s = jnp.where(live.transpose(1, 0, 2)[:, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pr = jnp.exp(s - safe[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
            l = corr * l + jnp.sum(pr, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "ghrt,gtd->ghrd", pr.astype(dt), rows_of("v"),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((G, Hg, R), -jnp.inf, jnp.float32),
                jnp.zeros((G, Hg, R), jnp.float32),
                jnp.zeros((G, Hg, R, D), jnp.float32))
        n_live = jnp.minimum(jnp.max(pos) // (tb * bs) + 1, tiles)
        m, l, acc = jax.lax.fori_loop(0, n_live, tile, init)
        o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return o.transpose(2, 0, 1, 3).reshape(R, G * Hg * D)
