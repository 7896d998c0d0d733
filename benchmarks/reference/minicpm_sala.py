"""Plain reference of the ``minicpm_sala`` decoder (MiniCPM-SALA): block
sparse (InfLLM-V2) attention layers among Lightning linear-attention
layers, the MiniCPM family's muP scalings.

The forward pass as straightforward ``jax.numpy`` in float32 with every
matrix product at ``Precision.HIGHEST``: no kernels, no cache, no state,
no batching. It imports nothing of the program under test. The equations
(``config.json`` of ``openbmb/MiniCPM-SALA``; what the file does not give
is listed under ``assumed`` in the benchmark's configuration and marked
ASSUMED here), with ``x = RMSNorm(h)`` (gain, eps ``rms_norm_eps``), no
biases, ``L`` the PUBLISHED depth (``published.num_hidden_layers``, 32:
a cut of the depth keeps the residual scale of the whole model):

* ``h = scale_emb * E[token]``; every layer ``h += (scale_depth / sqrt(L))
  * Mixer(x)`` and then ``h += (scale_depth / sqrt(L)) * down(silu(gate u)
  * up u)``, ``u = RMSNorm(h)``; ``logits = head(RMSNorm(h)) /
  (hidden_size / dim_model_base)``. ``mup_denominator`` and ``rand_init``
  touch initialisation only and are not read.
* ``lightning-attn``: ``q, k, v`` projected to ``lightning_nh`` heads of
  ``lightning_head_dim``; ``q, k <- RMSNorm_head(q), RMSNorm_head(k)``
  (``qk_norm``; gains of head width: ASSUMED); rope on the whole head of
  ``q`` and ``k`` (rotate-half, ``rope_theta``); per head ``o_t =
  d^-0.5 * sum_{s<=t} lambda^(t-s) (q_t . k_s) v_s`` with ``lambda =
  exp(-slope)`` (the O(n^2) decay-masked form of the recurrence ``S_t =
  lambda S_{t-1} + k_t^T v_t``, ``o_t = d^-0.5 q_t S_t``); ``o <-
  RMSNorm_head(o)`` (``use_output_norm``); ``o <- o * sigmoid(x W_g)``
  (``use_output_gate``); out ``o W_o``. The slopes are a LEAF of the
  weight tree, ``(lightning layer, head)``, which :func:`make_params`
  fills with the Lightning Attention code's ALiBi slopes ``2^(-8 (h+1) /
  heads)`` (ASSUMED: the config gives no schedule).
* ``minicpm4``: ``q`` to ``num_attention_heads`` heads, ``k, v`` to
  ``num_key_value_heads``, of ``head_dim``; ``q, k <-
  RMSNorm_head(q), RMSNorm_head(k)``; NO rope (``attn_use_rope`` false);
  scale ``head_dim^-0.5``. A query at position ``t`` with ``t + 1 <
  dense_len`` resident tokens attends all of them (causal softmax).
  Otherwise, for each K/V head ``g``: compressed keys ``Kc_j =
  mean(k[stride*j : stride*j + kernel_size])`` for every window that lies
  wholly at or before ``t``; ``p_{h,j} = softmax_j(q_h . Kc_j *
  head_dim^-0.5)`` for each query head of the group, ``P_j = sum_h
  p_{h,j}``; block ``b`` (``block_size`` tokens) scores the largest ``P_j``
  over the windows that overlap it; the first ``init_blocks`` blocks and
  the blocks that hold the last ``window_size`` tokens score +inf; the
  ``topk`` highest-scoring blocks at or before the query's own, forced
  ones counted among them, are selected, one set for the whole group;
  causal softmax attention over the tokens of the selected blocks alone.
  Then ``o <- o * sigmoid(x W_g)`` (``attn_use_output_gate``), out ``o
  W_o``. The selection's sizes are not in the config; they are the
  family's (MiniCPM4's published ``sparse_config``) and stand in the
  configuration under ``sparse_config`` (ASSUMED, as are: one compression
  stage, forced blocks inside the ``topk``, a sigmoid gate of width
  ``heads * head_dim``, no length-dependent scaling of the logits).

Here the sparse layer is a DENSE attention under the mask the selection
gives, the selection recomputed for every query row. The weights are held
as their bfloat16-exact values in bfloat16 (the float32 tree of the
benchmark's cut, 15.7 GB, fits beside nothing) and widened a layer at a
time; query rows go in blocks so that no ``rows x T x heads`` tensor of the
whole sequence exists.

``lower`` is the control of the comparison, one precision below what the
configuration states: every matrix-product operand rounded to float8
(e4m3, one absmax scale per row of the contraction), the selection's
scores and probabilities rounded to bfloat16, and the Lightning layer in
its chunked form with the carried state rounded to bfloat16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0
SPARSE_ROWS = 128         # query rows of a sparse layer attended at once
LIGHT_ROWS = 512          # rows (and keys) of a Lightning block
FFN_ROWS = 1024           # rows of a feed-forward block
SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"
# ASSUMED data (the configuration's ``assumed.weights``). Random q and k
# at gain 1 give products of std 1: the softmax over the 4,096 selected
# tokens is then nearly uniform, the mixer's output the mean of as many
# random v rows, 3% of a Lightning layer's, and no comparison of logits
# can see the selection or the attention. A trained attention is peaked:
# at a q gain of 3 the products have std 3 and a few tokens of the 4,096
# carry the sum, so the sparse mixer weighs in the residual stream as a
# Lightning layer does.
SPARSE_Q_GAIN = 3.0

_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "lightning_nh",
         "lightning_nkv", "lightning_head_dim", "num_hidden_layers",
         "vocab_size", "rms_norm_eps", "rope_theta", "scale_emb",
         "scale_depth", "dim_model_base")
_SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size",
                "init_blocks", "window_size", "topk", "dense_len")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (``PRNGKey`` alone
    takes 32 signed bits without x64)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _cfg_items(cfg: dict):
    """The keys the forward pass reads, hashable (a jit's static
    argument); refuses what is not built."""
    if cfg.get("lightning_nkv") != cfg.get("lightning_nh"):
        raise ValueError("lightning_nkv must equal lightning_nh")
    if cfg.get("attn_use_rope") or not cfg.get("lightning_use_rope", True):
        raise ValueError("rope is on the Lightning layers alone")
    for k in ("qk_norm", "use_output_gate", "use_output_norm",
              "attn_use_output_gate"):
        if not cfg.get(k, True):
            raise ValueError(f"{k} false is not built")
    if len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("mixer_types must name every layer")
    sp = cfg["sparse_config"]
    if sp["block_size"] % sp["kernel_stride"] \
            or sp["kernel_size"] % sp["kernel_stride"]:
        raise ValueError("a block and a window are whole strides")
    return (tuple((k, cfg[k]) for k in _KEYS)
            + tuple((k, sp[k]) for k in _SPARSE_KEYS)
            + (("depth", cfg.get("published", {}).get(
                "num_hidden_layers", cfg["num_hidden_layers"])),))


def _normal_bf16(key, shape, std):
    """Normal weights of std ``std``, rounded to bfloat16."""
    return (jax.random.normal(key, shape, jnp.float32)
            * std).astype(jnp.bfloat16)


def mixer_shapes(cfg: dict, kind: str) -> dict:
    """One layer's matrices (``o_gate``: the output gate)."""
    h = cfg["hidden_size"]
    if kind == SPARSE:
        nq = cfg["num_attention_heads"] * cfg["head_dim"]
        nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    else:
        nq = nkv = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return {"q": (h, nq), "k": (h, nkv), "v": (h, nkv),
            "o_gate": (h, nq), "o": (nq, h)}


def ffn_shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return {"gate": (h, i), "up": (h, i), "down": (i, h)}


def lightning_slopes(heads: int):
    """The Lightning Attention code's ALiBi slopes, ``2^(-8 (h+1) /
    heads)``: what :func:`make_params` fills the slopes' leaf with."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                   / heads)


def _layer(key, cfg, kind):
    h = cfg["hidden_size"]
    d = cfg["head_dim"] if kind == SPARSE else cfg["lightning_head_dim"]
    table = {**mixer_shapes(cfg, kind), **ffn_shapes(cfg)}
    ks = jax.random.split(key, len(table))
    out = {name: _normal_bf16(k, shape, shape[0] ** -0.5)
           for k, (name, shape) in zip(ks, sorted(table.items()))}
    out["input_norm"] = jnp.ones((h,), jnp.float32)
    out["post_norm"] = jnp.ones((h,), jnp.float32)
    out["q_norm"] = jnp.full(
        (d,), SPARSE_Q_GAIN if kind == SPARSE else 1.0, jnp.float32)
    out["k_norm"] = jnp.ones((d,), jnp.float32)
    if kind == LIGHTNING:
        out["o_norm"] = jnp.ones((d,), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_layer(key, cfg_items, kind):
    return _layer(key, dict(cfg_items), kind)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_ends(key, cfg_items):
    cfg = dict(cfg_items)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    k_embed, k_head = jax.random.split(key)
    # std 1 / scale_emb: ``scale_emb * E[token]`` has unit components,
    # so the residual stream is the layers' as much as the embedding's
    # (at std 1 it is 12 a component and every branch a hundredth of it)
    return {"embed": _normal_bf16(k_embed, (v, h), 1.0 / cfg["scale_emb"]),
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": _normal_bf16(k_head, (h, v), h ** -0.5)}


def make_params(seed: int, cfg: dict):
    """All weights on the device from the seed, a jitted call a layer:
    ``layers`` the list of the layers in the order of ``mixer_types``, a
    dict of leaves each; matrices in bfloat16, gains float32; ``slopes``
    (Lightning layer, head) float32."""
    items = _cfg_items(cfg)
    kinds = cfg["mixer_types"]
    k_ends, k_layers = jax.random.split(seed_key(seed))
    keys = jax.random.split(k_layers, len(kinds))
    out = _make_ends(k_ends, items)
    out["layers"] = [_make_layer(keys[i], items, kind)
                     for i, kind in enumerate(kinds)]
    n_light = sum(1 for kind in kinds if kind == LIGHTNING)
    out["slopes"] = jnp.tile(lightning_slopes(cfg["lightning_nh"])[None],
                             (n_light, 1))
    return out


# ------------------------------------------------------------------ pieces

def _f8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    """Round to bfloat16 and back (not a pair of converts: the TPU
    compiler may keep the excess precision and drop those)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, b, lower: bool):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``,
    ``b`` widened to float32 here."""
    b = b.astype(jnp.float32)
    if lower:
        a, b = _f8(a, -1), _f8(b, 0)
    return jnp.matmul(a, b, precision=_HI)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _rope(x, pos, theta):
    """Rotate (T, heads, D) rows at positions ``pos`` (T,): rotate-half
    convention over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _divisor(t: int, most: int) -> int:
    """The largest block of rows, up to ``most``, that divides ``t``."""
    return next(b for b in range(min(most, t), 0, -1) if t % b == 0)


def _row_blocks(fn, t: int, rb: int):
    """``fn(first row of the block)`` for every block of ``rb`` rows,
    the results laid end to end."""
    out = jax.lax.map(lambda i: fn(i * rb), jnp.arange(t // rb))
    return out.reshape((t,) + out.shape[2:])


# ------------------------------------------------------- the sparse layer

def compressed_keys(k, sp: dict):
    """``Kc_j = mean(k[stride*j : stride*j + kernel_size])`` for every
    whole window of ``k`` (T, groups, D): (windows, groups, D)."""
    t = k.shape[0]
    ks, st = sp["kernel_size"], sp["kernel_stride"]
    nw = max(0, (t - ks) // st + 1)
    idx = st * jnp.arange(nw)[:, None] + jnp.arange(ks)[None, :]
    return jnp.mean(k[idx], axis=1)


def block_scores(q, kc, pos, sp: dict, n_blocks: int,
                 lower: bool = False):
    """Each query row's score of every block, (R, groups, n_blocks):
    ``q`` (R, groups, heads of a group, D) at positions ``pos`` (R,),
    ``kc`` the compressed keys (windows, groups, D). Forced blocks read
    +inf, blocks after the query's own -inf."""
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    nw, d = kc.shape[0], q.shape[-1]
    if lower:
        q, kc = _f8(q, -1), _f8(kc, -1)
    s = jnp.einsum("rghd,wgd->rghw", q, kc, precision=_HI) * d ** -0.5
    whole = (st * jnp.arange(nw) + ks - 1)[None, :] <= pos[:, None]
    s = jnp.where(whole[:, None, None, :], s, -jnp.inf)
    if lower:
        s = _bf16(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    if lower:
        p = _bf16(p)
    total = jnp.where(whole[:, None, :], jnp.sum(p, axis=2), -jnp.inf)
    if lower:
        total = _bf16(total)
    # block b's windows: those that start in it, and those before that
    # still reach into it
    wpb = bs // st
    back = (ks - 1) // st
    r, g = total.shape[:2]
    width = n_blocks * wpb
    total = jnp.pad(total[..., :width],
                    ((0, 0), (0, 0), (back, max(0, width - nw))),
                    constant_values=-jnp.inf)
    score = jnp.max(total[..., back:].reshape(r, g, n_blocks, wpb), axis=-1)
    for dlt in range(1, back + 1):
        score = jnp.maximum(score,
                            total[..., back - dlt::wpb][..., :n_blocks])
    b = jnp.arange(n_blocks)[None, :]
    own = (pos // bs)[:, None]
    first_near = (jnp.maximum(pos - sp["window_size"] + 1, 0) // bs)[:, None]
    forced = (b < sp["init_blocks"]) | (b >= first_near)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    return jnp.where((b <= own)[:, None, :], score, -jnp.inf)


def select_blocks(score, sp: dict):
    """``(chosen (R, groups, n_blocks) bool, margin (R, groups))``: the
    ``topk`` highest-scoring blocks of every row and group (the lower
    index wins a tie, as ``top_k`` has it; a block after the query's own
    is never chosen), and the gap between the last score chosen and the
    first left out (+inf where nothing is left out), by which the tests
    tell a tie from a difference; the forward pass does not read it."""
    n_blocks = score.shape[-1]
    k = min(sp["topk"], n_blocks)
    top, idx = jax.lax.top_k(score, min(k + 1, n_blocks))
    chosen = jnp.any(idx[..., :k, None] == jnp.arange(n_blocks), axis=-2)
    chosen &= jnp.isfinite(score) | (score > 0)
    if n_blocks <= k:
        return chosen, jnp.full(score.shape[:-1], jnp.inf)
    last, nxt = top[..., k - 1], top[..., k]
    margin = jnp.where(jnp.isfinite(nxt) & jnp.isfinite(last),
                       last - nxt, jnp.inf)
    return chosen, margin


def _sparse_mixer(cfg, lower, p, x_all, k, v, kc, first, rb):
    """The sparse mixer for the ``rb`` rows from ``first``: a dense
    attention over all ``T`` keys under the mask the selection gives:
    (rb, hidden)."""
    sp = cfg                   # the selection's sizes ride in ``cfg``
    hq, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    t = k.shape[0]
    bs = sp["block_size"]
    n_blocks = -(-t // bs)
    x = jax.lax.dynamic_slice_in_dim(x_all, first, rb, axis=0)
    pos = first + jnp.arange(rb)
    q = _rms_norm(_mm(x, p["q"], lower).reshape(rb, g, hq // g, d),
                  p["q_norm"], cfg["rms_norm_eps"])
    chosen, _ = select_blocks(
        block_scores(q, kc, pos, sp, n_blocks, lower), sp)
    tok = jnp.repeat(chosen, bs, axis=-1)[..., :t]          # (rb, g, T)
    dense = (pos + 1 < sp["dense_len"])[:, None, None]
    causal = (jnp.arange(t)[None, :] <= pos[:, None])[:, None, :]
    live = jnp.where(dense, causal, tok & causal)
    kk, vv = k, v
    if lower:
        q, kk, vv = _f8(q, -1), _f8(k, -1), _f8(v, 0)
    s = jnp.einsum("rghd,tgd->rght", q, kk, precision=_HI) * d ** -0.5
    s = jnp.where(live[:, :, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    if lower:
        a = _f8(a, -1)
    o = jnp.einsum("rght,tgd->rghd", a, vv, precision=_HI)
    o = o.reshape(rb, hq * d) * jax.nn.sigmoid(_mm(x, p["o_gate"], lower))
    return _mm(o, p["o"], lower)


# ---------------------------------------------------- the Lightning layer

def lightning_attention(q, k, v, slopes, first=0, rows: Optional[int] = None):
    """``o_t = d^-0.5 * sum_{s<=t} exp(-slope (t - s)) (q_t . k_s) v_s``
    for the ``rows`` positions from ``first`` (default: all), the O(n^2)
    decay-masked form, a block of keys at a time. ``q, k, v`` (T, heads,
    D); ``slopes`` (heads,). Returns (rows, heads, D)."""
    t, _, d = q.shape
    rows = t if rows is None else rows
    kb = _divisor(t, LIGHT_ROWS)
    qi = jax.lax.dynamic_slice_in_dim(q, first, rows, axis=0)
    pos = first + jnp.arange(rows)

    def keys(j, o):
        kj = jax.lax.dynamic_slice_in_dim(k, j * kb, kb, axis=0)
        vj = jax.lax.dynamic_slice_in_dim(v, j * kb, kb, axis=0)
        dist = pos[:, None] - (j * kb + jnp.arange(kb))[None, :]
        decay = jnp.exp(jnp.where(
            dist >= 0, -slopes[:, None, None] * dist[None], -jnp.inf))
        s = jnp.einsum("rhd,thd->hrt", qi, kj, precision=_HI) * decay
        return o + jnp.einsum("hrt,thd->rhd", s, vj, precision=_HI)

    o = jax.lax.fori_loop(0, (first + rows - 1) // kb + 1, keys,
                          jnp.zeros_like(qi))
    return o * d ** -0.5


def _lightning_chunks_lower(q, k, v, slopes, chunk):
    """The control's Lightning layer: the chunked recurrence ``O = ((Q
    K^T) * D) V + Lambda Q S``, ``S <- lambda^C S + sum_i lambda^(C-1-i)
    k_i^T v_i``, operands in float8 and the carried state rounded to
    bfloat16 after every chunk."""
    t, h, d = q.shape
    q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, -1)
    i = jnp.arange(chunk)
    dist = i[:, None] - i[None, :]
    decay = jnp.exp(jnp.where(dist >= 0, -slopes[:, None, None] * dist[None],
                              -jnp.inf))                      # (h, C, C)
    into = jnp.exp(-slopes[:, None] * (i + 1)[None, :])       # (h, C)
    out_of = jnp.exp(-slopes[:, None] * (chunk - 1 - i)[None, :])

    def step(state, xs):
        qc, kc, vc = xs                                       # (C, h, d)
        s = _f8(jnp.einsum("rhd,thd->hrt", qc, kc, precision=_HI) * decay,
                -1)
        o = jnp.einsum("hrt,thd->rhd", s, vc, precision=_HI) \
            + jnp.einsum("rhd,hde->rhe", qc, state, precision=_HI) \
            * into.T[:, :, None]
        state = state * jnp.exp(-slopes * chunk)[:, None, None] \
            + jnp.einsum("thd,the->hde", kc * out_of.T[:, :, None], vc,
                         precision=_HI)
        return _bf16(state), o

    def chunks(a):
        return a.reshape(t // chunk, chunk, h, d)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (chunks(q), chunks(k), chunks(v)))
    return o.reshape(t, h, d) * d ** -0.5


def _lightning_out(cfg, lower, p, x_all, o, first):
    """What follows the linear attention ``o`` (rb, heads, D) of the rows
    from ``first``: the output norm, the gate, the projection."""
    nh, d, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], \
        cfg["rms_norm_eps"]
    rb = o.shape[0]
    x = jax.lax.dynamic_slice_in_dim(x_all, first, rb, axis=0)
    o = _rms_norm(o, p["o_norm"], eps).reshape(rb, nh * d)
    o = o * jax.nn.sigmoid(_mm(x, p["o_gate"], lower))
    return _mm(o, p["o"], lower)


# ------------------------------------------------------------------ layers

def _ffn(cfg, lower, p, h, res):
    t = h.shape[0]
    rb = _divisor(t, FFN_ROWS)

    def rows(first):
        hi = jax.lax.dynamic_slice_in_dim(h, first, rb, axis=0)
        x = _rms_norm(hi, p["post_norm"], cfg["rms_norm_eps"])
        y = _mm(jax.nn.silu(_mm(x, p["gate"], lower))
                * _mm(x, p["up"], lower), p["down"], lower)
        return hi + res * y

    return _row_blocks(rows, t, rb)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_forward(cfg_items, kind, lower, p, slopes, h):
    """One layer over the whole sequence ``h`` (T, hidden)."""
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    res = cfg["scale_depth"] / cfg["depth"] ** 0.5
    t = h.shape[0]
    x = _rms_norm(h, p["input_norm"], eps)
    if kind == SPARSE:
        g, d = cfg["num_key_value_heads"], cfg["head_dim"]
        k = _rms_norm(_mm(x, p["k"], lower).reshape(t, g, d),
                      p["k_norm"], eps)
        v = _mm(x, p["v"], lower).reshape(t, g, d)
        kc = compressed_keys(k, cfg)
        rb = _divisor(t, SPARSE_ROWS)
        a = _row_blocks(lambda first: _sparse_mixer(
            cfg, lower, p, x, k, v, kc, first, rb), t, rb)
    else:
        nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
        pos = jnp.arange(t)

        def heads(w, norm):
            y = _mm(x, w, lower).reshape(t, nh, d)
            return y if norm is None else _rope(
                _rms_norm(y, norm, eps), pos, cfg["rope_theta"])

        q, k = heads(p["q"], p["q_norm"]), heads(p["k"], p["k_norm"])
        v = heads(p["v"], None)
        rb = _divisor(t, LIGHT_ROWS)
        if lower:
            whole = _lightning_chunks_lower(q, k, v, slopes,
                                            _divisor(t, 128))

            def attended(first):
                return jax.lax.dynamic_slice_in_dim(whole, first, rb, axis=0)
        else:
            def attended(first):
                return lightning_attention(q, k, v, slopes, first, rb)
        a = _row_blocks(lambda first: _lightning_out(
            cfg, lower, p, x, attended(first), first), t, rb)
    return _ffn(cfg, lower, p, h + res * a, res)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg_items, lower, final_norm, head, h, rows):
    cfg = dict(cfg_items)
    h = _rms_norm(jnp.take(h, rows, axis=0), final_norm,
                  cfg["rms_norm_eps"])
    return _mm(h, head, lower) / (cfg["hidden_size"] / cfg["dim_model_base"])


def logits_at(params, cfg: dict, tokens, rows, lower: bool = False):
    """The logits (len(rows), vocab) after the input positions ``rows``
    of one sequence ``tokens`` (T,). Padding past the real length is
    harmless: every mixer is causal. The layers run one jitted call
    each."""
    items, lower = _cfg_items(cfg), bool(lower)
    rows = jnp.asarray(rows, jnp.int32)
    h = cfg["scale_emb"] * jnp.take(
        params["embed"], jnp.asarray(tokens, jnp.int32),
        axis=0).astype(jnp.float32)
    li = 0
    for kind, p in zip(cfg["mixer_types"], params["layers"]):
        slopes = params["slopes"][li] if kind == LIGHTNING else None
        li += kind == LIGHTNING
        h = _layer_forward(items, kind, lower, p, slopes, h)
    return _head(items, lower, params["final_norm"], params["head"], h,
                 rows)


@jax.jit
def gap_below_best(ref_logits, chosen):
    """How far each chosen token's reference logit lies below the
    reference's best, per row."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got


def served_gaps(params, cfg: dict, prompt, served, pad_to: int = 256,
                lower_too: bool = False):
    """For one finished request: the gap of every served token under
    the reference, and with ``lower_too`` the gap of the token the
    lower precision puts first at the same positions (the control).

    The reference runs once over the prompt with its served tokens
    (teacher forcing), so a flipped token costs one gap and no more.
    Every position is compared: the configuration's ``compare.note``
    gives the readings that show the page flips of a sound run (the
    64th block against the 65th, ranked in bfloat16 there and float32
    here) inside the limit."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, p = len(served), len(prompt)
    inputs = np.concatenate([prompt, served[:-1]])
    t = -(-len(inputs) // pad_to) * pad_to
    tokens = np.zeros((t,), np.int32)
    tokens[:len(inputs)] = inputs
    n_pad = -(-n // 128) * 128
    rows = np.full((n_pad,), p - 1, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    chosen = np.zeros((n_pad,), np.int32)
    chosen[:n] = served
    ref = logits_at(params, cfg, tokens, rows)
    gaps = np.asarray(gap_below_best(ref, jnp.asarray(chosen)))[:n]
    if not lower_too:
        return gaps, None
    low = logits_at(params, cfg, tokens, rows, lower=True)
    low_gaps = np.asarray(
        gap_below_best(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))
    return gaps, low_gaps[:n]


def param_count(cfg: dict) -> int:
    """Every weight of the configuration as cut (norm gains and the
    slopes included)."""
    def size(table):
        return sum(a * b for a, b in table.values())

    h = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * h + h
    for kind in cfg["mixer_types"]:
        d = cfg["head_dim"] if kind == SPARSE else cfg["lightning_head_dim"]
        total += size(mixer_shapes(cfg, kind)) + size(ffn_shapes(cfg)) \
            + 2 * h + 2 * d
        if kind == LIGHTNING:
            total += d + cfg["lightning_nh"]
    return total


def free(params: Optional[dict]):
    """Delete the arrays of a parameter tree now, not at the next
    collection."""
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            if not leaf.is_deleted():
                leaf.delete()
