"""Plain reference of the language model of Keye-VL-2.0 (``KeyeVL2``): a
Qwen3-MoE decoder whose attention is restricted, for every query, to the
cached tokens a learned indexer ranks highest (DeepSeek Sparse
Attention).

The forward pass as straightforward ``jax.numpy`` in float32 with every
matrix product at ``Precision.HIGHEST``: no kernels, no cache, no
batching, no sorting of tokens by expert. It imports nothing of the
program under test. The equations (``config.json`` of
``Kwai-Keye/Keye-VL-2.0-30B-A3B``; what the file does not give is listed
under ``assumed`` in the benchmark's configuration and marked ASSUMED
here), with ``x = RMSNorm(h)`` (gain, eps ``rms_norm_eps``), no biases,
rope in the rotate-half convention at ``rope_theta`` over a whole head
(text only: the three MRoPE sections carry one position):

* attention: ``q = x W_q`` (``num_attention_heads`` of ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads``); ``q, k <-
  RMSNorm_head(q), RMSNorm_head(k)`` (ASSUMED: the Qwen3-MoE family's
  q/k norms, gains of head width); ``q, k <- RoPE(q), RoPE(k)``;
* the indexer (ASSUMED: DeepSeek-V3.2-Exp's, reading ``x`` since there
  is no query latent): ``q_I = x W_qI`` (``indexer_num_heads`` of
  ``indexer_head_dim``), ``k_I = LayerNorm(x W_kI)`` (one head, gain and
  bias), both roped over the whole head; ``w = x W_w * (H_I d_I)^-1/2``;
  ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])`` for ``s <= t``;
  ``S_t`` is the ``topk`` of largest ``I[t, .]`` (of equal scores the
  earlier position), every ``s <= t`` while there are fewer;
* ``o[t, g] = softmax_{s in S_t}(q[t, g] . k[s, g/group] / sqrt(head_dim))
  . v[s, g/group]``; ``h += o W_o``;
* the experts, on ``x' = RMSNorm(h)``: ``p = softmax(x' . router)`` over
  all ``num_experts`` in float32; the ``num_experts_per_tok`` of largest
  ``p``, renormalised to sum to 1 (``norm_topk_prob``); ``h += sum_e p_e
  down_e(silu(x' gate_e) * (x' up_e))``. EVERY expert is applied to every
  token and the weights keep the chosen ones; no shared expert;
* ``logits = RMSNorm(h) . head``, untied.

The weights are held as their bfloat16-exact values in bfloat16 (the
float32 tree of the benchmark's cut, 12.5 GB, does not fit beside
anything) and widened to float32 a layer at a time; query rows go in
blocks so that no ``rows x T x heads`` tensor of the whole sequence
exists.

``lower`` is the control of the comparison, one precision below what the
configuration states: every matrix-product operand rounded to float8
(e4m3, one absmax scale per row of the contraction) and the indexer's
scores and the router's logits rounded to bfloat16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0
ROWS = 128                # query rows of a layer attended at once

_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "num_experts",
         "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
         "rms_norm_eps", "rope_theta", "vocab_size")
_SA = ("indexer_num_heads", "indexer_head_dim", "topk")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (``PRNGKey`` alone
    takes 32 signed bits without x64)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _cfg_items(cfg: dict):
    if cfg["sa_config"].get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("one indexer key head")
    return tuple((k, cfg[k]) for k in _KEYS) + tuple(
        (k, cfg["sa_config"][k]) for k in _SA)


def _normal_bf16(key, shape, fan_in):
    """Normal weights of std fan_in^-0.5, rounded to bfloat16."""
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(jnp.bfloat16)


def matrix_shapes(cfg: dict) -> dict:
    """A layer's matrices; an expert stack's fan-in is its second-last
    axis."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"q": (h, nh * d), "k": (h, nkv * d), "v": (h, nkv * d),
            "o": (nh * d, h), "iq": (h, hi * di), "ik": (h, di),
            "iw": (h, hi), "experts_gate": (e, h, f),
            "experts_up": (e, h, f), "experts_down": (e, f, h)}


def _layer(key, cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    di = cfg["indexer_head_dim"]
    table = matrix_shapes(
        dict(cfg, sa_config={k: cfg[k] for k in _SA}))
    ks = jax.random.split(key, len(table) + 1)
    out = {name: _normal_bf16(k, shape, shape[-2])
           for k, (name, shape) in zip(ks, sorted(table.items()))}
    # the router is float32: its values are not rounded
    out["router"] = jax.random.normal(
        ks[-1], (h, cfg["num_experts"]), jnp.float32) * h ** -0.5
    out["input_norm"] = jnp.ones((h,), jnp.float32)
    out["post_norm"] = jnp.ones((h,), jnp.float32)
    out["q_norm"] = jnp.ones((d,), jnp.float32)
    out["k_norm"] = jnp.ones((d,), jnp.float32)
    out["ik_norm"] = jnp.ones((di,), jnp.float32)
    out["ik_bias"] = jnp.zeros((di,), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, cfg_items):
    return _layer(key, dict(cfg_items))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_ends(key, cfg_items):
    cfg = dict(cfg_items)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    k_embed, k_head = jax.random.split(key)
    return {"embed": _normal_bf16(k_embed, (v, h), 1.0),
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": _normal_bf16(k_head, (h, v), h)}


def make_params(seed: int, cfg: dict):
    """All weights on the device from the seed, a jitted call a layer
    (so the generator's temporaries are one layer's): ``layers`` a list
    of dicts of leaves; matrices in bfloat16, router and gains in
    float32."""
    items = _cfg_items(cfg)
    k_ends, k_layers = jax.random.split(seed_key(seed))
    keys = jax.random.split(k_layers, cfg["num_hidden_layers"])
    out = _make_ends(k_ends, items)
    out["layers"] = [_make_layer(k, items) for k in keys]
    return out


# ------------------------------------------------------------------ forward

def _f8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
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


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def rope_tables(t: int, d: int, theta: float):
    """cos and sin (t, d/2) of the rotate-half rope at positions 0..t-1,
    made on the host: as arguments of the jitted layer they are not
    folded into its executable as constants."""
    import numpy as np
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))


def _rope(x, tables):
    """Rotate (T, heads, D) by position."""
    cos, sin = (a[:, None, :] for a in tables)
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def index_scores(qi, w, ki, lower: bool = False):
    """``I = sum_j w_j ReLU(q_I,j . k_I)``: rows' queries (R, H_I, d_I)
    and weights (R, H_I) against keys (T, d_I) → (R, T)."""
    s = jnp.einsum("rhd,td->rht", qi, ki, precision=_HI)
    out = jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)
    return _bf16(out) if lower else out


def _attention(cfg, lower, h, p, rope, irope):
    """The attention half of a layer; also each position's selection
    margin (the k-th largest index score less the (k+1)-th, +inf where
    every position is taken)."""
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    hi, di, topk = cfg["indexer_num_heads"], cfg["indexer_head_dim"], \
        cfg["topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = h.shape[0]
    x = _rms_norm(h, p["input_norm"], eps)
    q = _rms_norm(_mm(x, p["q"], lower).reshape(t, nh, d), p["q_norm"], eps)
    k = _rms_norm(_mm(x, p["k"], lower).reshape(t, nkv, d), p["k_norm"],
                  eps)
    v = _mm(x, p["v"], lower).reshape(t, nkv, d)
    q, k = _rope(q, rope), _rope(k, rope)
    qi = _rope(_mm(x, p["iq"], lower).reshape(t, hi, di), irope)
    ki = _rope(_layer_norm(_mm(x, p["ik"], lower), p["ik_norm"],
                           p["ik_bias"], eps)[:, None], irope)[:, 0]
    w = _mm(x, p["iw"], lower) * (hi * di) ** -0.5
    if lower:
        q, k, v, qi, ki = (_f8(a, -1) for a in (q, k, v, qi, ki))
    group = nh // nkv
    kk = min(topk, t)
    # the largest block of rows, up to ROWS, that divides t
    rb = next(b for b in range(min(ROWS, t), 0, -1) if t % b == 0)

    def rows(i):
        first = i * rb
        causal = (first + jnp.arange(rb))[:, None] >= jnp.arange(t)[None, :]
        score = index_scores(
            jax.lax.dynamic_slice_in_dim(qi, first, rb, axis=0),
            jax.lax.dynamic_slice_in_dim(w, first, rb, axis=0), ki, lower)
        score = jnp.where(causal, score, -jnp.inf)
        top, idx = jax.lax.top_k(score, min(kk + 1, t))
        kth = top[:, kk - 1]
        nxt = top[:, kk] if kk < t else jnp.full_like(kth, -jnp.inf)
        margin = jnp.where(jnp.isfinite(nxt), kth - nxt, jnp.inf)
        # the k that top_k names (of equal scores the earlier position)
        picked = causal & jnp.zeros((rb, t), bool).at[
            jnp.arange(rb)[:, None], idx[:, :kk]].set(True)
        qb = jax.lax.dynamic_slice_in_dim(q, first, rb, axis=0).reshape(
            rb, nkv, group, d)
        s = jnp.einsum("rkgd,tkd->kgrt", qb, k, precision=_HI) * d ** -0.5
        s = jnp.where(picked[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        if lower:
            a = _f8(a, -1)
        o = jnp.einsum("kgrt,tkd->rkgd", a, v, precision=_HI)
        return o.reshape(rb, nh * d), margin

    o, margin = jax.lax.map(rows, jnp.arange(t // rb))
    return h + _mm(o.reshape(t, nh * d), p["o"], lower), margin.reshape(t)


def route(cfg, x, p, lower: bool = False):
    """``(weight (T, E), margin (T,))``: each token's expert weights
    (softmax over every expert, the top ``num_experts_per_tok`` kept and
    renormalised) and the gap between the last router logit chosen and
    the first left out."""
    k = cfg["num_experts_per_tok"]
    logits = jnp.matmul(x, p["router"], precision=_HI)
    if lower:
        logits = _bf16(logits)
    prob = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(logits, k + 1)
    chosen = jnp.any(idx[:, :k, None] == jnp.arange(logits.shape[-1]),
                     axis=1)
    wgt = jnp.where(chosen, prob, 0.0)
    if cfg["norm_topk_prob"]:
        wgt = wgt / jnp.sum(wgt, axis=-1, keepdims=True)
    return wgt, top[:, k - 1] - top[:, k]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(cfg_items, lower, p, h, rope, irope):
    """One layer; also each position's selection and routing margins."""
    cfg = dict(cfg_items)
    h, sel_margin = _attention(cfg, lower, h, p, rope, irope)
    x = _rms_norm(h, p["post_norm"], cfg["rms_norm_eps"])
    wgt, route_margin = route(cfg, x, p, lower)

    def expert(e, y):
        out = _mm(jax.nn.silu(_mm(x, p["experts_gate"][e], lower))
                  * _mm(x, p["experts_up"][e], lower),
                  p["experts_down"][e], lower)
        return y + out * jax.lax.dynamic_slice_in_dim(wgt, e, 1, axis=1)

    y = jax.lax.fori_loop(0, cfg["num_experts"], expert, jnp.zeros_like(h))
    return h + y, sel_margin, route_margin


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg_items, lower, final_norm, head, h, rows):
    cfg = dict(cfg_items)
    h = _rms_norm(jnp.take(h, rows, axis=0), final_norm,
                  cfg["rms_norm_eps"])
    return _mm(h, head, lower)


def logits_at(params, cfg: dict, tokens, rows, lower: bool = False):
    """``(logits (len(rows), vocab), selection margin (len(rows),),
    routing margin (len(rows),))`` after the input positions ``rows`` of
    one sequence ``tokens`` (T,); each margin the smallest of that
    position over the layers. Padding past the real length is harmless:
    attention is causal. The layers run one jitted call each."""
    items, lower = _cfg_items(cfg), bool(lower)
    rows = jnp.asarray(rows, jnp.int32)
    h = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    sel = jnp.full((rows.shape[0],), jnp.inf, jnp.float32)
    rte = jnp.full((rows.shape[0],), jnp.inf, jnp.float32)
    t, theta = h.shape[0], cfg["rope_theta"]
    rope = rope_tables(t, cfg["head_dim"], theta)
    irope = rope_tables(t, cfg["sa_config"]["indexer_head_dim"], theta)
    for p in params["layers"]:
        h, s, r = _layer_forward(items, lower, p, h, rope, irope)
        sel = jnp.minimum(sel, jnp.take(s, rows, axis=0))
        rte = jnp.minimum(rte, jnp.take(r, rows, axis=0))
    return _head(items, lower, params["final_norm"], params["head"], h,
                 rows), sel, rte


@jax.jit
def gap_below_best(ref_logits, chosen):
    """How far each chosen token's reference logit lies below the
    reference's best, per row."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got


def _teacher_forced(prompt, served, pad_to):
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
    return tokens, rows, chosen, n


def served_gaps(params, cfg: dict, prompt, served, pad_to: int = 256,
                lower_too: bool = False):
    """For one finished request: the gap of every served token under
    the reference, and with ``lower_too`` the gap of the token the
    lower precision puts first at the same positions (the control).

    The reference runs once over the prompt with its served tokens
    (teacher forcing), so a flipped token costs one gap and no more.

    Where the configuration gives ``compare.router_margin_min`` or
    ``compare.select_margin_min``, the positions at which the
    reference's OWN float32 margin (routing: the last chosen router
    logit less the first left out; selection: the ``topk``-th index
    score less the next; each the smallest over the layers) is under it
    are left out, of both results: there a sound bfloat16 program may
    choose otherwise than float32 does, which is a tie broken otherwise
    and no fault of precision. The reference alone decides which; what
    is returned is what remains."""
    import numpy as np
    tokens, rows, chosen, n = _teacher_forced(prompt, served, pad_to)
    ref, sel, rte = logits_at(params, cfg, tokens, rows)
    rule = cfg.get("compare", {})
    keep = (np.asarray(rte)[:n] >= float(rule.get("router_margin_min", 0.0))) \
        & (np.asarray(sel)[:n] >= float(rule.get("select_margin_min", 0.0)))
    gaps = np.asarray(gap_below_best(ref, jnp.asarray(chosen)))[:n]
    if not lower_too:
        return gaps[keep], None
    low, _, _ = logits_at(params, cfg, tokens, rows, lower=True)
    low_gaps = np.asarray(
        gap_below_best(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))
    return gaps[keep], low_gaps[:n][keep]


def param_count(cfg: dict) -> int:
    """Every weight of the configuration as cut (norm gains, the
    indexer's key norm and the router included)."""
    def size(shape):
        return functools.reduce(lambda a, b: a * b, shape)

    h, d = cfg["hidden_size"], cfg["head_dim"]
    di = cfg["sa_config"]["indexer_head_dim"]
    layer = sum(size(s) for s in matrix_shapes(cfg).values()) \
        + h * cfg["num_experts"] + 2 * h + 2 * d + 2 * di
    return cfg["num_hidden_layers"] * layer \
        + 2 * cfg["vocab_size"] * h + h


def free(params: Optional[dict]):
    """Delete the arrays of a parameter tree now, not at the next
    collection."""
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            if not leaf.is_deleted():
                leaf.delete()
