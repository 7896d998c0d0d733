"""Plain reference of the ``glm4_moe_lite`` decoder (GLM-4.7-Flash).

The published forward pass, in the EXPANDED form of its latent attention,
as straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching, no sorting. It
imports nothing of the program under test.

With ``x = RMSNorm(h)`` (gain, eps ``rms_norm_eps``), no biases, rope in
the rotate-half convention over the ``qk_rope_head_dim`` dimensions:

* attention: ``c_q = RMSNorm(x . q_a)``; ``q = c_q . q_b`` → heads of
  ``nope ‖ rope``; ``[c_kv ‖ k_r] = x . kv_a``; ``c_kv ← RMSNorm(c_kv)``;
  ``k_rope = RoPE(k_r)``, one head shared by all; ``[k_nope ‖ v] = c_kv .
  kv_b`` per head; ``score = (q_nope . k_nope + RoPE(q_rope) . k_rope) *
  (nope + rope)^-0.5``; causal softmax; ``h += (p . v) . o``;
* the first ``first_k_dense_replace`` layers: ``h += down(silu(x . gate)
  * (x . up))``, width ``intermediate_size``;
* the other layers: ``s = sigmoid(x . router)``; the ``num_experts_per_tok``
  experts of largest ``s + e_score_correction_bias`` are chosen
  (``n_group`` = ``topk_group`` = 1: no group limit); their weights are
  ``s`` of the chosen over its sum, times ``routed_scaling_factor`` (the
  bias is not in the weight); ``h += sum_e w_e . SwiGLU_e(x) +
  SwiGLU_shared(x)``. EVERY expert is applied to every token and a mask
  keeps the chosen ones;
* ``logits = RMSNorm(h) . head``, untied. The multi-token-prediction
  layer is not part of the forward pass.

The weights are held as their bfloat16-exact values in bfloat16 (the
float32 tree of the benchmark's cut is larger than the chip) and widened
to float32 a layer at a time; query rows go in blocks so that a whole
``T x T x heads`` score tensor never exists.

``lower`` is the control of the comparison: the same forward pass with
every matrix-product operand rounded to float8 (e4m3, one absmax scale
per row of the contraction), the nearest precision below the bfloat16
the configuration states. The router stays float32 there, as the
configuration states it. ``router_bf16`` is a second control, of the
near-tie rule of :func:`served_gaps`: the router ALONE one precision
down (operands, scores and biased scores in bfloat16), everything else
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0
ROW_BLOCK = 1024          # query rows attended at once

_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_attention_heads", "num_hidden_layers", "vocab_size",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
         "n_shared_experts", "num_experts_per_tok",
         "first_k_dense_replace", "routed_scaling_factor",
         "norm_topk_prob", "rms_norm_eps", "rope_theta")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (``PRNGKey`` alone
    takes 32 signed bits without x64)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _cfg_items(cfg: dict):
    for k in ("n_group", "topk_group"):
        if cfg.get(k, 1) != 1:
            raise ValueError(f"{k} must be 1 (no group-limited routing)")
    return tuple((k, cfg[k]) for k in _KEYS)


def _normal_bf16(key, shape, fan_in):
    """Normal weights of std fan_in^-0.5, rounded to bfloat16."""
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(jnp.bfloat16)


def attention_shapes(cfg: dict) -> dict:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    return {"q_a": (h, cfg["q_lora_rank"]),
            "q_b": (cfg["q_lora_rank"], nh * qk),
            "kv_a": (h, r + cfg["qk_rope_head_dim"]),
            "kv_b": (r, nh * (cfg["qk_nope_head_dim"]
                              + cfg["v_head_dim"])),
            "o": (nh * cfg["v_head_dim"], h)}


def dense_shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return {"gate": (h, i), "up": (h, i), "down": (i, h)}


def expert_shapes(cfg: dict) -> dict:
    """Fan-in is the second-last axis: the leading one counts experts."""
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["n_routed_experts"]
    fs = f * cfg["n_shared_experts"]
    return {"experts_gate": (e, h, f), "experts_up": (e, h, f),
            "experts_down": (e, f, h), "shared_gate": (h, fs),
            "shared_up": (h, fs), "shared_down": (fs, h)}


def _layer(key, cfg, moe: bool):
    h = cfg["hidden_size"]
    table = dict(attention_shapes(cfg))
    table.update(expert_shapes(cfg) if moe else dense_shapes(cfg))
    ks = jax.random.split(key, len(table) + 2)
    out = {name: _normal_bf16(k, shape, shape[-2])
           for k, (name, shape) in zip(ks, sorted(table.items()))}
    out["input_norm"] = jnp.ones((h,), jnp.float32)
    out["q_a_norm"] = jnp.ones((cfg["q_lora_rank"],), jnp.float32)
    out["kv_a_norm"] = jnp.ones((cfg["kv_lora_rank"],), jnp.float32)
    out["post_norm"] = jnp.ones((h,), jnp.float32)
    if moe:
        # the router is float32: its values are not rounded
        out["router"] = jax.random.normal(
            ks[-2], (h, cfg["n_routed_experts"]), jnp.float32) * h ** -0.5
        out["e_score_correction_bias"] = 0.02 * jax.random.normal(
            ks[-1], (cfg["n_routed_experts"],), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_layer(key, cfg_items, moe):
    return _layer(key, dict(cfg_items), moe)


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
    (so the generator's temporaries are one layer's): ``dense`` the
    list of the leading layers, ``moe`` the list of the expert layers,
    a dict of leaves each; matrices in bfloat16, router, selection bias
    and gains in float32."""
    items = _cfg_items(cfg)
    n, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    k_ends, k_layers = jax.random.split(seed_key(seed))
    keys = jax.random.split(k_layers, n)
    out = _make_ends(k_ends, items)
    out["dense"] = [_make_layer(keys[i], items, False) for i in range(nd)]
    out["moe"] = [_make_layer(keys[i], items, True) for i in range(nd, n)]
    return out


# ------------------------------------------------------------------ forward

def _f8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


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


def _rope(x, theta):
    """Rotate (T, heads, D) by position: rotate-half convention."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(cfg, lower, h, p):
    nh = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    r, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        cfg["rope_theta"]
    t = h.shape[0]
    x = _rms_norm(h, p["input_norm"], eps)
    cq = _rms_norm(_mm(x, p["q_a"], lower), p["q_a_norm"], eps)
    q = _mm(cq, p["q_b"], lower).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    kva = _mm(x, p["kv_a"], lower)
    ckv = _rms_norm(kva[:, :r], p["kv_a_norm"], eps)
    k_rope = _rope(kva[:, None, r:], theta)                # (t, 1, dr)
    kv = _mm(ckv, p["kv_b"], lower).reshape(t, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    if lower:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, 0)
    scale = (dn + dr) ** -0.5
    # the largest block of rows, up to ROW_BLOCK, that divides t
    rb = next(b for b in range(min(ROW_BLOCK, t), 0, -1) if t % b == 0)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * rb, rb, axis=0)
        s = jnp.einsum("thd,shd->hts", qi, k, precision=_HI) * scale
        causal = (i * rb + jnp.arange(rb))[:, None] \
            >= jnp.arange(t)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        if lower:
            a = _f8(a, -1)
        return jnp.einsum("hts,shd->thd", a, v, precision=_HI)

    o = jax.lax.map(rows, jnp.arange(t // rb)).reshape(t, nh * dv)
    return h + _mm(o, p["o"], lower)


def _swiglu(x, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower),
               down, lower)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dense_layer(cfg_items, lower, p, h):
    cfg = dict(cfg_items)
    h = _attention(cfg, lower, h, p)
    x = _rms_norm(h, p["post_norm"], cfg["rms_norm_eps"])
    return h + _swiglu(x, p["gate"], p["up"], p["down"], lower)


def route(cfg, x, p, bf16: bool = False):
    """``(chosen (T, E) bool, weight (T, E), margin (T,))``: the mask of
    each token's experts, their weights, and the gap between the last
    score chosen and the first left out. ``bf16``: the router as a
    program that narrowed it would compute it (equal bfloat16 scores
    are common; the lower index wins, as ``top_k`` has it, so a token
    has its ``k`` experts and no more)."""
    k = cfg["num_experts_per_tok"]
    bias = p["e_score_correction_bias"]
    if bf16:
        def rnd(a):
            # not astype(bfloat16).astype(float32): the TPU compiler may
            # keep the excess precision and drop that pair of converts
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)
        # products of bfloat16 pairs are exact in float32, as on the MXU
        s = rnd(jax.nn.sigmoid(rnd(jnp.matmul(
            rnd(x), rnd(p["router"]), precision=_HI))))
        pick = rnd(s + rnd(bias))
    else:
        s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=_HI))
        pick = s + bias
    top, idx = jax.lax.top_k(pick, k + 1)
    chosen = jnp.any(idx[:, :k, None] == jnp.arange(pick.shape[-1]), axis=1)
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * cfg["routed_scaling_factor"], \
        top[:, k - 1] - top[:, k]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _moe_layer(cfg_items, lower, router_bf16, p, h):
    """One expert layer; also each token's routing margin."""
    cfg = dict(cfg_items)
    h = _attention(cfg, lower, h, p)
    x = _rms_norm(h, p["post_norm"], cfg["rms_norm_eps"])
    _, w, margin = route(cfg, x, p, router_bf16)

    def expert(e, y):
        out = _swiglu(x, p["experts_gate"][e], p["experts_up"][e],
                      p["experts_down"][e], lower)
        return y + out * jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)

    y = jax.lax.fori_loop(0, cfg["n_routed_experts"], expert,
                          jnp.zeros_like(h))
    y = y + _swiglu(x, p["shared_gate"], p["shared_up"],
                    p["shared_down"], lower)
    return h + y, margin


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg_items, lower, final_norm, head, h, rows):
    cfg = dict(cfg_items)
    h = _rms_norm(jnp.take(h, rows, axis=0), final_norm,
                  cfg["rms_norm_eps"])
    return _mm(h, head, lower)


def logits_at(params, cfg: dict, tokens, rows, lower: bool = False,
              router_bf16: bool = False):
    """``(logits (len(rows), vocab), margin (len(rows),))`` after the
    input positions ``rows`` of one sequence ``tokens`` (T,): ``margin``
    is the smallest routing margin of that position over the expert
    layers. Padding past the real length is harmless: attention is
    causal. The layers run one jitted call each, and inside it one
    expert at a time is widened to float32."""
    items, lower = _cfg_items(cfg), bool(lower)
    rows = jnp.asarray(rows, jnp.int32)
    h = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for p in params["dense"]:
        h = _dense_layer(items, lower, p, h)
    margin = jnp.full((rows.shape[0],), jnp.inf, jnp.float32)
    for p in params["moe"]:
        h, m = _moe_layer(items, lower, bool(router_bf16), p, h)
        margin = jnp.minimum(margin, jnp.take(m, rows, axis=0))
    return _head(items, lower, params["final_norm"], params["head"], h,
                 rows), margin


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

    Where the configuration gives ``compare.router_margin_min``, the
    positions at which the reference's OWN float32 routing margin (the
    last chosen score minus the first left out, the smallest over the
    expert layers) is under it are left out, of both results: there a
    sound bfloat16 program may pick another expert than float32 does,
    which is a tie broken otherwise and no fault of precision. The
    reference alone decides which; what is returned is what remains."""
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
    ref, margin = logits_at(params, cfg, tokens, rows)
    keep = np.asarray(margin)[:n] >= float(
        cfg.get("compare", {}).get("router_margin_min", 0.0))
    gaps = np.asarray(gap_below_best(ref, jnp.asarray(chosen)))[:n]
    if not lower_too:
        return gaps[keep], None
    low, _ = logits_at(params, cfg, tokens, rows, lower=True)
    low_gaps = np.asarray(
        gap_below_best(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))
    return gaps[keep], low_gaps[:n][keep]


def param_count(cfg: dict) -> int:
    """Every weight of the configuration as cut (norm gains and the
    selection bias included)."""
    def size(table):
        return sum(functools.reduce(lambda a, b: a * b, s)
                   for s in table.values())

    h, n, nd = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["first_k_dense_replace"]
    norms = 2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    attn = size(attention_shapes(cfg)) + norms
    moe = size(expert_shapes(cfg)) + (h + 1) * cfg["n_routed_experts"]
    return (nd * (attn + size(dense_shapes(cfg))) + (n - nd) * (attn + moe)
            + 2 * cfg["vocab_size"] * h + h)


def free(params: Optional[dict]):
    """Delete the arrays of a parameter tree now, not at the next
    collection."""
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            if not leaf.is_deleted():
                leaf.delete()
