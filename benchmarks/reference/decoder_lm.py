"""Plain reference of a Llama-shaped decoder-only language model.

Pre-norm blocks: RMSNorm, grouped-query causal attention with rotary
position embeddings (rotate-half convention, as the published Mistral
and Llama code has it), SwiGLU feed-forward, no biases, untied head.
Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching. It imports
nothing of the program under test.

``make_params`` is the benchmark's weight generator: the harness hands
the same arrays to the program, and the reference makes them again from
the seed once the program's state is freed.

``lower`` is the control of the comparison: the same forward pass with
every matrix-product operand rounded to float8 (e4m3, one absmax scale
per row of the contraction), the nearest precision below the bfloat16
the configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (``PRNGKey`` alone
    takes 32 signed bits without x64)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _normal_bf16(key, shape, fan_in):
    """Normal weights of std fan_in^-0.5 whose values are exact in
    bfloat16, returned as float32."""
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _shapes(cfg: dict):
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
            "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}


@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(key, cfg_items):
    cfg = dict(cfg_items)
    h, v, n = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    shapes = _shapes(cfg)

    def one_layer(k):
        ks = jax.random.split(k, len(shapes))
        out = {name: _normal_bf16(kk, shape, shape[0])
               for kk, (name, shape) in zip(ks, sorted(shapes.items()))}
        out["attn_norm"] = jnp.ones((h,), jnp.float32)
        out["mlp_norm"] = jnp.ones((h,), jnp.float32)
        return out

    # layer by layer, so the generator's temporaries are one layer's
    layers = jax.lax.map(one_layer, jax.random.split(k_layers, n))
    return {"embed": _normal_bf16(k_embed, (v, h), 1.0),
            "layers": layers,
            "final_norm": jnp.ones((h,), jnp.float32),
            "head": _normal_bf16(k_head, (h, v), h)}


def _cfg_items(cfg: dict):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def make_params(seed: int, cfg: dict):
    """All weights on the device in one jitted call from the seed."""
    return _make_params(seed_key(seed), _cfg_items(cfg))


# ------------------------------------------------------------------ forward

def _f8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, lower: bool):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
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


def _block(cfg, lower, h, p):
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    t = h.shape[0]
    x = _rms_norm(h, p["attn_norm"], cfg["rms_norm_eps"])
    q = _rope(_mm(x, p["wq"], lower).reshape(t, nh, d), cfg["rope_theta"])
    k = _rope(_mm(x, p["wk"], lower).reshape(t, nkv, d),
              cfg["rope_theta"])
    v = _mm(x, p["wv"], lower).reshape(t, nkv, d)
    # grouped queries: head j of group g reads kv head g
    q = q.reshape(t, nkv, nh // nkv, d)
    if lower:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, 0)
    s = jnp.einsum("tgjd,sgd->gjts", q, k, precision=_HI) * (d ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    if lower:
        a = _f8(a, -1)
    o = jnp.einsum("gjts,sgd->tgjd", a, v, precision=_HI)
    h = h + _mm(o.reshape(t, nh * d), p["wo"], lower)
    x = _rms_norm(h, p["mlp_norm"], cfg["rms_norm_eps"])
    f = jax.nn.silu(_mm(x, p["w_gate"], lower)) * _mm(x, p["w_up"], lower)
    return h + _mm(f, p["w_down"], lower)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_at(cfg_items, lower, params, tokens, rows):
    """Logits (len(rows), vocab) after the input positions ``rows`` of
    one sequence ``tokens`` (T,). Padding past the real length is
    harmless: attention is causal."""
    cfg = dict(cfg_items)
    h = jnp.take(params["embed"], tokens, axis=0)

    def body(h, p):
        return _block(cfg, lower, h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = jnp.take(h, rows, axis=0)
    h = _rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
    return _mm(h, params["head"], lower)


def logits_at(params, cfg: dict, tokens, rows, lower: bool = False):
    return _logits_at(_cfg_items(cfg), bool(lower), params,
                      jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32))


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
    (teacher forcing), so a flipped token costs one gap and no more."""
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


def param_bytes(cfg: dict, itemsize: int = 4) -> int:
    h, v, n = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    per = sum(a * b for a, b in _shapes(cfg).values()) + 2 * h
    return (2 * v * h + n * per + h) * itemsize


def free(params: Optional[dict]):
    """Delete the arrays of a parameter tree now, not at the next
    collection."""
    if params is not None:
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
