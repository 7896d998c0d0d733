"""Plain reference of BERT sequence classification and its AdamW step.

Post-layer-norm encoder as published (token + learned position
embeddings, embedding layer norm, blocks of bidirectional multi-head
attention and a GELU feed-forward), a linear two-class head on token 0
and softmax cross-entropy, mean over the batch. The optimizer is AdamW
with bias correction and decoupled weight decay on every leaf.
Straightforward ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``, loss, gradients and update written out. It
imports nothing of the program under test.

Departures from the published model, all stated in the configuration's
file: tanh GELU, layer-norm epsilon 1e-5, no dropout, no segment ids
(so ``seg`` and the pooler get no gradient and move by decay alone).

``follow`` trains for some steps. Its ``lower`` switch is the control
(float8 e4m3 operands in every forward matrix product, straight-through
gradients: the nearest precision below the bfloat16 the configuration
states) and ``rows`` plants the faults "part of the batch left out,
the mean taken over the rest" and "the exchange between chips left
out" (one chip's rows alone).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0
ROW_BLOCK = 32


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _cfg_items(cfg: dict):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "intermediate_size", "vocab_size", "max_position_embeddings",
            "type_vocab_size", "initializer_range", "layer_norm_eps")
    return tuple((k, cfg[k]) for k in keys) \
        + (("num_labels", cfg["train"]["num_labels"]),)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(key, cfg_items):
    c = dict(cfg_items)
    h, i, n = c["hidden_size"], c["intermediate_size"], \
        c["num_hidden_layers"]
    std = c["initializer_range"]
    ks = iter(jax.random.split(key, 16))

    def w(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "tok": w((c["vocab_size"], h)),
        "pos": w((c["max_position_embeddings"], h)),
        "seg": w((c["type_vocab_size"], h)),
        "emb_ln_g": jnp.ones((h,)), "emb_ln_b": jnp.zeros((h,)),
        "layers": {
            "qkv_w": w((n, h, 3 * h)), "qkv_b": jnp.zeros((n, 3 * h)),
            "proj_w": w((n, h, h)), "proj_b": jnp.zeros((n, h)),
            "ln1_g": jnp.ones((n, h)), "ln1_b": jnp.zeros((n, h)),
            "fc1_w": w((n, h, i)), "fc1_b": jnp.zeros((n, i)),
            "fc2_w": w((n, i, h)), "fc2_b": jnp.zeros((n, h)),
            "ln2_g": jnp.ones((n, h)), "ln2_b": jnp.zeros((n, h)),
        },
        "pool_w": w((h, h)), "pool_b": jnp.zeros((h,)),
        "cls_w": w((h, c["num_labels"])),
        "cls_b": jnp.zeros((c["num_labels"],)),
    }


def make_params(seed: int, cfg: dict):
    """All float32 weights on the device in one jitted call."""
    return _make_params(seed_key(seed), _cfg_items(cfg))


def make_data(seed: int, cfg: dict, steps: int, batch: int, seq: int):
    """Token ids (steps*batch, seq) and labels: random rows that all
    differ, whose first token tells the label so the loss can fall."""
    import numpy as np
    rs = np.random.default_rng(int(seed) + 1)
    n = steps * batch
    ids = rs.integers(3, cfg["vocab_size"], (n, seq), dtype=np.int32)
    y = rs.integers(0, 2, n, dtype=np.int32)
    ids[:, 0] = 1 + y
    return ids, y


# ------------------------------------------------------------------ forward

def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jax.lax.stop_gradient(jnp.where(s > 0, s, 1.0))
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)      # straight-through


def _mm(a, b, lower):
    if lower:
        a, b = _f8(a, -1), _f8(b, 0)
    return jnp.matmul(a, b, precision=_HI)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(c, lower, h, p):
    b, t, hid = h.shape
    nh = c["num_attention_heads"]
    d = hid // nh
    eps = c["layer_norm_eps"]
    qkv = _mm(h, p["qkv_w"], lower) + p["qkv_b"]
    q, k, v = (a.reshape(b, t, nh, d) for a in jnp.split(qkv, 3, -1))
    if lower:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, 1)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=_HI) * (d ** -0.5)
    a = jax.nn.softmax(s, axis=-1)
    if lower:
        a = _f8(a, -1)
    o = jnp.einsum("bhts,bshd->bthd", a, v, precision=_HI)
    o = _mm(o.reshape(b, t, hid), p["proj_w"], lower) + p["proj_b"]
    h = _layer_norm(h + o, p["ln1_g"], p["ln1_b"], eps)
    f = _gelu_tanh(_mm(h, p["fc1_w"], lower) + p["fc1_b"])
    f = _mm(f, p["fc2_w"], lower) + p["fc2_b"]
    return _layer_norm(h + f, p["ln2_g"], p["ln2_b"], eps)


def loss_fn(c, lower, params, ids, labels):
    """Mean cross-entropy of the rows ``ids`` (B, T)."""
    t = ids.shape[1]
    h = jnp.take(params["tok"], ids, axis=0) + params["pos"][:t]
    h = _layer_norm(h, params["emb_ln_g"], params["emb_ln_b"],
                    c["layer_norm_eps"])

    def body(h, p):
        return _block(c, lower, h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    logits = _mm(h[:, 0], params["cls_w"], lower) + params["cls_b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def _grads(c, lower, params, ids, labels):
    """Loss and gradient of one batch, in blocks of rows so that the
    float32 activations fit; ids is (blocks, rows, T)."""
    nb = ids.shape[0]

    def one(acc, xs):
        loss, g = jax.value_and_grad(functools.partial(loss_fn, c, lower))(
            params, xs[0], xs[1])
        return jax.tree_util.tree_map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(one, zero, (ids, labels))
    return loss / nb, jax.tree_util.tree_map(lambda a: a / nb, g)


def _adamw(hp, params, m, v, g, step):
    b1, b2 = hp["beta_1"], hp["beta_2"]
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m_, v_):
        u = (m_ / c1) / (jnp.sqrt(v_ / c2) + hp["epsilon"]) \
            + hp["weight_decay"] * p
        return p - hp["learning_rate"] * u

    return jax.tree_util.tree_map(upd, params, m, v), m, v


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _follow(cfg_items, hp_items, lower, first, params, ids, labels):
    c, hp = dict(cfg_items), dict(hp_items)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def step(carry, xs):
        params, m, v, k = carry
        loss, g = _grads(c, lower, params, xs[0], xs[1])
        k = k + 1.0
        params, m, v = _adamw(hp, params, m, v, g, k)
        return (params, m, v, k), loss

    _, first_grad = _grads(c, lower, params, ids[0], labels[0])
    carry = (params, zeros, zeros, jnp.zeros(()))
    carry, losses = jax.lax.scan(step, carry, (ids[:first], labels[:first]))
    p_first = carry[0]
    (p_end, m, _, _), later = jax.lax.scan(
        step, carry, (ids[first:], labels[first:]))
    return {"losses": jnp.concatenate([losses, later]), "params": p_end,
            "moment": m, "params_first": p_first, "first_grad": first_grad}


def follow(params, cfg: dict, ids, labels, steps: int, batch: int,
           lower: bool = False, rows: float = 1.0, devices=None,
           first: int = 1):
    """Train ``steps`` steps of ``batch`` rows each from ``params`` on
    the rows of ``ids`` in order. Returns the per-step ``losses``, the
    ``params`` and Adam's first ``moment`` after the last step, the
    ``first_grad``ient, and ``params_first``, the parameters after the
    ``first`` steps.
    ``rows`` < 1 keeps only that leading share of every batch. Several
    ``devices`` share the rows of each block (the compiler adds the
    reduction); the arithmetic is the same."""
    import numpy as np
    hp = train_hyper(cfg)
    ids = np.asarray(ids)[:steps * batch].reshape(steps, batch, -1)
    labels = np.asarray(labels)[:steps * batch].reshape(steps, batch)
    keep = max(1, int(round(batch * rows)))
    ids, labels = ids[:, :keep], labels[:, :keep]
    n_dev = len(devices) if devices else 1
    rb = min(ROW_BLOCK * n_dev, keep)
    if keep % rb or rb % n_dev:
        raise ValueError(f"{keep} rows do not divide into blocks of {rb} "
                         f"over {n_dev} devices")
    ids = ids.reshape(steps, keep // rb, rb, -1)
    labels = labels.reshape(steps, keep // rb, rb)
    if n_dev > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("rows",))
        ids = jax.device_put(
            ids, NamedSharding(mesh, P(None, None, "rows", None)))
        labels = jax.device_put(
            labels, NamedSharding(mesh, P(None, None, "rows")))
        params = jax.device_put(params, NamedSharding(mesh, P()))
    return _follow(_cfg_items(cfg), tuple(sorted(hp.items())), bool(lower),
                   max(1, min(int(first), steps)), params,
                   jnp.asarray(ids), jnp.asarray(labels))


def train_hyper(cfg: dict) -> dict:
    t = cfg["train"]
    return {k: float(t[k]) for k in ("learning_rate", "beta_1", "beta_2",
                                     "epsilon", "weight_decay")}
