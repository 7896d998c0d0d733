"""Plain reference of the tests' second trained architecture: a bag of
token embeddings (their mean over the sequence), one tanh layer and a
linear head, softmax cross-entropy and the AdamW step of
``reference/bert_classifier.py``. Its tree is its own: ``table``,
``hidden`` and ``out``, each of the last two a pair ``w`` / ``b``. It
exists to show that the harness takes an architecture by files alone; it
imports nothing of the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import bert_classifier as plain


def _sizes(cfg: dict):
    return cfg["tokens"], cfg["width"], cfg["inner"], cfg["classes"]


def make_params(seed: int, cfg: dict):
    v, w, f, c = _sizes(cfg)
    k = jax.random.split(plain.seed_key(seed), 3)
    return {"table": jax.random.normal(k[0], (v, w), jnp.float32) * 0.5,
            "hidden": {"w": jax.random.normal(k[1], (w, f)) * w ** -0.5,
                       "b": jnp.zeros((f,), jnp.float32)},
            "out": {"w": jax.random.normal(k[2], (f, c)) * f ** -0.5,
                    "b": jnp.zeros((c,), jnp.float32)}}


def make_data(seed: int, cfg: dict, steps: int, batch: int, seq: int):
    return plain.make_data(seed, {"vocab_size": cfg["tokens"]}, steps,
                           batch, seq)


def loss_fn(lower, params, ids, labels):
    h = jnp.mean(jnp.take(params["table"], ids, axis=0), axis=1)
    f = jnp.tanh(plain._mm(h, params["hidden"]["w"], lower)
                 + params["hidden"]["b"])
    logits = plain._mm(f, params["out"]["w"], lower) + params["out"]["b"]
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _follow(hp_items, lower, first, params, ids, labels):
    hp = dict(hp_items)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def step(carry, xs):
        params, m, v, k = carry
        loss, g = jax.value_and_grad(functools.partial(loss_fn, lower))(
            params, xs[0], xs[1])
        params, m, v = plain._adamw(hp, params, m, v, g, k + 1.0)
        return (params, m, v, k + 1.0), loss

    _, first_grad = jax.value_and_grad(functools.partial(loss_fn, lower))(
        params, ids[0], labels[0])
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
    """As ``bert_classifier.follow``, on one device."""
    import numpy as np
    keep = max(1, int(round(batch * rows)))
    ids = np.asarray(ids)[:steps * batch].reshape(steps, batch, -1)[:, :keep]
    labels = np.asarray(labels)[:steps * batch].reshape(steps, batch)[:, :keep]
    hp = tuple(sorted(plain.train_hyper(cfg).items()))
    return _follow(hp, bool(lower), max(1, min(int(first), steps)), params,
                   jnp.asarray(ids), jnp.asarray(labels))
