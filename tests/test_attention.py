import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zoo_tpu.ops.attention import dot_product_attention, merge_heads, split_heads
from zoo_tpu.pipeline.api.keras.layers.self_attention import (
    BERT,
    LayerNorm,
    TransformerLayer,
)


def test_dot_product_attention_matches_manual():
    rs = np.random.RandomState(0)
    q = rs.randn(1, 2, 4, 8).astype(np.float32)
    k = rs.randn(1, 2, 4, 8).astype(np.float32)
    v = rs.randn(1, 2, 4, 8).astype(np.float32)
    out = np.asarray(dot_product_attention(*map(jnp.asarray, (q, k, v))))
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    manual = np.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(out, manual, rtol=1e-4)


def test_attention_mask_blocks_positions():
    rs = np.random.RandomState(0)
    q = k = v = jnp.asarray(rs.randn(1, 1, 4, 4).astype(np.float32))
    mask = jnp.asarray([[True, True, False, False]])[:, None, None, :]
    out = dot_product_attention(q, k, v, mask=mask)
    # perturb masked-out positions; output must not change
    k2 = k.at[:, :, 2:].set(99.0)
    v2 = v.at[:, :, 2:].set(99.0)
    out2 = dot_product_attention(q, k2, v2, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-5)


def test_split_merge_heads_roundtrip():
    x = jnp.arange(2 * 3 * 8.0).reshape(2, 3, 8)
    np.testing.assert_array_equal(
        np.asarray(merge_heads(split_heads(x, 4))), np.asarray(x))


@pytest.mark.heavy
def test_transformer_causal_no_leak():
    t = TransformerLayer(vocab=50, seq_len=8, n_block=2, hidden_size=16,
                         n_head=2)
    p = t.build(jax.random.PRNGKey(0), (None, 8))
    ids = np.random.RandomState(0).randint(0, 50, (2, 8))
    y1 = np.asarray(t.call(p, jnp.asarray(ids)))
    ids2 = ids.copy()
    ids2[:, -1] = (ids2[:, -1] + 7) % 50
    y2 = np.asarray(t.call(p, jnp.asarray(ids2)))
    np.testing.assert_allclose(y1[:, :-1], y2[:, :-1], atol=1e-6)
    assert np.abs(y1[:, -1] - y2[:, -1]).max() > 1e-4


@pytest.mark.heavy
def test_bert_outputs_and_mask():
    b = BERT(vocab=60, hidden_size=16, n_block=2, n_head=2, seq_len=8,
             intermediate_size=32, max_position_len=8)
    p = b.build(jax.random.PRNGKey(0), (None, 8))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 60, (2, 8)))
    seg = jnp.zeros((2, 8), jnp.int32)
    mask = jnp.asarray(np.array([[1] * 8, [1] * 4 + [0] * 4]))
    seq = b.call(p, [ids, seg, mask])
    assert seq.shape == (2, 8, 16)
    pool = b.pooled_output(p, seq)
    assert pool.shape == (2, 16)
    # masked tokens must not affect unmasked outputs of row 1
    ids2 = np.asarray(ids).copy()
    ids2[1, 6] = (ids2[1, 6] + 3) % 60
    seq2 = b.call(p, [jnp.asarray(ids2), seg, mask])
    np.testing.assert_allclose(np.asarray(seq)[1, :4],
                               np.asarray(seq2)[1, :4], atol=1e-5)


def test_layernorm():
    ln = LayerNorm()
    p = ln.build(jax.random.PRNGKey(0), (None, 6))
    x = jnp.asarray(np.random.RandomState(0).randn(3, 6) * 5 + 2)
    y = np.asarray(ln.call(p, x))
    np.testing.assert_allclose(y.mean(-1), 0, atol=1e-5)
    np.testing.assert_allclose(y.std(-1), 1, atol=1e-2)


@pytest.mark.slow
def test_tiny_bert_classifier_trains(orca_ctx):
    """BERT + pooler + head, end-to-end fit on a toy task: does the first
    token id determine the class."""
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.engine.base import Layer
    from zoo_tpu.pipeline.api.keras.layers import Dense, Lambda
    from zoo_tpu.pipeline.api.keras.optimizers import Adam

    rs = np.random.RandomState(0)
    n, T = 128, 8
    x = rs.randint(0, 20, (n, T)).astype(np.int32)
    y = (x[:, 0] % 2).astype(np.int32)

    m = Sequential()
    m.add(TransformerLayer(vocab=20, seq_len=T, n_block=1, hidden_size=16,
                           n_head=2, hidden_drop=0.0, attn_drop=0.0,
                           bidirectional=True, input_shape=(T,)))
    m.add(Lambda(lambda h: h[:, 0], output_shape=(16,)))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer=Adam(lr=0.01),
              loss="sparse_categorical_crossentropy", metrics=["accuracy"])
    hist = m.fit(x, y, batch_size=32, nb_epoch=8, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0] * 0.7


@pytest.mark.parametrize("remat", ["dots", True])
def test_transformer_remat_trains(orca_ctx, remat):
    """remat policies compile and train (the benchmark's BERT cell runs
    remat='dots'); loss matches the no-remat path step-for-step
    (remat changes memory, never math)."""
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import Dense, Lambda, BERT

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 50, (32, 8)).astype(np.int32)
    y = rs.randint(0, 2, 32).astype(np.int32)

    losses = {}
    for rm in (False, remat):
        m = Sequential()
        m.add(BERT(vocab=50, hidden_size=16, n_block=2, n_head=2,
                   seq_len=8, intermediate_size=32, hidden_p_drop=0.0,
                   attn_p_drop=0.0, max_position_len=8, remat=rm,
                   input_shape=(8,)))
        m.add(Lambda(lambda h: h[:, 0], output_shape=(16,)))
        m.add(Dense(2))
        m.compile(optimizer="sgd",
                  loss="sparse_categorical_crossentropy_from_logits")
        h = m.fit(ids, y, batch_size=16, nb_epoch=2, shuffle=False,
                  verbose=0, seed=0)
        losses[rm] = h["loss"]
    np.testing.assert_allclose(losses[False], losses[remat], rtol=1e-4)
