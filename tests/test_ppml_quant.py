"""PPML crypto (EncryptSupportive) + encrypted-model and int8 inference
wiring (InferenceModel.load_encrypted / quantize_model)."""

import numpy as np
import pytest

from zoo_tpu.pipeline.api.keras.engine.topology import Sequential
from zoo_tpu.pipeline.api.keras.layers import Dense
from zoo_tpu.pipeline.inference.inference_model import (
    InferenceModel,
    quantize_model,
    save_encrypted,
)
from zoo_tpu.ppml import EncryptSupportive


def test_cbc_roundtrip_bytes():
    data = bytes(range(256)) * 33  # not block-aligned
    enc = EncryptSupportive.encrypt_bytes_with_aes_cbc(
        data, "secret", "salty")
    assert enc[:16] != data[:16] and len(enc) > len(data)
    dec = EncryptSupportive.decrypt_bytes_with_aes_cbc(
        enc, "secret", "salty")
    assert dec == data


def test_cbc_roundtrip_string_base64():
    msg = "hello TPU enclave ✓"
    enc = EncryptSupportive.encrypt_with_aes_cbc(msg, "s3cret", "NaCl")
    assert enc != msg
    assert EncryptSupportive.decrypt_with_aes_cbc(
        enc, "s3cret", "NaCl") == msg


def test_gcm_roundtrip_and_tamper_detection():
    data = b"model bytes " * 100
    enc = EncryptSupportive.encrypt_bytes_with_aes_gcm(data, "k", "s")
    assert EncryptSupportive.decrypt_bytes_with_aes_gcm(
        enc, "k", "s") == data
    tampered = enc[:20] + bytes([enc[20] ^ 0xFF]) + enc[21:]
    with pytest.raises(ValueError, match="decryption failed"):
        EncryptSupportive.decrypt_bytes_with_aes_gcm(tampered, "k", "s")


def test_wrong_secret_fails():
    enc = EncryptSupportive.encrypt_bytes_with_aes_cbc(b"x" * 64, "a", "b")
    with pytest.raises(ValueError):
        EncryptSupportive.decrypt_bytes_with_aes_cbc(enc, "WRONG", "b")


def test_key_lengths():
    for key_len in (128, 256):
        enc = EncryptSupportive.encrypt_bytes_with_aes_cbc(
            b"abc", "s", "t", key_len=key_len)
        assert EncryptSupportive.decrypt_bytes_with_aes_cbc(
            enc, "s", "t", key_len=key_len) == b"abc"


def _small_model():
    m = Sequential(name="enc_test")
    m.add(Dense(32, activation="relu", input_shape=(16,)))
    m.add(Dense(4))
    m.build()
    return m


def test_encrypted_model_roundtrip(tmp_path):
    model = _small_model()
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    ref = np.asarray(model.predict(x, batch_size=8))
    p = str(tmp_path / "m.enc")
    save_encrypted(model, p, "topsecret", "pepper")
    # ciphertext on disk: loading it unencrypted must fail
    with pytest.raises(Exception):
        InferenceModel().load(p)
    im = InferenceModel().load_encrypted(p, "topsecret", "pepper")
    got = np.asarray(im.predict(x, batch_size=8))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_quantize_model_close_and_int8(tmp_path):
    model = _small_model()
    x = np.random.RandomState(1).randn(8, 16).astype(np.float32)
    ref = np.asarray(model.predict(x, batch_size=8))
    q = quantize_model(model)
    for key, group in q.params.items():
        if "dense" in key:
            assert group["W_q"].dtype == np.int8
            assert "W" not in group
    got = np.asarray(q.predict(x, batch_size=8))
    # int8 per-channel quantization: ~1% relative error budget
    assert np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-9) < 0.02


def test_load_quantized_from_disk(tmp_path):
    model = _small_model()
    x = np.random.RandomState(2).randn(4, 16).astype(np.float32)
    ref = np.asarray(model.predict(x, batch_size=4))
    p = str(tmp_path / "m.zoo")
    model.save(p)
    im = InferenceModel().load(p, quantize=True)
    got = np.asarray(im.predict(x, batch_size=4))
    assert np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-9) < 0.02


def test_quantized_model_refuses_fit():
    model = quantize_model(_small_model())
    model.compile(optimizer="adam", loss="mse")
    x = np.zeros((4, 16), np.float32)
    with pytest.raises(RuntimeError, match="inference-only"):
        model.fit(x, np.zeros((4, 4), np.float32), batch_size=4,
                  nb_epoch=1, verbose=0)


def test_quantize_conv_model(orca_ctx):
    """Int8 covers conv nets (the reference's headline int8 use —
    SSD/VGG inference): quantized conv predictions stay close to float,
    weights shrink to int8."""
    import jax.numpy as jnp

    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import (
        Conv2D, Dense, Flatten, GlobalAveragePooling2D)
    from zoo_tpu.pipeline.inference.inference_model import quantize_model

    m = Sequential()
    m.add(Conv2D(8, 3, 3, border_mode="same", dim_ordering="tf",
                 activation="relu", input_shape=(8, 8, 3)))
    m.add(Conv2D(8, 3, 3, border_mode="same", dim_ordering="tf"))
    m.add(GlobalAveragePooling2D(dim_ordering="tf"))
    m.add(Dense(4, activation="softmax"))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    x = np.random.RandomState(0).rand(6, 8, 8, 3).astype(np.float32)
    m.build()
    ref = np.asarray(m.predict(x, batch_size=6))

    quantize_model(m)
    for layer in m.layers:
        p = m.params[m._key_of(layer)]
        if "W_q" in p:
            assert p["W_q"].dtype == jnp.int8
            assert "W" not in p
    got = np.asarray(m.predict(x, batch_size=6))
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=0.05)
    # int8 is inference-only
    import pytest

    with pytest.raises(RuntimeError, match="quantized"):
        m.fit(x, np.zeros(6, np.int32), batch_size=6, nb_epoch=1)


def test_quantize_auto_falls_back_when_int8_loses(monkeypatch):
    """auto mode measures int8 against the float forward and restores
    the float weights when int8 does not win (the BENCH_r05 pathology:
    resnet50_int8_speedup = 0.974 — int8 *slower* than bf16)."""
    from zoo_tpu.pipeline.inference import inference_model as im

    rates = iter([1000.0, 800.0])  # float first, then int8: int8 loses
    monkeypatch.setattr(im, "_time_forward",
                        lambda model, xs, reps=3: next(rates))
    model = _small_model()
    x = np.random.RandomState(3).randn(4, 16).astype(np.float32)
    ref = np.asarray(model.predict(x, batch_size=4))
    out = im.quantize_model(model, mode="auto")
    assert out._quant_path == "bf16-fallback"
    assert abs(out._quant_speedup - 0.8) < 1e-6
    for key, group in out.params.items():
        if "dense" in key:
            assert "W" in group and "W_q" not in group
    # the restored model still predicts EXACTLY like the original
    np.testing.assert_allclose(
        np.asarray(out.predict(x, batch_size=4)), ref, atol=1e-6)
    assert not getattr(out, "_quantized", False)  # fit() still allowed


def test_quantize_auto_keeps_int8_when_it_wins(monkeypatch):
    from zoo_tpu.pipeline.inference import inference_model as im

    rates = iter([1000.0, 2000.0])  # int8 2x faster
    monkeypatch.setattr(im, "_time_forward",
                        lambda model, xs, reps=3: next(rates))
    out = im.quantize_model(_small_model(), mode="auto")
    assert out._quant_path == "int8"
    assert any("W_q" in g for g in out.params.values()
               if isinstance(g, dict))


def test_quantize_mode_off_and_env_override(monkeypatch):
    from zoo_tpu.pipeline.inference import inference_model as im

    out = im.quantize_model(_small_model(), mode="off")
    assert out._quant_path == "bf16"
    assert all("W_q" not in g for g in out.params.values()
               if isinstance(g, dict))
    # ZOO_INT8_MODE fills in an UNSPECIFIED mode...
    monkeypatch.setenv("ZOO_INT8_MODE", "off")
    out2 = im.quantize_model(_small_model())
    assert out2._quant_path == "bf16"
    # ...but an explicit call-site mode always wins (an A/B relies on
    # mode="force" measuring real int8 whatever the ambient env says)
    out3 = im.quantize_model(_small_model(), mode="force")
    assert out3._quant_path == "int8"
    monkeypatch.setenv("ZOO_INT8_MODE", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        im.quantize_model(_small_model())


def test_quantize_auto_measures_for_real():
    """No stubs: auto mode on a real model picks SOME path, the model
    stays usable, and the measured ratio is recorded."""
    from zoo_tpu.pipeline.inference import inference_model as im

    model = _small_model()
    x = np.random.RandomState(4).randn(4, 16).astype(np.float32)
    ref = np.asarray(model.predict(x, batch_size=4))
    out = im.quantize_model(model, mode="auto")
    assert out._quant_path in ("int8", "bf16-fallback")
    assert out._quant_speedup > 0
    got = np.asarray(out.predict(x, batch_size=4))
    assert np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-9) < 0.02


@pytest.fixture(autouse=True)
def _fresh_verdict_cache():
    """Each test measures its own world: the auto-verdict cache would
    otherwise replay a verdict stubbed by an earlier test (same probe
    architecture across the whole file)."""
    from zoo_tpu.pipeline.inference import inference_model as im

    im._AUTO_VERDICT_CACHE.clear()
    yield
    im._AUTO_VERDICT_CACHE.clear()


def test_quantize_auto_verdict_cached_per_architecture(monkeypatch):
    """The auto microbench runs ONCE per (architecture, sample shape):
    a second quantize_model of the same topology replays the cached
    verdict — no timing calls — the rolling-reload / A-B replica case."""
    from zoo_tpu.pipeline.inference import inference_model as im

    calls = []

    def timed(model, xs, reps=3):
        calls.append(1)
        return [1000.0, 2000.0][len(calls) - 1]  # int8 wins

    monkeypatch.setattr(im, "_time_forward", timed)
    out1 = im.quantize_model(_small_model(), mode="auto")
    assert out1._quant_path == "int8" and len(calls) == 2
    out2 = im.quantize_model(_small_model(), mode="auto")
    assert len(calls) == 2, "cache miss re-ran the microbench"
    assert out2._quant_path == "int8"
    assert out2._quant_speedup == out1._quant_speedup
    assert any("W_q" in g for g in out2.params.values()
               if isinstance(g, dict))
    # a DIFFERENT architecture is a different verdict
    m3 = Sequential()
    m3.add(Dense(8, input_shape=(16,)))
    m3.compile(optimizer="sgd", loss="mse")
    m3.build()
    calls.clear()
    im.quantize_model(m3, mode="auto")
    assert len(calls) == 2


def test_quantize_path_published_to_metrics():
    """Every quantize_model decision lands in the scrape as
    zoo_quant_path_info{path,speedup} with exactly one series at 1."""
    from zoo_tpu.obs.metrics import get_registry
    from zoo_tpu.pipeline.inference import inference_model as im

    im.quantize_model(_small_model(), mode="force")
    text = get_registry().render_prometheus()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("zoo_quant_path_info")]
    assert any('path="int8"' in ln and ln.rstrip().endswith(" 1")
               for ln in lines), lines
    im.quantize_model(_small_model(), mode="off")
    text = get_registry().render_prometheus()
    live = [ln for ln in text.splitlines()
            if ln.startswith("zoo_quant_path_info")
            and ln.rstrip().endswith(" 1")]
    assert len(live) == 1 and 'path="bf16"' in live[0], live
