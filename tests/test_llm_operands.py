"""One operand hand-off a device call (``serving/llm/model.py``): every
serving executable takes its host operands as ONE packed buffer of
int32 words, a fresh one a dispatch.

* the count: ``zoo_llm_operand_transfers_total{call}`` grows by exactly
  one a dispatch, on every entry point of every served architecture;
* the bits: what is packed on the host is what the jitted body unpacks,
  for every lane dtype;
* the hazard: two ticks and a chunk in flight each see their own
  operands, and no two dispatches share a buffer.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zoo_tpu.obs.catalog import METRICS
from zoo_tpu.obs.metrics import counter
from zoo_tpu.serving.llm.engine import LLMEngine
from zoo_tpu.serving.llm.model import OperandLayout

from llm_tick import tick

CALLS = ("decode", "prefill_chunk", "verify", "prefill")


def _llama(**kw):
    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg = LlamaConfig(vocab=64, hidden=32, n_block=2, n_head=4,
                      n_kv_head=2, intermediate=64, rope_theta=10000.0)
    return PagedLlamaModel(cfg, seed=0, num_slots=2, block_size=8,
                           num_blocks=16, max_blocks_per_seq=4,
                           prefill_buckets=(8, 16), prefill_chunk=8,
                           **{"spec_k": 2, **kw})


def _glm(**kw):
    from zoo_tpu.models.llm.glm_moe_lite import tiny_glm_moe_lite_config
    from zoo_tpu.serving.llm.model_mla import PagedGlmMoeLiteModel
    return PagedGlmMoeLiteModel(
        tiny_glm_moe_lite_config(64), num_slots=2, block_size=8,
        num_blocks=16, max_blocks_per_seq=4, prefill_buckets=(8, 16),
        prefill_chunk=8, kv_dtype="f32", **{"spec_k": 2, **kw})


def _sala(**kw):
    from zoo_tpu.models.llm.minicpm_sala import tiny_minicpm_sala_config
    from zoo_tpu.serving.llm.model_sala import PagedMiniCpmSalaModel
    return PagedMiniCpmSalaModel(
        tiny_minicpm_sala_config(64), num_slots=2, block_size=8,
        num_blocks=16, max_blocks_per_seq=4, prefill_buckets=(8, 16),
        prefill_chunk=8, kv_dtype="f32", **{"spec_k": 0, **kw})


ARCHS = {"llama": _llama, "glm_moe_lite": _glm, "minicpm_sala": _sala}


@pytest.fixture(scope="module")
def models():
    """One toy model an architecture, built when first asked for."""
    built = {}

    def get(arch):
        if arch not in built:
            built[arch] = ARCHS[arch]()
        return built[arch]
    return get


def _lanes(S, temp=0.0, seeds=None):
    return (np.full(S, temp, np.float32), np.zeros(S, np.int32),
            np.full(S, 0.95, np.float32),
            np.zeros(S, np.uint32) if seeds is None
            else np.asarray(seeds, np.uint32))


def _dispatch(model, call):
    """One dispatch of ``call``, read back so that nothing is in flight
    when the next starts."""
    S, W = model.num_slots, model.max_blocks_per_seq
    tables = np.zeros((S, W), np.int32)
    tables[0, :2] = (3, 4)
    slot = {"slot": 0} if model.UNPAGED_LEAVES else {}
    if call == "decode":
        return model.read_tokens(model.decode_step(
            None, np.ones(S, np.int32), np.ones(S, bool), tables,
            np.asarray([9, 0], np.int32), _lanes(S)))
    if call == "verify":
        return model.read_tokens(model.verify_step(
            np.ones((S, model.spec_k + 1), np.int32), tables,
            np.asarray([9, 0], np.int32), _lanes(S)))
    if call == "prefill_chunk":
        return int(model.prefill_chunk(np.arange(5), 0, 5, tables[0],
                                       **slot))
    return model.prefill(np.arange(11), tables[0], **slot)


# the third architecture refuses speculative decoding at construction
@pytest.mark.parametrize("arch,call", [
    (a, c) for a in ARCHS for c in CALLS
    if (a, c) != ("minicpm_sala", "verify")])
def test_one_operand_hand_off_a_dispatch(models, arch, call):
    """Every entry point hands the device its host operands once: the
    counter, the model's own count and ``stats()`` each grow by one a
    dispatch and only under the call's own label (the parent's code
    made 9 / 8-9 / 7 / 7-8 transfers here)."""
    assert METRICS["zoo_llm_operand_transfers_total"] == \
        ("counter", ("call",))
    model = models(arch)
    fam = counter("zoo_llm_operand_transfers_total", labels=("call",))

    def read():
        return {c: fam.labels(call=c).value for c in CALLS}

    for _ in range(3):
        before, own = read(), dict(model.operand_transfers)
        _dispatch(model, call)
        after = read()
        assert {c: after[c] - before[c] for c in CALLS} == \
            {c: float(c == call) for c in CALLS}
        assert model.operand_transfers == \
            {c: own[c] + (c == call) for c in CALLS}


def test_engine_stats_count_one_hand_off_a_tick():
    """Through the engine: a prompt of two chunks and its decode ticks
    leave one hand-off a chunk and one a tick in ``stats()``."""
    model = _llama(spec_k=0)
    eng = LLMEngine(model)
    h = eng.submit(np.arange(13) % 60, 5)
    while not h.done:
        tick(eng)
    stats = eng.stats()
    assert h.outcome == "ok" and len(h.tokens) == 5
    assert stats["operand_transfers"] == {
        "decode": stats["decode_steps"], "prefill_chunk": 2,
        "verify": 0, "prefill": 0}
    assert stats["decode_steps"] >= 4


FIELDS = [("ids", (1, 8), np.int32), ("flag", (4,), np.bool_),
          ("table", (2, 5), np.int32), ("temp", (), np.float32),
          ("topps", (4,), np.float32), ("seed", (), np.uint32),
          ("seeds", (4,), np.uint32), ("n", (), np.int32)]


@pytest.mark.parametrize("name,value", [
    ("temp", np.float32(0.7)),
    ("topps", np.asarray([0.95, 0.7, 1.0, 1e-30], np.float32)),
    ("seed", np.uint32(0xFFFFFFFF)),
    ("seeds", np.asarray([0, 2 ** 31, 2 ** 31 + 5, 0xFFFFFFFF],
                         np.uint32)),
    ("flag", np.asarray([True, False, False, True])),
    # a full-width table: no entry left at the trash block
    ("table", np.arange(1, 11, dtype=np.int32).reshape(2, 5) * 1000),
    # a short last chunk: three real ids, zero padding behind them
    ("ids", np.asarray([[63, 1, 2, 0, 0, 0, 0, 0]], np.int32)),
    ("n", np.int32(-2 ** 31)),
])
def test_pack_unpack_is_bit_exact(name, value):
    """What the host packs is what the jitted body unpacks, bit for
    bit and in the field's own dtype and shape; the other fields are
    untouched by it."""
    layout = OperandLayout(FIELDS)
    assert layout.words == 8 + 4 + 10 + 1 + 4 + 1 + 4 + 1
    values = {n: np.zeros(shape, dt) for n, shape, dt in FIELDS}
    values[name] = value
    buf = layout.pack(**values)
    assert buf.dtype == np.int32 and buf.shape == (layout.words,)
    assert layout.aval().shape == buf.shape
    out = jax.jit(layout.unpack)(buf)
    for n, shape, dt in FIELDS:
        got = np.asarray(out[n])
        assert got.dtype == dt and got.shape == shape, n
        assert got.tobytes() == np.asarray(values[n], dt).tobytes(), n


def test_pack_refuses_an_operand_of_another_size():
    layout = OperandLayout(FIELDS)
    values = {n: np.zeros(shape, dt) for n, shape, dt in FIELDS}
    values["table"] = np.zeros((2, 4), np.int32)
    with pytest.raises(ValueError, match="table"):
        layout.pack(**values)
    model = _llama(spec_k=0)
    with pytest.raises(ValueError, match="block_tables"):
        model.decode_step(None, np.ones(2, np.int32), np.ones(2, bool),
                          np.zeros((2, 3), np.int32),
                          np.zeros(2, np.int32), _lanes(2))


def test_sampling_operands_reach_the_sampler_exactly(monkeypatch):
    """Through a whole entry point: temperature 0.7, top-p 0.95 and a
    seed over 2**31 arrive at the sampler as the very float32 / uint32
    the nine separate operands carried."""
    from zoo_tpu.serving.llm import model as M

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    # the executables end in a sampler; hand its operands out instead
    monkeypatch.setattr(
        M, "_sample_row", lambda last, temp, topk, topp, seed, index:
        jnp.stack([bits(temp), topk, bits(topp), bits(seed), index]))
    monkeypatch.setattr(
        M, "_sample_tokens",
        lambda logits, temps, topks, topps, seeds, index:
        jnp.stack([bits(temps), topks, bits(topps), bits(seeds)]))
    model = _llama(spec_k=0)
    row = np.asarray([3, 4, 0, 0], np.int32)
    seed = 2 ** 31 + 12345
    want = [np.float32(0.7).view(np.int32), 40,
            np.float32(0.95).view(np.int32),
            np.uint32(seed).view(np.int32)]
    got = np.asarray(model.prefill_chunk(
        np.arange(5), 0, 5, row, sampling=(0.7, 40, 0.95, seed)))
    assert got.tolist() == want + [5]
    tables = np.zeros((2, 4), np.int32)
    tables[0] = row
    lanes = (np.asarray([0.7, 0.0], np.float32),
             np.asarray([40, 0], np.int32),
             np.asarray([0.95, 1.0], np.float32),
             np.asarray([seed, 0xFFFFFFFF], np.uint32))
    got = np.asarray(model.decode_step(
        None, np.ones(2, np.int32), np.ones(2, bool), tables,
        np.asarray([5, 0], np.int32), lanes))
    assert got[:, 0].tolist() == want
    assert got[:, 1].tolist() == [0, 0, np.float32(1.0).view(np.int32), -1]


@pytest.mark.parametrize("arch", list(ARCHS))
def test_operands_in_flight_keep_their_own(monkeypatch, arch):
    """Two decode ticks and a chunk dispatched back to back with
    DIFFERENT tables, positions and seeds and nothing read between
    them, then all read: each saw its own operands, that is, the three
    read what three separately blocked calls read on a second model of
    the same weights; and the three hand-offs share no memory (a fresh
    buffer a call: the copy to the device is asynchronous and the CPU
    backend may alias numpy memory)."""
    piped, blocked = ARCHS[arch](spec_k=0), ARCHS[arch](spec_k=0)
    S = piped.num_slots
    slot = (lambda i: {"slot": i}) if piped.UNPAGED_LEAVES \
        else (lambda i: {})

    def calls(model, wait):
        # both slots hold a prompt of 8 first (blocked on both sides)
        for i, blocks in enumerate(((3, 4, 5, 0), (6, 7, 8, 0))):
            int(model.prefill_chunk(np.arange(8) + i, 0, 8,
                                    np.asarray(blocks, np.int32),
                                    **slot(i)))
        t1 = np.asarray([(3, 4, 5, 0), (0, 0, 0, 0)], np.int32)
        t2 = np.asarray([(3, 4, 5, 0), (6, 7, 8, 0)], np.int32)
        first = model.decode_step(
            None, np.asarray([7, 9], np.int32), np.ones(S, bool), t1,
            np.asarray([8, 0], np.int32),
            _lanes(S, 0.7, [2 ** 31 + 1, 5]))
        wait(first)
        second = model.decode_step(
            first, np.asarray([0, 11], np.int32),
            np.asarray([False, True]), t2, np.asarray([9, 8], np.int32),
            _lanes(S, 0.9, [77, 0xFFFFFFFF]))
        wait(second)
        chunk = model.prefill_chunk(
            np.asarray([5, 6, 7]), 8, 11, np.asarray((6, 7, 8, 0), np.int32),
            sampling=(0.8, 0, 0.95, 2 ** 31 + 9), **slot(1))
        wait(chunk)
        out = [model.read_tokens(first), model.read_tokens(second),
               int(chunk)]
        cache = {k: np.asarray(v) for k, v in model._cache.items()}
        return out, cache

    handed = []
    pack = OperandLayout.pack

    def keep(self, **values):
        handed.append(pack(self, **values))
        return handed[-1]

    with monkeypatch.context() as m:
        m.setattr(OperandLayout, "pack", keep)
        got, got_cache = calls(piped, lambda x: None)
    want, want_cache = calls(
        blocked,
        lambda x: jax.block_until_ready(getattr(x, "tokens", x)))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    for name in want_cache:
        np.testing.assert_array_equal(got_cache[name], want_cache[name],
                                      err_msg=name)
    assert len(handed) == 5
    for i, a in enumerate(handed):
        for b in handed[i + 1:]:
            assert not np.shares_memory(a, b)
