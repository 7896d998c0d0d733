"""The program times itself (docs/observability.md "The span ring"): the
always-on ring of ``zoo_tpu.obs.tracing``, the spans of the engine's
tick, a request's life, the fit seam and every compile, the ``zoo:``
annotations in a profile, and the names the benchmark matches."""

import glob
import inspect
import os
import statistics
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zoo_tpu.obs import tracing
from zoo_tpu.obs.metrics import get_registry
from zoo_tpu.serving.llm.engine import TICK_LEAF_SPANS, LLMEngine
from zoo_tpu.serving.llm.synthetic import SyntheticLLMModel
from zoo_tpu.util.resilience import clear_faults, inject


@pytest.fixture(autouse=True)
def _no_sink():
    tracing.stop_tracing()
    yield
    tracing.stop_tracing()
    clear_faults()


def _counter(name):
    return sum(c["value"] for c in get_registry().snapshot()["counters"]
               if c["name"] == name)


# ------------------------------------------------------------------ the ring

def test_ring_records_with_no_sink_and_filters_by_name_and_time():
    assert not tracing.tracing_enabled()
    t_a = time.perf_counter()
    with tracing.span("t.ring.a", k=1) as sid:
        pass
    t_b = time.perf_counter()
    sp = tracing.span("t.ring.b")
    with sp:
        sp.note(n=3)
    assert sid is None                    # no JSONL sink, no span id
    (a,) = tracing.recent_spans("t.ring.a", since=t_a)
    name, t0, dur, tid, attrs = a
    assert name == "t.ring.a" and t_a <= t0 <= t_b and 0 <= dur < 0.1
    assert tid == threading.get_ident() and attrs == {"k": 1}
    assert tracing.recent_spans("t.ring.b", since=t_b)[0][4] == {"n": 3}
    assert tracing.recent_spans("t.ring.a", since=t_b) == []
    assert tracing.recent_spans("t.ring.b", until=t_b) == []
    both = [s[0] for s in tracing.recent_spans(since=t_a)
            if s[0].startswith("t.ring.")]
    assert both == ["t.ring.a", "t.ring.b"]


def test_emit_span_reaches_the_ring_only_with_a_perf_counter_start():
    t = time.perf_counter()
    tracing.emit_span("t.emit.wall_only", time.time(), 0.5, rid="x")
    tracing.emit_span("t.emit.both", time.time(), 0.25, t0=t, rid="y")
    assert tracing.recent_spans("t.emit.wall_only") == []
    (s,) = tracing.recent_spans("t.emit.both")
    assert s[1:3] == (t, 0.25) and s[4] == {"rid": "y"}


def test_ring_state_shows_a_wrap():
    before = tracing.ring_state()
    assert before["capacity"] == tracing.RING_CAPACITY
    t = time.perf_counter()
    for _ in range(tracing.RING_CAPACITY + 10):
        tracing.emit_span("t.wrap", 0.0, 0.0, t0=time.perf_counter())
    after = tracing.ring_state()
    assert after["written"] == before["written"] \
        + tracing.RING_CAPACITY + 10
    assert after["written"] > after["capacity"]
    # the oldest spans are gone: what is left began after the flood did
    assert after["oldest_t0"] > t
    assert len(tracing.recent_spans()) == tracing.RING_CAPACITY


def test_a_span_costs_microseconds_with_no_sink():
    """The floor docs/observability.md states: under 20 us at the
    median with no sink and no profiler session (measured ~1 us)."""
    costs = []
    for _ in range(5000):
        t = time.perf_counter()
        with tracing.span("t.cost"):
            pass
        costs.append(time.perf_counter() - t)
    assert statistics.median(costs) < 20e-6, statistics.median(costs)


# ------------------------------------------------- the engine's tick, a request

def _drive(overlap, new_tokens=55, tick_s=0.002):
    """Four streams of ``new_tokens`` through an engine over the
    synthetic model, two at a time, each tick ``tick_s`` on the fake
    device. Returns (engine, its scheduler thread's id, finished
    handles, (t_start, t_end))."""
    model = SyntheticLLMModel(num_slots=2, block_size=4, num_blocks=256,
                              max_blocks_per_seq=64, max_prompt_len=64,
                              prefill_chunk=4)
    eng = LLMEngine(model, overlap=overlap)
    inject("llm.decode", action=lambda **ctx: time.sleep(tick_s))
    t_start = time.perf_counter()
    eng.start()
    sched = eng._thread.ident
    rng = np.random.default_rng(0)
    handles = [eng.submit(rng.integers(1, 90, 9), new_tokens)
               for _ in range(4)]
    deadline = time.monotonic() + 30
    while not all(h.done for h in handles):
        assert time.monotonic() < deadline, "streams did not finish"
        time.sleep(0.01)
    t_end = time.perf_counter()
    assert eng.stats()["decode_steps"] >= 2 * (new_tokens - 1)
    eng.stop()
    return eng, sched, handles, (t_start, t_end)


@pytest.mark.parametrize("overlap", [True], ids=["overlap"])
def test_scheduler_leaf_spans_are_disjoint_and_cover_the_loop(overlap):
    # 200 ticks of 10 ms: what the loop spends between two leaves (its
    # histograms, the hand-over to the readback thread: 50-90 us a pass)
    # is 1% of such a tick and 0.2% of a real one
    _, sched, handles, (t0, t1) = _drive(overlap, new_tokens=101,
                                         tick_s=0.010)
    leaves = sorted((s[1], s[1] + s[2], s[0])
                    for s in tracing.recent_spans(since=t0, until=t1)
                    if s[3] == sched and s[0] in TICK_LEAF_SPANS)
    assert len(leaves) > 1500
    for (_, end, a), (start, _, b) in zip(leaves, leaves[1:]):
        assert start >= end - 1e-6, f"{a} overlaps {b}"
    wall = leaves[-1][1] - leaves[0][0]
    covered = sum(e - s for s, e, _ in leaves)
    assert covered / wall >= 0.98, covered / wall
    names = {n for _, _, n in leaves}
    want = {"llm.tick.lock_wait", "llm.tick.sweep_admit",
            "llm.tick.prefill", "llm.tick.grow_build",
            "llm.tick.dispatch", "llm.tick.idle",
            "llm.tick.inflight_wait"}
    assert want <= names, want - names
    # one complete span a tick, inside twins of the harness's
    for name in ("llm.tick.schedule", "llm.tick.decode"):
        assert len(tracing.recent_spans(name, since=t0, until=t1)) >= 200
    # the readback thread's spans are its own
    rb = {s[3] for s in tracing.recent_spans("llm.readback.apply",
                                             since=t0)}
    assert rb and sched not in rb
    chunks = [s[4]["chunks"] for s in tracing.recent_spans(
        "llm.tick.prefill", since=t0, until=t1)]
    assert sum(chunks) == 4 * 3       # 9-token prompts, chunk 4


@pytest.mark.parametrize("overlap", [True], ids=["overlap"])
def test_queue_wait_and_prefill_total_sum_to_the_ttft(overlap):
    _, _, handles, (t0, _) = _drive(overlap)
    by_rid = {}
    for name in ("llm.queue_wait", "llm.prefill_total", "llm.stream"):
        for s in tracing.recent_spans(name, since=t0):
            by_rid.setdefault(s[4]["rid"], {})[name] = s
    for h in handles:
        mine = by_rid[h.id]
        both = mine["llm.queue_wait"][2] + mine["llm.prefill_total"][2]
        assert both == pytest.approx(h.ttft(), abs=1e-3)
        assert mine["llm.queue_wait"][1] == h.created
        assert mine["llm.prefill_total"][4]["chunks"] == 3
        assert mine["llm.prefill_total"][4]["prompt_tokens"] == 9
        assert mine["llm.stream"][4]["outcome"] == "ok"
    # no span per token: four streams of 55 tokens left a few spans each
    per_request = [s for s in tracing.recent_spans(since=t0)
                   if s[4] and s[4].get("rid") in by_rid]
    assert len(per_request) == 4 * (3 + 3)    # 3 chunks + the 3 above


def test_zoo_annotations_lie_in_a_profile_beside_the_ring(tmp_path):
    """Under a profiler session every span is a ``zoo:`` annotation in
    the ``.xplane.pb``; one offset maps the ring's clock onto it."""
    jax.block_until_ready(jnp.ones(4) + 1)
    with jax.profiler.trace(str(tmp_path)):
        _, _, _, (t0, t1) = _drive(True)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    starts = sorted(ev.start_ns * 1e-9 for plane in data.planes
                    for line in plane.lines for ev in line.events
                    if ev.name == "zoo:llm.tick.dispatch")
    ring = sorted(s[1] for s in tracing.recent_spans(
        "llm.tick.dispatch", since=t0, until=t1))
    assert len(starts) == len(ring) >= 100
    shift = statistics.median(a - b for a, b in zip(starts, ring))
    assert max(abs(a - b - shift) for a, b in zip(starts, ring)) < 1e-3


# ------------------------------------------------------------- the fit seam

def test_fit_epochs_are_the_sum_of_their_three_leaves():
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import Dense
    m = Sequential()
    m.add(Dense(8, input_shape=(4,), activation="relu"))
    m.add(Dense(1))
    m.compile(optimizer="sgd", loss="mse")
    x = np.random.RandomState(0).randn(64, 4).astype("float32")
    t0 = time.perf_counter()
    m.fit(x, x.sum(1, keepdims=True), batch_size=16, nb_epoch=3, verbose=0)
    fit = [s for s in tracing.recent_spans(since=t0)
           if s[0].startswith("fit.")]
    names = [s[0] for s in fit]
    assert names[0] == "fit.setup" and names[-1] == "fit.params_to_host"
    assert names[1:-1] == ["fit.epoch.launch", "fit.epoch.loss_sync",
                           "fit.epoch.host", "fit.epoch"] * 3
    for i in range(3):
        launch, sync, host, epoch = fit[1 + 4 * i:5 + 4 * i]
        assert launch[2] + sync[2] + host[2] == pytest.approx(
            epoch[2], abs=1e-3)
        assert epoch[1] <= launch[1] and \
            host[1] + host[2] <= epoch[1] + epoch[2] + 1e-6


# --------------------------------------------------------------- compiles

def test_a_fresh_jit_is_one_compile_span_and_a_second_call_none():
    tracing.watch_compiles()
    assert tracing.watch_compiles() is False          # idempotent

    @jax.jit
    def obs_spans_fresh_step(x):
        return x * 3 + 1

    n0 = _counter("zoo_jit_compiles_total")
    s0 = _counter("zoo_jit_compile_seconds_total")
    x = jax.block_until_ready(jnp.ones(7))
    n_before = _counter("zoo_jit_compiles_total")
    t0 = time.perf_counter()
    jax.block_until_ready(obs_spans_fresh_step(x))
    mine = [s for s in tracing.recent_spans("jit.compile", since=t0 - 1)
            if s[4]["fun"] == "jit(obs_spans_fresh_step)"]
    assert len(mine) == 1 and mine[0][2] > 0
    assert any(s[4]["fun"].endswith("obs_spans_fresh_step")
               or s[4]["fun"].endswith("obs_spans_fresh_step)")
               for s in tracing.recent_spans("jit.lower", since=t0 - 1))
    assert _counter("zoo_jit_compiles_total") == n_before + 1 > n0
    assert _counter("zoo_jit_compile_seconds_total") > s0
    t1 = time.perf_counter()
    jax.block_until_ready(obs_spans_fresh_step(x))
    assert tracing.recent_spans("jit.compile", since=t1) == []
    assert _counter("zoo_jit_compiles_total") == n_before + 1


# ----------------------------------- the names the benchmark's files match

def _tiny_paged_model(**kw):
    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    cfg = LlamaConfig(vocab=64, hidden=32, n_block=2, n_head=4,
                      n_kv_head=2, intermediate=64)
    return PagedLlamaModel(cfg, num_slots=2, block_size=4, num_blocks=16,
                           max_blocks_per_seq=4, prefill_buckets=(8,),
                           prefill_chunk=8, **kw)


def _lowered_steps(model):
    """The decode and the chunk executables as lowered text, each from
    the previous tick's tokens (decode) and its one packed operand."""
    def text(fn, *args):
        return fn.lower(model.params, model._cache,
                        *args).as_text(debug_info=True)
    return (text(model._decode, model._zero_tokens,
                 model._layouts["decode"].aval()),
            text(model._prefill_chunked,
                 model._layouts["prefill_chunk"].aval()))


def test_jitted_steps_kernels_and_scopes_keep_their_names():
    """``benchmarks/metrics/*.json`` match executables by function name
    (``decode_fn``, ``epoch_fn``) and the paged decode kernel by its
    own; the ``zoo.*`` scopes carry the rest of a step's operations."""
    from zoo_tpu.pipeline.api.keras.engine.topology import KerasNet
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    for fn in ("_decode_fn", "_prefill_chunk_fn", "_prefill_fn",
               "_verify_fn"):
        assert callable(getattr(PagedLlamaModel, fn))
    assert "def epoch_fn(" in inspect.getsource(
        KerasNet._build_epoch_train_step)
    model = _tiny_paged_model(kv_dtype="int8", decode_impl="flash",
                              prefill_impl="flash")
    decode, chunk = _lowered_steps(model)
    assert "jit(_decode_fn)" in decode
    assert "jit(_prefill_chunk_fn)" in chunk
    assert "zoo_paged_decode" in decode and "zoo_paged_prefill" in chunk
    for scope in ("zoo.attn_proj", "zoo.kv_append", "zoo.paged_attend",
                  "zoo.mlp", "zoo.lm_head", "zoo.sample"):
        assert scope in decode and scope in chunk, scope


def test_the_latent_moe_step_keeps_the_names_and_counts_its_experts():
    """The second architecture runs the same ``_decode_fn`` /
    ``_prefill_chunk_fn`` under the scopes the accepted metric files
    match, adds its own (``zoo.moe_route``, ``zoo.moe_experts``,
    ``zoo.moe_shared``, the kernel ``zoo_mla_decode``), and its two
    counters are in the catalog and move with a decode tick."""
    from zoo_tpu.models.llm.glm_moe_lite import tiny_glm_moe_lite_config
    from zoo_tpu.obs.catalog import METRICS
    from zoo_tpu.serving.llm.model import PagedDecoderModel
    from zoo_tpu.serving.llm.model_mla import PagedGlmMoeLiteModel
    assert issubclass(PagedGlmMoeLiteModel, PagedDecoderModel)
    for fn in ("_decode_fn", "_prefill_chunk_fn", "_prefill_fn",
               "_verify_fn", "decode_step", "read_tokens",
               "prefill_chunk"):
        # the skeleton's, not a copy of them
        assert getattr(PagedGlmMoeLiteModel, fn) \
            is getattr(PagedDecoderModel, fn), fn
    model = PagedGlmMoeLiteModel(
        tiny_glm_moe_lite_config(64), num_slots=2, block_size=4,
        num_blocks=16, max_blocks_per_seq=4, prefill_buckets=(8,),
        prefill_chunk=8, kv_dtype="f32", decode_impl="flash", spec_k=0)
    S, W = model.num_slots, model.max_blocks_per_seq
    lanes = (np.zeros(S, np.float32), np.zeros(S, np.int32),
             np.ones(S, np.float32), np.zeros(S, np.uint32))
    decode, chunk = _lowered_steps(model)
    assert "jit(_decode_fn)" in decode
    assert "jit(_prefill_chunk_fn)" in chunk
    assert "zoo_mla_decode" in decode and "zoo_paged_decode" not in decode
    for scope in ("zoo.attn_proj", "zoo.kv_append", "zoo.paged_attend",
                  "zoo.mlp", "zoo.moe_route", "zoo.moe_experts",
                  "zoo.moe_shared", "zoo.lm_head", "zoo.sample"):
        assert scope in decode and scope in chunk, scope
    # the new scopes lie inside zoo.mlp
    assert "zoo.mlp/zoo.moe_experts" in decode
    for name in ("zoo_llm_moe_expert_visits_total",
                 "zoo_llm_moe_rows_total"):
        assert METRICS[name] == ("counter", ())
    visits0 = _counter("zoo_llm_moe_expert_visits_total")
    rows0 = _counter("zoo_llm_moe_rows_total")
    tables = np.zeros((S, W), np.int32)
    tables[0, 0] = 3                       # one live lane, one idle
    args = (np.ones(S, np.int32), np.ones(S, bool), tables,
            np.zeros(S, np.int32), lanes)
    first = model.decode_step(None, *args)
    # the counts travel with the tick's batch: the second tick chains on
    # the first, and the first, never read, leaves nothing behind
    model.read_tokens(model.decode_step(first, *args))
    # one live lane x 2 choices x 2 expert layers, of the tick read
    assert _counter("zoo_llm_moe_rows_total") - rows0 == 4
    assert 2 <= _counter("zoo_llm_moe_expert_visits_total") - visits0 <= 4


def test_the_sparse_and_state_step_keeps_the_names_and_counts_its_pages():
    """The third architecture runs the same ``_decode_fn`` /
    ``_prefill_chunk_fn`` under the scopes the accepted metric files
    match, adds its own (``zoo.sparse_select``, ``zoo.sparse_attend``
    inside ``zoo.paged_attend``, ``zoo.ck_append``, ``zoo.lightning``,
    the kernels ``zoo_sparse_decode`` and ``zoo_lightning_decode``), and
    its counters and gauge are in the catalog and move with a decode
    tick that is read."""
    from zoo_tpu.models.llm.minicpm_sala import tiny_minicpm_sala_config
    from zoo_tpu.obs.catalog import METRICS
    from zoo_tpu.obs.metrics import get_registry
    from zoo_tpu.serving.llm.model import PagedDecoderModel
    from zoo_tpu.serving.llm.model_sala import PagedMiniCpmSalaModel
    for fn in ("_decode_fn", "_prefill_chunk_fn", "_prefill_fn",
               "decode_step", "read_tokens", "copy_block",
               "export_kv_blocks", "import_kv_blocks"):
        # the skeleton's, not a copy of them
        assert getattr(PagedMiniCpmSalaModel, fn) \
            is getattr(PagedDecoderModel, fn), fn
    model = PagedMiniCpmSalaModel(
        tiny_minicpm_sala_config(64), num_slots=2, block_size=8,
        num_blocks=16, max_blocks_per_seq=6, prefill_buckets=(8,),
        prefill_chunk=8, kv_dtype="f32", decode_impl="flash", spec_k=0)
    S, W = model.num_slots, model.max_blocks_per_seq
    lanes = (np.zeros(S, np.float32), np.zeros(S, np.int32),
             np.ones(S, np.float32), np.zeros(S, np.uint32))
    decode, chunk = _lowered_steps(model)
    assert "jit(_decode_fn)" in decode
    assert "jit(_prefill_chunk_fn)" in chunk
    assert "zoo_sparse_decode" in decode and "zoo_lightning_decode" in decode
    assert "zoo_paged_decode" not in decode and "zoo_state_write" in chunk
    for scope in ("zoo.attn_proj", "zoo.kv_append", "zoo.paged_attend",
                  "zoo.sparse_select", "zoo.sparse_attend", "zoo.ck_append",
                  "zoo.lightning", "zoo.mlp", "zoo.lm_head", "zoo.sample"):
        assert scope in decode and scope in chunk, scope
    assert "zoo.paged_attend/zoo.sparse_attend" in decode
    for name in ("zoo_llm_sparse_pages_attended_total",
                 "zoo_llm_sparse_pages_resident_total",
                 "zoo_llm_state_steps_total", "zoo_llm_state_resets_total"):
        assert METRICS[name] == ("counter", ())
    assert METRICS["zoo_llm_state_bytes"] == ("gauge", ())
    gauges = {g["name"]: g["value"]
              for g in get_registry().snapshot()["gauges"]}
    assert gauges["zoo_llm_state_bytes"] == model.state_bytes \
        == 2 * 2 * 4 * 16 * 16 * 4
    before = {n: _counter(n) for n in METRICS if n.startswith(
        ("zoo_llm_sparse_", "zoo_llm_state_")) and n.endswith("_total")}
    tables = np.zeros((S, W), np.int32)
    tables[0, :2] = (3, 4)                 # one live lane, one idle
    args = (np.ones(S, np.int32), np.ones(S, bool), tables,
            np.asarray([9, 0], np.int32),
            lanes)
    first = model.decode_step(None, *args)
    model.read_tokens(model.decode_step(first, *args))
    moved = {n: _counter(n) - v for n, v in before.items()}
    # of the tick read: one live lane at position 9 holds 2 pages, both
    # attended (dense), by 2 K/V heads in 2 sparse layers; 2 states
    assert moved == {"zoo_llm_sparse_pages_attended_total": 8,
                     "zoo_llm_sparse_pages_resident_total": 8,
                     "zoo_llm_state_steps_total": 2,
                     "zoo_llm_state_resets_total": 0}


def test_the_attributes_the_harness_wraps_are_there():
    """``benchmarks/harness/serve_cell.py`` wraps these by name and
    reads ``decode_step``'s positional arguments 3 and 4."""
    from zoo_tpu.serving.llm.model import PagedLlamaModel
    for attr in ("_admit", "_build_tick", "_prefill_tick", "submit"):
        assert callable(getattr(LLMEngine, attr)), attr
    for attr in ("decode_step", "read_tokens", "prefill_chunk"):
        assert callable(getattr(PagedLlamaModel, attr)), attr
    params = list(inspect.signature(
        PagedLlamaModel.decode_step).parameters)
    assert params == ["self", "prev_batch", "host_tokens", "use_host",
                      "block_tables", "positions", "sampling_lanes"]
    assert "trace_id" in inspect.signature(LLMEngine.submit).parameters


# ------------------------------------------------------ the ring's operator

def test_the_postmortem_bundle_holds_the_last_spans():
    from zoo_tpu.obs.flight import RECENT_SPANS, flight_recorder
    with tracing.span("t.bundle.last", step=7):
        pass
    bundle = flight_recorder().snapshot_bundle("test")
    recent = bundle["recent_spans"]
    assert 0 < len(recent) <= RECENT_SPANS
    last = recent[-1]
    assert last["name"] == "t.bundle.last" and last["attrs"] == {"step": 7}
    assert last["ago_s"] >= last["dur_s"] >= 0
    assert "active_spans" in bundle


def test_the_timeline_says_whether_a_first_token_queued_or_prefilled(
        tmp_path):
    from zoo_tpu.obs.timeline import (
        merge_timeline,
        render_text,
        ttft_breakdown,
    )
    tracing.trace_to(str(tmp_path))
    model = SyntheticLLMModel(num_slots=1, block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, prefill_chunk=4)
    eng = LLMEngine(model).start()
    h = eng.submit(np.arange(1, 10), 4, trace_id="trace-ttft")
    deadline = time.monotonic() + 10
    while not h.done and time.monotonic() < deadline:
        time.sleep(0.01)
    eng.stop()
    tracing.stop_tracing()
    timeline = merge_timeline(str(tmp_path), "trace-ttft")
    names = [e["name"] for e in timeline]
    assert "llm.queue_wait" in names and "llm.prefill_total" in names
    queue, prefill, chunks = ttft_breakdown(timeline)
    assert chunks == 3
    assert queue + prefill == pytest.approx(h.ttft(), abs=1e-3)
    assert "engine ttft" in render_text(timeline).splitlines()[-1]
