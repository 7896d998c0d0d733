"""The readers of the program's own span ring (``program_spans.py``) on
a recorded ring and the small recorded trace of ``test_trace.py``, whose
answers can be worked out by hand."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import program_spans as ps  # noqa: E402
from harness import readers, trace as tr  # noqa: E402
from harness.spans import Recorder  # noqa: E402
from zoo_tpu.obs import tracing  # noqa: E402

# the ring as the program leaves it: (name, t0, dur_s, thread, attrs),
# ordered by END time; the window is [100, 110) on perf_counter
RING = [
    ("jit.compile", 20.0, 12.0, 1, {"fun": "jit(_decode_fn)"}),
    ("jit.compile", 40.0, 3.0, 1, {"fun": "jit(_prefill_chunk_fn)"}),
    ("jit.lower", 39.0, 1.0, 1, {"fun": "jit(_prefill_chunk_fn)"}),
    ("llm.queue_wait", 100.5, 0.002, 2, {"rid": "a"}),
    ("llm.tick.inflight_wait", 101.0, 2.0, 2, None),
    ("llm.tick.lock_wait", 103.0, 0.001, 2, None),
    ("llm.tick.lock_wait", 104.0, 0.003, 2, None),
    ("llm.tick.inflight_wait", 104.5, 3.0, 2, None),
    ("llm.queue_wait", 108.0, 0.010, 2, {"rid": "b"}),
    ("llm.tick.lock_wait", 110.5, 0.5, 2, None),     # after the window
]


def _ctx(**kw):
    base = dict(rec=Recorder(), t0=100.0, t1=110.0, cfg={}, traffic={},
                chips=1, peaks={"hbm_bytes_per_s": 1e6}, facts={},
                counters=None)
    base.update(kw)
    return readers.Context(**base)


@pytest.fixture
def ring(monkeypatch):
    """Puts a recorded ring in the program's place; ``held(...)``
    changes what it holds and how many spans it has ever written."""
    state = {"spans": list(RING), "written": len(RING)}

    def held(spans=None, written=None):
        if spans is not None:
            state["spans"] = list(spans)
        state["written"] = len(state["spans"]) if written is None \
            else written

    monkeypatch.setattr(tracing, "recent_spans",
                        lambda *a, **k: list(state["spans"]))
    monkeypatch.setattr(tracing, "ring_state", lambda: {
        "capacity": tracing.RING_CAPACITY, "written": state["written"],
        "oldest_t0": state["spans"][0][1] if state["spans"] else None})
    return held


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return tr.Trace.from_json(json.load(f))


def test_percentile_share_count_and_set_up_sum(ring):
    ctx = _ctx()
    assert ps.ring_percentile(ctx, {"span": "llm.tick.lock_wait", "q": 99,
                                    "scale": 1000}) == pytest.approx(3.0)
    # the harness's own nearest-rank percentile: of two, the upper
    assert ps.ring_percentile(ctx, {"span": "llm.queue_wait", "q": 50,
                                    "scale": 1000}) == pytest.approx(10.0)
    assert ps.ring_share(ctx, {"span": "llm.tick.inflight_wait"}) \
        == pytest.approx(50.0)
    assert ps.ring_count(ctx, {"span": "llm.tick.lock_wait"}) == 2.0
    # no compile began in the window, and the ring shows it would know
    assert ps.ring_count(ctx, {"span": "jit.compile"}) == 0.0
    assert ps.ring_sum_before(ctx, {"span": "jit.compile"}) \
        == pytest.approx(15.0)


def test_nothing_to_read_is_none_and_never_zero(ring):
    ctx = _ctx()
    for reader in (ps.ring_percentile, ps.ring_share, ps.ring_count,
                   ps.ring_sum_before):
        assert reader(ctx, {"span": "no.such.span", "q": 50}) is None
    assert ps.ring_sum_before(ctx, {"span": "llm.queue_wait"}) is None
    assert ps.idle_unattributed(ctx, {"spans": "."}) is None   # no trace


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    """The parent of the PR that brought the ring has the module and
    not the functions: every reader leaves its metric out."""
    monkeypatch.delattr(tracing, "recent_spans")
    ctx = _ctx()
    assert ps.ring(None) is None
    assert ps.ring_percentile(ctx, {"span": "llm.queue_wait",
                                    "q": 50}) is None
    assert ps.ring_count(ctx, {"span": "jit.compile"}) is None


def test_a_ring_wrapped_past_the_window_reads_as_nothing(ring):
    ctx = _ctx()
    # wrapped, but the oldest span it holds ended before the window
    # opened: everything that began in the window is still there
    ring(written=tracing.RING_CAPACITY + 5)
    assert ps.ring_count(ctx, {"span": "llm.tick.lock_wait"}) == 2.0
    # set-up is asked for from the start of the process: gone
    assert ps.ring_sum_before(ctx, {"span": "jit.compile"}) is None
    # wrapped into the window: the oldest span held ended inside it
    ring(RING[4:], written=tracing.RING_CAPACITY + 5)
    for reader in (ps.ring_percentile, ps.ring_share, ps.ring_count):
        assert reader(ctx, {"span": "llm.tick.lock_wait", "q": 50}) is None


# ------------------------------------------------- the ring beside a trace

SHIFT = 9.0          # profiler's clock = perf_counter + 9 s


def _rec_of(host, drop_first=0, extra=()):
    """The harness's recorder holding the trace's host spans on
    perf_counter, jittered by a few microseconds."""
    rec = Recorder()
    by_name = {}
    for i, (name, s, d) in enumerate(host):
        by_name.setdefault(name, []).append(
            (s - SHIFT + 2e-6 * (i % 3), d - 1e-6))
    for name, spans in by_name.items():
        for s, d in spans[drop_first:]:
            rec.add_span(name, s, d)
    for name, s, d in extra:
        rec.add_span(name, s, d)
    return rec


def test_offset_from_the_spans_both_sides_hold(small):
    host = small.host + [("engine.build_tick", 10.030, 0.002),
                         ("engine.build_tick", 10.036, 0.0015)]
    rec = _rec_of(host)
    assert ps.clock_offset(rec.spans, host, 0.9, 1.1) \
        == pytest.approx(SHIFT, abs=5e-6)
    # the trace began a little earlier than the recorder's interval and
    # holds one span more at its start: dropped, not paired off by one
    assert ps.clock_offset(_rec_of(host, drop_first=1).spans, host,
                           0.9, 1.1) == pytest.approx(SHIFT, abs=5e-6)
    # spans only the recorder has (added after the window) pair with
    # nothing and change nothing
    rec = _rec_of(host, extra=[("client.ttft", 1.0, 0.3)])
    assert ps.clock_offset(rec.spans, host, 0.9, 1.1) \
        == pytest.approx(SHIFT, abs=5e-6)
    assert ps.clock_offset(Recorder().spans, host, 0.9, 1.1) is None


def test_pairs_that_disagree_give_no_offset(small):
    host = [("engine.build_tick", 10.0 + 0.004 * i, 0.001 + 1e-5 * i)
            for i in range(8)]
    rec = Recorder()
    for i, (name, s, d) in enumerate(host):
        # every other pair is a millisecond off: no one offset fits
        rec.add_span(name, s - SHIFT + (1e-3 if i % 2 else 0.0), d)
    assert ps.clock_offset(rec.spans, host, 0.0, 2.0) is None


def test_idle_goes_to_the_shortest_program_span_over_it(small, ring, capsys):
    ring([("llm.readback.device", 1.000, 0.050, 3, None),
          ("llm.tick.grow_build", 1.0135, 0.004, 2, None),
          ("llm.stream", 0.5, 2.0, 2, {"rid": "a"})])
    ctx = _ctx(rec=_rec_of(small.host), trace=small, traced=(0.9, 1.1))
    share = ps.idle_unattributed(ctx, {"spans": r"^llm\.(tick|readback)\."})
    assert share == pytest.approx(0.0)
    said = json.loads(capsys.readouterr().out.split(
        "idle_by_program_span: ")[1])
    assert said["llm.tick.grow_build"] == pytest.approx(0.006)
    assert said["between_ops_under_20us"] == pytest.approx(5e-6)
    assert "llm.readback.device" not in said and "llm.stream" not in said
    # with the scheduler's spans left out the readback thread's is the
    # shortest over the gap; with nothing over it, it is unattributed
    ps.idle_unattributed(ctx, {"spans": r"^llm\.readback\."})
    assert "llm.readback.device" in capsys.readouterr().out
    ring([("llm.tick.grow_build", 1.030, 0.004, 2, None)])
    assert ps.idle_unattributed(ctx, {"spans": r"^llm\."}) \
        == pytest.approx(100 * 0.006 / 0.04)
    # a ring that no longer reaches back to the traced part says nothing
    ring([("llm.tick.grow_build", 1.0135, 0.004, 2, None)],
         written=tracing.RING_CAPACITY + 1)
    assert ps.idle_unattributed(ctx, {"spans": r"^llm\."}) is None


def test_a_kernel_by_its_name_against_its_byte_floor(small):
    cfg = {"precision": {"kv_cache": "int8"}, "num_hidden_layers": 2,
           "num_key_value_heads": 2, "head_dim": 8}
    p = {"work": "formulas_kernels:paged_decode_bytes",
         "op": r"fusion\.1", "module": "epoch_fn",
         "peak": "hbm_bytes_per_s", "ticks_from_trace": "decode_ticks"}
    ctx = _ctx(cfg=cfg, trace=small, traced=(0.9, 1.1),
               traced_census={"decode_ticks": 4,
                              "attended_positions": 1000})
    # 8 rows x 8 B + 8 scales x 4 B = 96 B a position; the trace holds 2
    # of the host's 4 ticks, so 500 positions; 20 ms of the operation
    assert ps.op_formula_share(ctx, p) == pytest.approx(
        100 * 96 * 500 / 0.020 / 1e6)
    assert ps.op_formula_share(ctx, dict(p, op="zoo_paged_decode")) is None
    assert ps.op_formula_share(_ctx(cfg=cfg), p) is None
