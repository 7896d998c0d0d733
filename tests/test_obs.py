"""The obs subsystem: registry semantics, Prometheus rendering + HTTP
exporter, trace spans, JSONL snapshots, merge math, the smoke script,
and the cross-layer end-to-end scrape (serving + fit + checkpoint +
retry/breaker all landing on one /metrics page)."""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import zoo_tpu.obs as obs
from llm_tick import tick
from zoo_tpu.obs import (
    MetricsExporter,
    MetricsRegistry,
    StatTimer,
    merge_snapshots,
    read_trace,
    span,
    validate_prometheus_text,
    write_snapshot,
)

pytestmark = pytest.mark.obs


# ------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("t_requests_total", "requests", labels=("outcome",))
    c.labels(outcome="ok").inc()
    c.labels(outcome="ok").inc(2)
    c.labels(outcome="err").inc()
    assert c.labels(outcome="ok").value == 3
    assert c.labels(outcome="err").value == 1
    with pytest.raises(ValueError):
        c.labels(outcome="ok").inc(-1)  # counters only go up

    g = r.gauge("t_depth", "depth")
    g.set(5)
    g.inc()
    g.dec(3)
    assert g.value == 3

    h = r.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 50.0):
        h.observe(v)
    snap = h.snapshot_value()
    assert snap["counts"] == [1, 1, 1]  # <=0.1, <=1.0, +Inf
    assert snap["count"] == 3
    assert abs(snap["sum"] - 50.55) < 1e-9


def test_get_or_create_and_type_mismatch():
    r = MetricsRegistry()
    a = r.counter("t_shared_total", "x")
    b = r.counter("t_shared_total", "x")
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("t_shared_total", "now a gauge?")
    with pytest.raises(ValueError):
        r.counter("t_shared_total", "x", labels=("k",))  # label mismatch
    with pytest.raises(ValueError):
        r.counter("bad name!", "x")
    with pytest.raises(ValueError):
        c = r.counter("t_lbl_total", "x", labels=("k",))
        c.labels(wrong="v")


def test_render_prometheus_is_valid_and_escaped():
    r = MetricsRegistry()
    r.counter("t_esc_total", 'has "quotes" and \\slashes\\',
              labels=("k",)).labels(k='va"l\\ue\n2').inc()
    r.histogram("t_h_seconds", "h", labels=("stage",),
                buckets=(0.001, 0.1)).labels(stage="s").observe(0.05)
    text = r.render_prometheus()
    assert validate_prometheus_text(text) == []
    assert '\\"quotes\\"' not in text  # help escapes \ and newline only
    assert 'k="va\\"l\\\\ue\\n2"' in text


def test_validator_catches_garbage():
    assert validate_prometheus_text("not a metric line at all{\n") != []
    # histogram with a non-cumulative bucket series
    bad = ("# HELP h x\n# TYPE h histogram\n"
           'h_bucket{le="0.1"} 5\nh_bucket{le="1"} 3\n'
           'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n')
    assert any("cumulative" in e for e in validate_prometheus_text(bad))
    # sample without a TYPE line
    assert any("no # TYPE" in e
               for e in validate_prometheus_text("orphan_total 1\n"))


def test_stat_timer_unifies_stage_and_phase_timers():
    from zoo_tpu.common.profiling import PhaseTimer
    from zoo_tpu.serving.server import StageTimer

    assert PhaseTimer is StatTimer and StageTimer is StatTimer
    t = StatTimer()
    for dt in (0.01, 0.03):
        t.record(dt)
    s = t.stats()
    assert s["count"] == 2
    assert abs(s["avg_ms"] - 20.0) < 1e-6
    assert abs(s["max_ms"] - 30.0) < 1e-6
    assert abs(s["min_ms"] - 10.0) < 1e-6

    # histogram mirroring: the registry sees every record
    r = MetricsRegistry()
    h = r.histogram("t_stage_seconds", "x", buckets=(0.02,))
    t2 = StatTimer(histogram=h)
    t2.record(0.01)
    t2.record(0.5)
    assert h.snapshot_value()["counts"] == [1, 1]


def test_disabled_registry_under_1us():
    """Acceptance bound: a disabled registry's record hot path costs
    < 1 µs (it is one attribute check + early return). Measured on the
    CHILD metric — labels() documents "cache the returned child on hot
    paths", so the family proxy's __getattr__ dispatch is deliberately
    outside the bound."""
    r = MetricsRegistry()
    fam = r.counter("t_hot_total", "x")
    c, c_inc = fam.labels(), fam.labels().inc
    h_obs = r.histogram("t_hot_seconds", "x").labels().observe
    r.disable()
    n = 100_000
    best = float("inf")
    for _ in range(3):  # best-of-3 shields against CI scheduler noise
        t0 = time.perf_counter()
        for _ in range(n):
            c_inc()
        best = min(best, time.perf_counter() - t0)
    assert c.value == 0  # nothing recorded
    assert best / n < 1e-6, f"disabled inc cost {best / n * 1e9:.0f} ns"
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            h_obs(0.5)
        best = min(best, time.perf_counter() - t0)
    assert best / n < 1e-6, f"disabled observe cost {best / n * 1e9:.0f} ns"
    r.enable()
    c_inc()
    assert c.value == 1


# ---------------------------------------------------------------- spans

def test_spans_nest_and_record_errors(tmp_path):
    d = str(tmp_path / "trace")
    obs.trace_to(d)
    try:
        with span("outer", step=3):
            with span("inner"):
                pass
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
    finally:
        obs.stop_tracing()
    evs = read_trace(d)
    by = {}
    for e in evs:
        by.setdefault((e["name"], e["ev"]), e)
    assert by[("outer", "B")]["attrs"] == {"step": 3}
    assert by[("inner", "B")]["parent"] == by[("outer", "B")]["span"]
    assert by[("outer", "B")]["parent"] is None
    assert by[("outer", "E")]["ok"] is True
    assert by[("outer", "E")]["dur_s"] >= 0
    assert by[("boom", "E")]["ok"] is False
    # all events share one process trace id
    assert len({e["trace"] for e in evs}) == 1


def test_span_disabled_is_cheap_noop(tmp_path):
    obs.stop_tracing()
    with span("nothing") as sid:
        assert sid is None
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("hot"):
            pass
    # generous bound: a no-op contextmanager round trip, not a write
    assert (time.perf_counter() - t0) / n < 20e-6


# ------------------------------------------------------------ exporters

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def test_exporter_metrics_healthz_cluster(tmp_path, monkeypatch):
    r = MetricsRegistry()
    r.counter("t_exp_total", "x").inc(7)
    ex = MetricsExporter(registry=r).start()
    try:
        code, text = _get(ex.url + "/metrics")
        assert code == 200
        assert "t_exp_total 7" in text
        assert validate_prometheus_text(text) == []

        # no heartbeat configured: answering at all is healthy
        monkeypatch.delenv("ZOO_HEARTBEAT_FILE", raising=False)
        code, body = _get(ex.url + "/healthz")
        assert code == 200 and json.loads(body)["ok"] is True

        # fresh heartbeat: healthy, with an age
        hb = str(tmp_path / "hb")
        from zoo_tpu.util.resilience import touch_heartbeat
        touch_heartbeat(hb)
        monkeypatch.setenv("ZOO_HEARTBEAT_FILE", hb)
        code, body = _get(ex.url + "/healthz")
        assert code == 200
        assert json.loads(body)["heartbeat_age"] < 5

        # stale heartbeat: 503, same staleness rule ProcessMonitor uses
        with open(hb, "w") as f:
            f.write(repr(time.monotonic() - 3600))
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ex.url + "/healthz")
        assert ei.value.code == 503

        # no aggregation ran yet: /cluster is explicit about it
        monkeypatch.setattr("zoo_tpu.obs.aggregate._last_view", None)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ex.url + "/cluster")
        assert ei.value.code == 404
        # an aggregate_cluster() run is picked up with no extra wiring
        obs.aggregate_cluster(registry=r)
        code, body = _get(ex.url + "/cluster")
        assert code == 200
        assert json.loads(body)["counters"][0]["name"] == "t_exp_total"
        # an explicitly set view wins over the ambient one
        ex.set_cluster_view({"processes": 9, "counters": []})
        code, body = _get(ex.url + "/cluster")
        assert code == 200 and json.loads(body)["processes"] == 9
    finally:
        ex.stop()


def test_jsonl_snapshot_writer(tmp_path):
    r = MetricsRegistry()
    r.counter("t_snap_total", "x").inc(4)
    path = str(tmp_path / "metrics.jsonl")
    write_snapshot(path, r)
    r.counter("t_snap_total", "x").inc()
    write_snapshot(path, r, extra={"round": 2})
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["pid"] == os.getpid()
    assert lines[1]["extra"] == {"round": 2}
    vals = [e["value"] for rec in lines
            for e in rec["metrics"]["counters"]
            if e["name"] == "t_snap_total"]
    assert vals == [4, 5]


def test_check_metrics_export_script_runs():
    """The CI smoke script: exporter up, curl, validate — as a real
    subprocess, the same invocation an operator would use."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join("scripts", "check_metrics_export.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "valid Prometheus text" in proc.stdout


# ---------------------------------------------------------- aggregation

def test_merge_snapshots_semantics():
    def snap(c, g, counts):
        return {"counters": [{"name": "t_c_total", "labels": {}, "value": c}],
                "gauges": [{"name": "t_g", "labels": {}, "value": g}],
                "histograms": [{"name": "t_h_seconds", "labels": {},
                                "bounds": [0.1, 1.0],
                                "counts": counts,
                                "sum": sum(counts), "count": sum(counts)}]}

    m = merge_snapshots([snap(3, 10, [1, 0, 2]), snap(5, -2, [0, 4, 1])])
    assert m["processes"] == 2
    assert m["counters"] == [{"name": "t_c_total", "labels": {},
                              "value": 8.0}]
    assert m["gauges"] == [{"name": "t_g", "labels": {},
                            "max": 10.0, "min": -2.0}]
    h = m["histograms"][0]
    assert h["counts"] == [1, 4, 3]
    assert h["count"] == 8

    # label sets are distinct series
    a = {"counters": [{"name": "t", "labels": {"k": "1"}, "value": 1}],
         "gauges": [], "histograms": []}
    b = {"counters": [{"name": "t", "labels": {"k": "2"}, "value": 1}],
         "gauges": [], "histograms": []}
    assert len(merge_snapshots([a, b])["counters"]) == 2


def test_aggregate_cluster_single_process():
    r = MetricsRegistry()
    r.counter("t_agg_total", "x").inc(6)
    merged = obs.aggregate_cluster(registry=r)
    assert merged["processes"] == 1
    assert merged["counters"] == [{"name": "t_agg_total", "labels": {},
                                   "value": 6.0}]
    assert obs.last_cluster_view() is merged


# ----------------------------------------------------------- end-to-end

def test_metrics_end_to_end_serving_fit_checkpoint(orca_ctx, tmp_path):
    """The acceptance scrape: a model served through ServingServer, a
    short profiled Estimator.fit, a checkpoint save, a forced retry and
    a tripped breaker — then ONE GET /metrics shows serving batch/latency
    histograms, retry/breaker counters, checkpoint save durations and
    per-phase step-time stats, in valid Prometheus text."""
    from zoo_tpu.orca.learn.ckpt import CheckpointManager
    from zoo_tpu.orca.learn.keras import Estimator
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import Dense
    from zoo_tpu.pipeline.inference import InferenceModel
    from zoo_tpu.serving import ServingServer, TCPInputQueue
    from zoo_tpu.util.resilience import (
        CircuitBreaker,
        RetryError,
        RetryPolicy,
    )

    # 1. short profiled fit through the Estimator
    rs = np.random.RandomState(0)
    x = rs.randn(64, 4).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(1, activation="sigmoid"))
    m.compile(optimizer="adam", loss="binary_crossentropy")
    est = Estimator.from_keras(m)
    est.set_profile()
    est.fit({"x": x, "y": y}, epochs=1, batch_size=16)

    # 2. serve it over the TCP door
    inf = InferenceModel().load_keras(m, batch_size=8)
    server = ServingServer(inf, port=0, batch_size=8,
                           max_wait_ms=5).start()
    try:
        q = TCPInputQueue(host=server.host, port=server.port)
        preds = q.predict(x[:12])
        assert np.asarray(preds).shape == (12, 1)
        q.close()
    finally:
        server.stop()

    # 3. checkpoint save + restore
    cm = CheckpointManager(str(tmp_path / "ckpt"))
    cm.save(1, {"w": np.arange(4.0)})
    cm.restore()

    # 4. a retry give-up and a breaker trip
    pol = RetryPolicy(max_attempts=2, sleep=lambda s: None)

    def dead():
        raise ConnectionError("down")

    with pytest.raises(RetryError):
        pol.call(dead)
    br = CircuitBreaker(failure_threshold=1, recovery_timeout=60)
    br.record_failure()

    # 4b. the serving-HA paths (docs/serving_ha.md) — shed at an
    # open-breaker door, a dead-on-arrival deadline, a failover past a
    # dead endpoint, and a hedge that wins over a stalled primary — so
    # the scrape below carries every zoo_serve_* family with real counts
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.tcp_client import _Connection

    class _Stall:
        def __init__(self, factor, delay):
            self.factor, self.delay = factor, delay

        def predict(self, xx, batch_size=None):
            import time as _t
            if self.delay:
                _t.sleep(self.delay)
            return np.asarray(xx) * self.factor

    tripped = CircuitBreaker(failure_threshold=1, recovery_timeout=60)
    tripped.record_failure()
    shed_srv = ServingServer(_Stall(2.0, 0.0), port=0, batch_size=2,
                             max_wait_ms=1.0, breaker=tripped).start()
    slow_srv = ServingServer(_Stall(3.0, 0.5), port=0, batch_size=1,
                             max_wait_ms=0.0).start()
    fast_srv = ServingServer(_Stall(2.0, 0.0), port=0, batch_size=2,
                             max_wait_ms=1.0, version="v9").start()
    try:
        conn = _Connection(shed_srv.host, shed_srv.port)
        resp = conn.rpc({"op": "predict", "uri": "u",
                         "data": np.zeros((1, 2), np.float32)})
        assert resp.get("shed") and resp.get("retryable")
        conn.close()
        conn = _Connection(fast_srv.host, fast_srv.port)
        resp = conn.rpc({"op": "predict", "uri": "u",
                         "data": np.zeros((1, 2), np.float32),
                         "deadline_ms": 0.0})
        assert resp.get("expired")
        conn.close()
        import socket as _socket
        probe = _socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead = probe.getsockname()
        probe.close()
        cli = HAServingClient([dead, (fast_srv.host, fast_srv.port)],
                              hedge=False, deadline_ms=8000)
        assert np.asarray(cli.predict(
            np.ones((1, 2), np.float32))).shape == (1, 2)
        cli.close()
        cli2 = HAServingClient(
            [(slow_srv.host, slow_srv.port),
             (fast_srv.host, fast_srv.port)],
            hedge=True, hedge_delay_ms=20, deadline_ms=8000)
        hedged = np.asarray(cli2.predict(np.ones((1, 2), np.float32)))
        np.testing.assert_allclose(hedged, 2.0)  # the fast replica won
        cli2.close()
        # the model-lifecycle families (docs/model_lifecycle.md): a
        # version-pinned mismatch bounce and a pinned A/B request
        conn = _Connection(fast_srv.host, fast_srv.port)
        resp = conn.rpc({"op": "predict", "uri": "u",
                         "data": np.zeros((1, 2), np.float32),
                         "model_version": "v8"})
        assert resp.get("version_mismatch") and resp["version"] == "v9"
        conn.close()
        cli3 = HAServingClient([(fast_srv.host, fast_srv.port)],
                               hedge=False, deadline_ms=8000)
        np.testing.assert_allclose(
            np.asarray(cli3.predict(np.ones((1, 2), np.float32),
                                    model_version="v9")), 2.0)
        cli3.close()
    finally:
        shed_srv.stop()
        slow_srv.stop()
        fast_srv.stop()

    # 4c. the overlapped tick pipeline's phase histograms + overlap
    # gauge (docs/llm_serving.md): one short jax-free engine run over a
    # deterministic fake model populates zoo_llm_tick_seconds{phase}
    # and zoo_llm_tick_overlap_ratio. Runs BEFORE the allocator probe
    # below — the engine's own allocator republishes the process-global
    # zoo_llm_kv_blocks_* gauges on every mutation, and the scrape
    # asserts the probe's values.
    from zoo_tpu.serving.llm.engine import LLMEngine

    class _TickModel:
        num_slots, block_size, num_blocks = 2, 4, 16
        max_blocks_per_seq, max_prompt_len = 4, 12
        max_context, prefill_chunk_size, eos_id = 16, 0, None
        suffix_chunk_size = 4
        kv_bytes_per_token = 160          # -> zoo_llm_kv_bytes_per_token
        weight_bytes = 4096               # -> zoo_llm_weight_bytes
        spec_k = 2                        # -> the verify path + the
        #                                   zoo_llm_spec_* families

        def prefill(self, prompt, row, sampling=None):
            return (int(prompt[-1]) + 1) % 4

        def prefill_chunk(self, chunk, start, total, row,
                          sampling=None):
            return (int(chunk[-1]) + 1) % 4

        def decode_step(self, prev, host, use, tables, pos, lanes):
            import time as _t
            _t.sleep(0.001)
            return (np.where(np.asarray(use), host,
                             prev if prev is not None else 0) + 1) % 4

        def verify_step(self, tokens, tables, pos, lanes):
            import time as _t
            _t.sleep(0.001)
            return (np.asarray(tokens) + 1) % 4

        def read_tokens(self, batch):
            return np.asarray(batch)

    # prefix caching ON + speculative decoding ON: the second identical
    # prompt hits the first's registered blocks (populating
    # zoo_llm_prefix_cache_{hit,miss}_* and the shared/cached gauges),
    # and the cyclic prompt makes the prompt-lookup drafter propose
    # tokens the (x+1)%4 fake accepts — all jax-free
    llm_eng = LLMEngine(_TickModel(), prefix_cache=True).start()
    try:
        for rid in ("scrape-a", "scrape-b"):
            h = llm_eng.submit([1, 2, 3, 1, 2, 3], 6, rid=rid)
            deadline = time.monotonic() + 30
            while not h.done and time.monotonic() < deadline:
                time.sleep(0.01)
            assert h.done
        llm_stats = llm_eng.stats()
        assert llm_stats["prefix_hit_tokens"] > 0
        assert llm_stats["spec_proposed_tokens"] > 0
        assert llm_stats["spec_accepted_tokens"] > 0
    finally:
        llm_eng.stop()

    # 4c-ter. multi-tenant QoS (docs/multitenancy.md): a tenancy-armed
    # engine drives the zoo_tenant_* families — an admitted stream and
    # a rate shed off the free tier's dry bucket, a class-0 preemption
    # of the youngest best-effort stream, and the per-tenant slot/KV
    # gauges the scheduler loop republishes
    from zoo_tpu.serving.llm.engine import AdmissionError
    from zoo_tpu.serving.tenancy import TenantRegistry

    # ticked WHITE-BOX (never .start()ed) so the preemption is
    # deterministic: a live engine loop finishes the best-effort
    # streams before the paid submit could ever contend for a slot
    qos_eng = LLMEngine(
        _TickModel(), prefix_cache=False,
        tenancy=TenantRegistry(
            spec="gold:class=0,rate=0;brz:class=1,rate=0;"
                 "free:class=1,rate=0.001,burst=1",
            qos=True))

    def _qtick(handles=(), ticks=1):
        for _ in range(ticks):
            if handles and all(h.done for h in handles):
                return
            tick(qos_eng)

    f1 = qos_eng.submit([1, 2, 3], 4, rid="ten-f1", tenant="free")
    with pytest.raises(AdmissionError):   # burst of 1 is spent
        qos_eng.submit([1, 2, 3], 4, rid="ten-f2", tenant="free")
    _qtick([f1], ticks=50)
    assert f1.done and f1.outcome == "ok"
    b1 = qos_eng.submit([1, 2, 3], 6, rid="ten-b1", tenant="brz")
    b2 = qos_eng.submit([2, 3, 1], 6, rid="ten-b2", tenant="brz")
    _qtick(ticks=2)                       # both brz slots live
    assert qos_eng.stats()["active"] == 2
    g1 = qos_eng.submit([3, 1, 2], 4, rid="ten-g1", tenant="gold")
    _qtick(ticks=2)                       # evict youngest brz, admit
    _qtick([b1, b2, g1], ticks=100)       # resume + drain everything
    assert all(h.done and h.outcome == "ok" for h in (b1, b2, g1))

    # 4c-bis. disaggregated serving (docs/disaggregated_serving.md):
    # one long prompt through a prefill+decode pair drives the whole
    # two-leg kv_migrate handoff — the prefill seat's push populates
    # zoo_llm_kv_migrated_bytes_total + zoo_llm_handoff_seconds, the
    # decode seat's adoption populates zoo_llm_kv_migrated_blocks_total,
    # and the client's routing plan stamps zoo_serve_route_affinity_total.
    # Runs BEFORE the 4d allocator probe for the same reason 4c does:
    # these engines' allocators republish the process-global
    # zoo_llm_kv_blocks_* gauges on every mutation.
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.llm.synthetic import SyntheticLLMModel, reference
    from zoo_tpu.serving.server import ServingServer

    mk = dict(num_slots=2, block_size=4, num_blocks=32,
              max_blocks_per_seq=8, max_prompt_len=48)
    pre_eng = LLMEngine(SyntheticLLMModel(**mk), role="prefill").start()
    dec_eng = LLMEngine(SyntheticLLMModel(**mk), role="decode").start()
    pre_srv = ServingServer(None, llm_engine=pre_eng, port=0,
                            batch_size=2, max_wait_ms=1.0).start()
    dec_srv = ServingServer(None, llm_engine=dec_eng, port=0,
                            batch_size=2, max_wait_ms=1.0).start()
    disagg_cli = HAServingClient(
        [(pre_srv.host, pre_srv.port), (dec_srv.host, dec_srv.port)],
        hedge=False, migrate_min_tokens=16)
    try:
        disagg_cli.update_topology()
        long_prompt = [(3 * i + 1) % 50 for i in range(18)]
        assert list(disagg_cli.generate(long_prompt, 6)) == \
            reference(long_prompt, 6)
        assert dec_eng.stats()["handoffs_in"] == 1
    finally:
        disagg_cli.close()
        pre_srv.stop()
        dec_srv.stop()
        pre_eng.stop()
        dec_eng.stop()

    # 4d. the paged-KV gauges: a jax-free allocator round-trip leaves
    # zoo_llm_kv_blocks_{used,free} at the pool's live accounting
    from zoo_tpu.serving.llm.kv_cache import (BlockAllocator,
                                              prefix_block_hashes)
    # first, a last-resort cross-tenant eviction: a 3-usable-block
    # pool where gold's ask can only be covered by reclaiming victim's
    # parked cache block (own + shared partitions both empty) bumps
    # zoo_tenant_kv_cross_evictions_total{tenant="gold"}
    t_alloc = BlockAllocator(num_blocks=4, block_size=4,
                             prefix_cache=True)
    t_alloc.set_tenant("t-v", "victim")
    t_alloc.allocate("t-v", 1)
    t_alloc.register_blocks(
        "t-v", prefix_block_hashes([1, 2, 3, 4], 4,
                                   salt=b"tenant:victim"))
    t_alloc.free("t-v")
    t_alloc.set_tenant("t-g", "gold")
    assert t_alloc.allocate("t-g", 3) is not None
    # ... then the plain probe LAST — the used/free gauges are
    # process-global, so the final _publish() is the scraped value
    alloc = BlockAllocator(num_blocks=17, block_size=8)
    alloc.allocate("scrape-seq", 4)

    # 4e. the SLO watchdog (docs/observability.md): two evaluation
    # passes over the process-global registry publish the zoo_slo_*
    # burn-rate/breach gauges the fleet alerts on
    from zoo_tpu.obs.metrics import counter as _counter
    from zoo_tpu.obs.slo import SLORule, SLOWatchdog, _error_rate
    watchdog = SLOWatchdog(
        rules=[SLORule("error_rate", _error_rate, 0.99)],
        window_s=60.0, interval_s=60.0)
    watchdog.tenant_shed_objective = 0.5   # arm the per-tenant burn
    watchdog.evaluate()
    # traffic must flow INSIDE the window for a burn-rate verdict
    _counter("zoo_serving_requests_total", labels=("outcome",)) \
        .labels(outcome="ok").inc()
    _counter("zoo_tenant_admitted_total", labels=("tenant",)) \
        .labels(tenant="gold").inc()
    _counter("zoo_tenant_shed_total", labels=("tenant", "reason")) \
        .labels(tenant="gold", reason="rate").inc()
    watchdog.evaluate()

    # 5. one scrape sees all of it
    ex = MetricsExporter().start()  # process-global registry
    try:
        code, text = _get(ex.url + "/metrics")
    finally:
        ex.stop()
    assert code == 200
    assert validate_prometheus_text(text) == []
    for needle in (
            'zoo_serving_stage_seconds_bucket{stage="inference"',
            "zoo_serving_batch_occupancy_bucket",
            'zoo_serving_requests_total{outcome="ok"}',
            "zoo_retry_attempts_total",
            "zoo_retry_giveups_total",
            'zoo_breaker_transitions_total{state="open"}',
            "zoo_ckpt_save_seconds_bucket",
            "zoo_ckpt_restore_seconds_count",
            'zoo_step_phase_seconds_bucket{phase="step"',
            'zoo_serve_shed_total{reason="breaker_open"}',
            'zoo_serve_deadline_expired_total{stage="admission"}',
            "zoo_serve_failover_total",
            'zoo_serve_hedge_total{event="fired"}',
            'zoo_serve_hedge_total{event="won"}',
            'zoo_serve_shed_total{reason="version_mismatch"}',
            'zoo_registry_version_info{version="v9"} 1',
            'zoo_serve_ab_requests_total{version="v9",outcome="ok"}',
            "zoo_llm_kv_blocks_used 4",
            "zoo_llm_kv_blocks_free 12",
            # the tick pipeline (PR 10): per-phase engine tick
            # histograms + the device-busy/wall overlap gauge
            'zoo_llm_tick_seconds_bucket{phase="schedule"',
            'zoo_llm_tick_seconds_bucket{phase="decode"',
            'zoo_llm_tick_seconds_bucket{phase="readback"',
            "zoo_llm_tick_overlap_ratio",
            # prefix caching + quantized KV (this PR): token hit/miss
            # counters, the shared-blocks gauge, and the per-token HBM
            # byte cost under the active cache dtype
            "zoo_llm_prefix_cache_hit_tokens_total",
            "zoo_llm_prefix_cache_miss_tokens_total",
            "zoo_llm_kv_blocks_shared",
            "zoo_llm_kv_bytes_per_token 160",
            # the resident weight tree's bytes (PR 26: bf16 dot
            # weights on a TPU halve it)
            "zoo_llm_weight_bytes 4096",
            # speculative decoding (this PR): proposed/accepted draft
            # tokens, the per-pass accept-length histogram, and the
            # drafter hit-rate gauge — republished from engine.stats()
            "zoo_llm_spec_proposed_tokens_total",
            "zoo_llm_spec_accepted_tokens_total",
            "zoo_llm_spec_accept_len_bucket",
            "zoo_llm_spec_draft_hit_rate",
            # per-stream token cadence (PR 13): the request-level
            # latency families the SLO watchdog burns against — the
            # engine runs above pushed multi-token streams, so both
            # carry real observations
            "zoo_llm_inter_token_seconds_bucket",
            'zoo_llm_stream_ttft_seconds_bucket{outcome="ok"',
            # disaggregated serving (this PR): the kv_migrate handoff
            # volume counters, the push-to-adopt latency histogram,
            # and the client's routing-decision tally — populated by
            # the 4c-bis two-leg handoff above
            "zoo_llm_kv_migrated_blocks_total",
            "zoo_llm_kv_migrated_bytes_total",
            "zoo_llm_handoff_seconds_bucket",
            'zoo_serve_route_affinity_total{reason="handoff"}',
            # the SLO watchdog's published verdict (4e above) and the
            # flight recorder's event tally
            'zoo_slo_burn_rate{slo="error_rate"}',
            'zoo_slo_breach{slo="error_rate"}',
            "zoo_flight_events_total",
            # the GSPMD layer (docs/multichip.md): the fixture's 8-device
            # mesh publishes its axis sizes, and the fit above ran DP
            # over it, so the plan's estimated grad all-reduce bytes
            # accumulated per executed step
            'zoo_mesh_axis_size{axis="data"}',
            'zoo_mesh_collective_bytes_total{op="all_reduce"}',
            # multi-tenant QoS (this PR): the 4c-ter engine's admit /
            # rate-shed / class-preempt tallies, the per-tenant
            # slot/KV gauges its scheduler loop republishes, the 4d
            # cross-partition eviction counter, and the 4e watchdog's
            # per-tenant shed burn verdict (family-prefix needles for
            # the multi-label families)
            'zoo_tenant_admitted_total{tenant="free"}',
            'zoo_tenant_shed_total{',
            'zoo_tenant_preempted_total{',
            'zoo_tenant_decode_slots{tenant="brz"}',
            'zoo_tenant_kv_blocks{tenant="gold"}',
            'zoo_tenant_kv_cross_evictions_total{tenant="gold"} 1',
            'zoo_tenant_burn_rate{',
    ):
        assert needle in text, f"/metrics is missing {needle}"
    # the fit really recorded step phases (count > 0, not just a family)
    for line in text.splitlines():
        if line.startswith('zoo_step_phase_seconds_count{phase="step"'):
            assert float(line.rsplit(" ", 1)[1]) > 0
            break
    else:
        raise AssertionError("no step-phase count sample")
    # the mesh gauges/counters carry real values, not just families
    for line in text.splitlines():
        if line.startswith('zoo_mesh_axis_size{axis="data"}'):
            assert float(line.rsplit(" ", 1)[1]) == 8.0
        if line.startswith('zoo_mesh_collective_bytes_total'
                           '{op="all_reduce"}'):
            assert float(line.rsplit(" ", 1)[1]) > 0
