"""Streaming model serving with micro-batching.

Rebuild of Cluster Serving (reference: ``serving/ClusterServing.scala:31-76``
— Flink job: FlinkRedisSource → batch → InferenceModel → FlinkRedisSink,
batching controlled by ``ClusterServingInference``; per-stage ``Timer``
stats ``serving/engine/Timer.scala:22-60``).

The JVM streaming stack collapses to one async Python server pinned to the
TPU: a TCP front door accepts length-prefixed requests in a
NON-EXECUTABLE codec (``serving/codec.py`` — JSON structure + raw array
buffers; never pickle, so a reachable port cannot execute code), a batcher
thread micro-batches up to ``batch_size`` or ``max_wait_ms`` (the
reference's "batch size = core count" guidance maps to a fixed XLA batch,
padded so one executable serves every request), the InferenceModel runs the
batch, and responses are routed back per-request. Per-stage timers are kept
(same avg/max/min stats the reference's Timer collects). The server binds
loopback by default; pass ``host="0.0.0.0"`` only on a trusted network —
there is no authentication on this door (see docs/serving.md).
"""

from __future__ import annotations

import collections
import os
import queue
import socket
import socketserver
import struct
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from zoo_tpu.common.knobs import value as knob_value
from zoo_tpu.obs.flight import flight_recorder, record_event
from zoo_tpu.obs.metrics import StatTimer, counter, gauge, histogram
from zoo_tpu.obs.tracing import emit_event, emit_span, span
from zoo_tpu.util.integrity import (
    corrupt_seam,
    frame_crc,
    verify_crc,
    wire_crc_enabled,
)
from zoo_tpu.serving.tenancy import registry as tenant_registry
from zoo_tpu.util.resilience import (
    CircuitBreaker,
    Deadline,
    FrameCorrupt,
    env_float,
    env_int,
    fault_point,
)

# StageTimer and profiling's PhaseTimer were copy-pasted twins of the
# reference's Timer.scala; both are now obs.StatTimer. The old name stays
# importable (cluster_serving and user code import it from here).
StageTimer = StatTimer

_queue_depth = gauge(
    "zoo_serving_queue_depth", "Predict requests waiting in the batcher "
    "queue of this process")
_batch_occupancy = histogram(
    "zoo_serving_batch_occupancy", "Requests per inference micro-batch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128))
_stage_seconds = histogram(
    "zoo_serving_stage_seconds",
    "Per-stage serving latency (batch assembly / inference / total "
    "round-trip)", labels=("stage",))
_requests = counter(
    "zoo_serving_requests_total", "Predict requests by outcome "
    "(ok / error / shed / expired)", labels=("outcome",))
# serving-HA families (docs/serving_ha.md): the per-cause shed tally the
# admission door keeps, the per-stage deadline-drop tally, and the
# request-id dedup tally that makes retries/hedges idempotent
_shed = counter(
    "zoo_serve_shed_total", "Requests rejected at the admission door, "
    "by cause (queue_full / breaker_open / draining)", labels=("reason",))
_deadline_expired = counter(
    "zoo_serve_deadline_expired_total",
    "Requests dropped because their propagated deadline expired, by the "
    "stage that caught it (admission / batch / reply / http)",
    labels=("stage",))
_dedup = counter(
    "zoo_serve_dedup_total", "Duplicate request ids absorbed without "
    "re-executing (inflight = joined a pending request, replay = served "
    "from the completed-request cache)", labels=("kind",))
# multi-tenant QoS (docs/multitenancy.md): the predict door keeps the
# same per-tenant admission tallies the LLM engine keeps for generate —
# the registry dedupes, so both creation sites share one family
_tenant_admitted = counter(
    "zoo_tenant_admitted_total",
    "Requests admitted past the tenant token bucket, per tenant",
    labels=("tenant",))
_tenant_shed = counter(
    "zoo_tenant_shed_total",
    "Requests shed per tenant and reason (rate = the tenant's own "
    "token bucket ran dry, queue_full = the shared waiting queue was "
    "at bound, slots/kv = per-tenant quota)", labels=("tenant", "reason"))
# model-lifecycle families (docs/model_lifecycle.md): which registry
# version this replica is serving (1 = current, 0 = a version it served
# before a hot-swap), hot-swap outcomes, and the measured drain time the
# rolling updater budgets with ZOO_SERVE_DRAIN_TIMEOUT_S
_version_info = gauge(
    "zoo_registry_version_info",
    "Registry model version served by this replica (1 = current; a "
    "version flips to 0 when a reload swaps it out)", labels=("version",))
_reloads = counter(
    "zoo_serve_reload_total", "Hot-swap model reloads, by outcome "
    "(ok / failed — failed never flips, the old model keeps serving)",
    labels=("outcome",))
_drain_seconds = histogram(
    "zoo_serve_drain_seconds",
    "Graceful-drain wall time (raise the ZOO_SERVE_DRAIN_TIMEOUT_S "
    "budget when this nears it)")
# disaggregated serving (docs/disaggregated_serving.md): what the
# prefill replica pays to push a parked sequence's KV to its decode
# replica, and how many cache bytes crossed the wire doing it
_migrated_bytes = counter(
    "zoo_llm_kv_migrated_bytes_total",
    "KV cache bytes pushed to decode replicas over kv_migrate (int8 "
    "rows + scale planes; 0 for stateless-decodable models)")
_handoff_seconds = histogram(
    "zoo_llm_handoff_seconds",
    "Prefill-side kv_migrate push wall time (export + begin/block/"
    "commit round trip), successful pushes only",
    buckets=(.001, .005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5))


def drain_timeout() -> float:
    """The graceful-drain budget (``ZOO_SERVE_DRAIN_TIMEOUT_S``, default
    30 s) — shared by :meth:`ServingServer.drain` and
    :meth:`zoo_tpu.serving.ha.ReplicaGroup.rolling_update` so a budget
    raised for slow LLM streams protects a rolling swap too."""
    return env_float("ZOO_SERVE_DRAIN_TIMEOUT_S", 30.0)


# Frame layout (docs/serving_ha.md, integrity section): a u32 length
# word, then the ZSRV codec payload. When the length word's HIGH BIT is
# set, a u32 CRC of the payload follows it on the wire (the real length
# is the low 31 bits) — self-describing per frame, so a receiver needs
# no negotiation to VERIFY; negotiation (piggybacked: the client stamps
# ``crc: 1`` into a request, a CRC-capable server answers with a
# CRC-framed reply) only decides whether a sender may USE the bit
# without breaking an old peer.
_FRAME_CRC_BIT = 0x80000000


def _send_msg(sock: socket.socket, obj, crc: bool = False):
    from zoo_tpu.serving.codec import dumps

    payload = dumps(obj)
    if not crc:
        sock.sendall(struct.pack(">I", len(payload)) + payload)
        return
    trailer = frame_crc(payload)
    # chaos seam: bit rot "in transit" — AFTER the CRC was computed, so
    # the receiver's verify catches it exactly like real corruption
    payload = corrupt_seam("serving.wire.corrupt", payload)
    sock.sendall(struct.pack(">I", _FRAME_CRC_BIT | len(payload))
                 + payload + struct.pack(">I", trailer))


def _recv_frame(sock: socket.socket):
    """One frame off the wire → ``(msg | None, frame_had_crc)``.
    A CRC-flagged frame whose trailer does not match its payload raises
    :class:`FrameCorrupt` (counted + flight-ring event) — the bytes
    never reach the codec."""
    from zoo_tpu.serving.codec import loads

    header = _recv_exact(sock, 4)
    if header is None:
        return None, False
    (word,) = struct.unpack(">I", header)
    has_crc = bool(word & _FRAME_CRC_BIT)
    body = _recv_exact(sock, word & ~_FRAME_CRC_BIT)
    if body is None:
        return None, has_crc
    if has_crc:
        trailer = _recv_exact(sock, 4)
        if trailer is None:
            return None, True
        verify_crc(body, struct.unpack(">I", trailer)[0], "serving")
    return loads(body), has_crc


def _recv_msg(sock: socket.socket):
    return _recv_frame(sock)[0]


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytearray]:
    # preallocate + recv_into: multi-MB array payloads would otherwise
    # pay quadratic bytes-concat; the bytearray goes straight to the
    # codec (slicing/compare/frombuffer all take it) — no final copy
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:])
        if not r:
            return None
        got += r
    return buf


class _Request:
    __slots__ = ("uri", "data", "event", "result", "error", "id",
                 "deadline", "expired", "trace", "pspan", "t_enqueue",
                 "t_dequeue")

    def __init__(self, uri: str, data, rid: Optional[str] = None,
                 deadline: Optional[Deadline] = None,
                 trace: Optional[str] = None,
                 pspan: Optional[str] = None):
        self.uri = uri
        self.data = data
        self.event = threading.Event()
        self.result = None
        self.error = None
        self.id = rid
        self.deadline = deadline
        self.expired = False
        # request-scoped trace identity off the wire + queue timing,
        # so the reply path can emit a per-request span with its
        # measured queue wait (docs/observability.md)
        self.trace = trace
        self.pspan = pspan
        self.t_enqueue: Optional[float] = None
        self.t_dequeue: Optional[float] = None


class _DedupCache:
    """Request-id → :class:`_Request` LRU, the server half of idempotent
    retries/hedges: a duplicate id joins the pending request (or replays
    the finished one) instead of executing the model twice. Entries keep
    their result arrays until evicted, so the capacity knob
    (``ZOO_SERVE_DEDUP_CACHE``) bounds memory, not correctness — an
    evicted id simply re-executes, which is safe for a pure predict."""

    def __init__(self, capacity: int):
        self._cap = int(capacity)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _Request]" = \
            collections.OrderedDict()

    def get(self, rid: str) -> Optional[_Request]:
        with self._lock:
            req = self._entries.get(rid)
            if req is not None:
                self._entries.move_to_end(rid)
            return req

    def put(self, rid: str, req: _Request):
        with self._lock:
            self._entries[rid] = req
            self._entries.move_to_end(rid)
            while len(self._entries) > self._cap:
                self._entries.popitem(last=False)


class ServingServer:
    """``ServingServer(inference_model).start()`` → serve until
    ``stop()``.

    ``num_replicas``: size of the worker pool behind the TCP door — the
    role of the reference's Flink task-slot parallelism
    (``serving/ClusterServing.scala:54-67``: one model copy per slot
    draining a shared queue). Each replica is a batcher thread pulling
    from the shared request queue; pass ``models=[...]`` to give every
    replica its own model copy (distinct devices / true CPU
    parallelism), else they share ``model`` (bounded by its
    ``supported_concurrent_num`` semaphore)."""

    def __init__(self, model, host: str = "127.0.0.1", port: int = 0,
                 batch_size: int = 8, max_wait_ms: float = 5.0,
                 num_replicas: int = 1, models=None,
                 certfile: str = None, keyfile: str = None,
                 breaker: Optional[CircuitBreaker] = None,
                 max_queue: Optional[int] = None,
                 request_timeout: Optional[float] = None,
                 handshake_timeout: Optional[float] = None,
                 dedup_cache: Optional[int] = None,
                 llm_engine=None,
                 version: Optional[str] = None,
                 model_spec: Optional[str] = None,
                 model_loader=None,
                 tenancy=None):
        """``certfile``/``keyfile``: serve over TLS — the trusted-
        serving door of the reference's PPML trusted-realtime-ml story
        (``ppml/trusted-realtime-ml/``: encrypted transport in front of
        the serving pipeline; model-at-rest encryption is
        ``InferenceModel.load_encrypted``).

        ``breaker``: optional :class:`CircuitBreaker` for load shedding —
        after its consecutive-failure threshold trips, predict requests
        are rejected immediately at the front door (error mentions
        "shedding load") instead of queueing behind a dead model; the
        breaker half-opens after its recovery timeout and closes again on
        the first successful batch.

        Admission / deadline knobs (``None`` → the ``ZOO_SERVE_*`` env,
        docs/serving_ha.md): ``max_queue`` bounds the batcher queue —
        past it predicts are rejected at the door with
        ``retryable: True`` and a ``retry_after_ms`` hint instead of
        parking behind work the server cannot finish in time (0 =
        unbounded). ``request_timeout`` is the per-request reply bound
        when the client propagated NO deadline (requests that carry
        ``deadline_ms`` use the deadline itself). ``handshake_timeout``
        bounds the TLS handshake. ``dedup_cache`` sizes the request-id
        LRU that makes client retries/hedges idempotent (0 = off).

        ``llm_engine``: an :class:`zoo_tpu.serving.llm.LLMEngine`
        mounted on this door — adds the streaming ``generate`` op
        (docs/llm_serving.md) next to ``predict``. ``model`` may be
        ``None`` for an llm-only replica (the batcher threads are then
        not started and ``predict`` answers with a routing error).

        Lifecycle identity (docs/model_lifecycle.md): ``version`` is
        the registry version this model came from (``"v3"``; echoed on
        every reply and published as the ``zoo_registry_version_info``
        gauge), ``model_spec`` the spec it was loaded from, and
        ``model_loader`` a ``spec -> (model, version)`` callable the
        wire ``reload`` op uses to hot-swap a new version beside the
        old one (defaults to
        :func:`zoo_tpu.serving.ha.resolve_model_spec`)."""
        self.model = model
        self.llm_engine = llm_engine
        # disaggregation role, advertised on every reply frame (like
        # version) so the HA client learns the pool topology passively;
        # predict-only replicas have none
        self.role = getattr(llm_engine, "role", None)
        self.version = version
        self.model_spec = model_spec
        self.model_loader = model_loader
        # hot-swap: the batcher reads the live model under this lock and
        # reload_model flips it under the same lock — atomic, no drain
        self._swap_lock = threading.Lock()
        # input signatures seen by the batcher ((row_shape, dtype) ->
        # None, insertion-ordered): reload warms the incoming model with
        # one padded-batch inference per signature so the flip never
        # pays a live request's first XLA compile
        # guarded-by: _swap_lock
        self._warm_shapes: "collections.OrderedDict" = \
            collections.OrderedDict()
        if version is not None:
            _version_info.labels(version=version).set(1)
        if model is None and llm_engine is None:
            raise ValueError("ServingServer needs a model, an "
                             "llm_engine, or both")
        self.breaker = breaker
        # multi-tenant QoS (docs/multitenancy.md): the tenant registry
        # the predict door gates admission on; inert (enabled=False)
        # without ZOO_TENANT_CONFIG, so unlabeled single-tenant
        # traffic behaves exactly as before tenancy existed
        self.tenancy = tenancy if tenancy is not None \
            else tenant_registry()
        self.max_queue = max_queue if max_queue is not None else \
            env_int("ZOO_SERVE_MAX_QUEUE", 1024)
        self.request_timeout = request_timeout if request_timeout \
            is not None else env_float("ZOO_SERVE_REQUEST_TIMEOUT", 120.0)
        self.handshake_timeout = handshake_timeout if handshake_timeout \
            is not None else env_float("ZOO_SERVE_HANDSHAKE_TIMEOUT", 10.0)
        cap = dedup_cache if dedup_cache is not None else \
            env_int("ZOO_SERVE_DEDUP_CACHE", 1024)
        self._dedup_cache = _DedupCache(cap) if cap > 0 else None
        # wire-frame integrity (ZOO_WIRE_CRC, default on): replies to
        # CRC-speaking clients carry a CRC trailer; old clients that
        # never stamp/send CRC frames get the plain protocol unchanged
        self._wire_crc = wire_crc_enabled()
        self._replicas = list(models) if models else (
            [model] * max(1, int(num_replicas))
            if model is not None else [])
        self.batch_size = batch_size
        self.max_wait_ms = max_wait_ms
        self._ssl_ctx = None
        if certfile:
            import ssl
            self._ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            self._ssl_ctx.load_cert_chain(certfile, keyfile)
        # local per-stage stats (the reference Timer.scala view, served
        # by the "stats" op) double-published into the shared registry's
        # stage-latency histogram for /metrics scrapes
        self.timers = {
            name: StageTimer(histogram=_stage_seconds.labels(stage=name))
            for name in ("batch", "inference", "total")}
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._stop = threading.Event()
        self._draining = threading.Event()
        # exact drain accounting: a request is ACCEPTED (under
        # _accept_lock, so no admission can race the drain flag) before
        # it is queued, and COMPLETED when its batch resolves — drain is
        # done iff completed == accepted, with no window for a request
        # to hide between the queue and the batch loop
        self._accept_lock = threading.Lock()
        self._accepted = 0
        self._completed = 0
        self._inflight = 0  # batches currently in model.predict
        self._inflight_lock = threading.Lock()

        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def setup(self):
                # wire-integrity state: flips True (sticky, per
                # connection) once the peer proves it speaks CRC frames
                # — either by sending one or by stamping ``crc: 1``
                # into a request; replies then carry the trailer too
                self._crc = False
                # kv_migrate staging, PER CONNECTION: begin/block
                # frames accumulate here and commit hands the engine
                # the assembled payload — a pusher that dies mid-stream
                # takes its half-received state down with the socket
                self._migrate: Dict[str, Dict] = {}
                # small request/response frames ping-pong on each
                # connection: Nagle + delayed-ACK interactions add
                # spurious tail latency under concurrent clients
                try:
                    self.request.setsockopt(socket.IPPROTO_TCP,
                                            socket.TCP_NODELAY, 1)
                except OSError:
                    pass
                # TLS handshake PER CONNECTION THREAD — in get_request
                # it would run on the accept loop, where one idle client
                # blocks every other connection (and stop())
                if outer._ssl_ctx is not None:
                    # handshake bound (ZOO_SERVE_HANDSHAKE_TIMEOUT)
                    self.request.settimeout(outer.handshake_timeout)
                    self.request = outer._ssl_ctx.wrap_socket(
                        self.request, server_side=True)
                    self.request.settimeout(None)

            def finish(self):
                # wrap_socket detaches the original fd, so socketserver's
                # shutdown_request closes the dead pre-wrap object; close
                # the SSLSocket here for a clean close_notify + fd release
                if outer._ssl_ctx is not None:
                    try:
                        self.request.close()
                    except OSError:
                        pass

            def _reply(self, msg, extra):
                """One response frame; the request id AND trace id
                (when the client sent them) are ALWAYS echoed — the id
                so the client can discard a stale attempt's frame, the
                trace so EVERY reply is joinable to its request's
                timeline, sheds and errors included (a rejected request
                that vanished from the trace was the old bug)."""
                out = {}
                if "uri" in msg:
                    out["uri"] = msg.get("uri")
                if msg.get("id") is not None:
                    out["id"] = msg["id"]
                if msg.get("trace") is not None:
                    out["trace"] = msg["trace"]
                if msg.get("tenant") is not None:
                    # tenant echoed on EVERY reply, sheds included —
                    # the client's per-tenant backoff and A/B pinning
                    # key on it without guessing which request this was
                    out["tenant"] = msg["tenant"]
                if outer.version is not None:
                    # lifecycle identity on every frame: the HA client
                    # learns which version each endpoint serves (A/B
                    # routing) without extra probe round-trips
                    out["version"] = outer.version
                if outer.role is not None:
                    # disaggregation role on every frame, same passive
                    # learning: routing needs to know which seats are
                    # prefill/decode before it can pair a handoff
                    out["role"] = outer.role
                out.update(extra)
                _send_msg(self.request, out, crc=self._crc)

            def _note_reject(self, msg, reason):
                """Door-rejection bookkeeping beyond the counters: the
                flight ring gets the shed (with its reason — the first
                thing a postmortem wants), and the request's trace gets
                an instant event so rejected requests reconstruct in
                the timeline too."""
                kw = {}
                if msg.get("tenant"):
                    kw["tenant"] = msg["tenant"]
                record_event("shed", op=msg.get("op", "predict"),
                             reason=reason, **kw)
                if msg.get("trace") is not None:
                    emit_event("server.shed", trace=msg["trace"],
                               parent=msg.get("pspan"), reason=reason,
                               rid=msg.get("id"), **kw)

            def _await_and_reply(self, msg, req, deadline):
                """Reply stage: wait for the batcher to resolve ``req``
                under a deadline-derived bound (the propagated deadline
                when present, else ZOO_SERVE_REQUEST_TIMEOUT) and send
                the outcome. Used by fresh requests and by duplicates
                joining an in-flight/completed request. Returns the
                outcome string ACTUALLY sent to this caller — a reply-
                stage timeout is this connection's verdict only (a
                joined duplicate must not mutate the shared request's
                state), so the per-request span reads it from here."""
                if deadline is not None:
                    done = req.event.wait(
                        timeout=max(0.0, deadline.remaining()))
                else:
                    done = req.event.wait(timeout=outer.request_timeout)
                if not done:
                    if deadline is not None:
                        # post-inference reply enforcement: the budget
                        # ran out while the request sat in the queue or
                        # the batch — answer "expired" NOW; the batcher
                        # will drop (or has computed-and-wasted) the
                        # stale entry on its own
                        _requests.labels(outcome="expired").inc()
                        _deadline_expired.labels(stage="reply").inc()
                        self._reply(msg, {
                            "expired": True,
                            "error": "deadline expired before the batch "
                                     "resolved (request dropped)"})
                        return "expired"
                    _requests.labels(outcome="error").inc()
                    self._reply(msg, {
                        "error": "timeout waiting for batch inference "
                                 "(first request may be paying XLA "
                                 "compile; bound is "
                                 "$ZOO_SERVE_REQUEST_TIMEOUT "
                                 f"= {outer.request_timeout:g}s)"})
                    return "error"
                if req.error is not None:
                    if req.expired:
                        _requests.labels(outcome="expired").inc()
                        self._reply(msg, {"expired": True,
                                          "error": req.error})
                        return "expired"
                    _requests.labels(outcome="error").inc()
                    self._reply(msg, {"error": req.error})
                    return "error"
                _requests.labels(outcome="ok").inc()
                self._reply(msg, {"result": req.result})
                return "ok"

            def _handle_predict(self, msg):
                rid = msg.get("id")
                if outer.model is None:
                    _requests.labels(outcome="error").inc()
                    self._reply(msg, {
                        "error": "this replica serves the llm "
                                 "generate op only (no predict "
                                 "model mounted)"})
                    return
                deadline = Deadline.from_ms(msg.get("deadline_ms"))
                # 1. idempotency: a duplicate id (client retry after a
                # mid-RPC reset, or a hedge landing on the same replica)
                # joins the original request — never a second execution
                if rid is not None and outer._dedup_cache is not None:
                    prior = outer._dedup_cache.get(rid)
                    if prior is not None:
                        _dedup.labels(
                            kind="replay" if prior.event.is_set()
                            else "inflight").inc()
                        self._await_and_reply(msg, prior, deadline)
                        return
                # 2. A/B version pinning: a request pinned to a version
                # this replica does not serve is bounced retryable so
                # the client's failover finds a replica that does (the
                # echoed version teaches it which). AFTER dedup — a
                # retry/hedge of an already-executed request must join
                # it even when a hot-swap flipped the version between
                # the attempts (idempotency survives the flip).
                want = msg.get("model_version")
                if want is not None and outer.version is not None \
                        and want != outer.version:
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="version_mismatch").inc()
                    self._note_reject(msg, "version_mismatch")
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "version_mismatch": True,
                        "error": f"this replica serves {outer.version}, "
                                 f"not {want}; retry another replica"})
                    return
                # 3. breaker load shedding: fail fast at the door while
                # the model is known-broken, instead of parking the
                # caller behind a dead batcher
                if outer.breaker is not None and \
                        not outer.breaker.allow():
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="breaker_open").inc()
                    self._note_reject(msg, "breaker_open")
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "retry_after_ms": int(
                            1000 * outer.breaker.recovery_timeout),
                        "error": "server shedding load (circuit "
                                 "open after repeated inference "
                                 "failures; retry later)"})
                    return
                # 4. dead-on-arrival: the budget was spent in transit or
                # upstream queues — reject instead of computing a result
                # nobody is waiting for
                if deadline is not None and deadline.expired():
                    _requests.labels(outcome="expired").inc()
                    _deadline_expired.labels(stage="admission").inc()
                    self._note_reject(msg, "expired_admission")
                    self._reply(msg, {
                        "expired": True,
                        "error": "deadline expired before admission "
                                 "(budget exhausted upstream)"})
                    return
                # 5. tenant admission (docs/multitenancy.md): charge
                # the request to ITS tenant's token bucket before it
                # can touch the shared queue. The retry hint is that
                # bucket's own refill time — a flooding tenant backs
                # off on its own clock while everyone else's hints
                # stay untouched. Inert without tenant config.
                tenant = msg.get("tenant") or ""
                if outer.tenancy.enabled:
                    ok, t_hint = outer.tenancy.admit(tenant)
                    if not ok:
                        label = tenant or "default"
                        _requests.labels(outcome="shed").inc()
                        _shed.labels(reason="tenant_rate").inc()
                        _tenant_shed.labels(tenant=label,
                                            reason="rate").inc()
                        self._note_reject(msg, "tenant_rate")
                        self._reply(msg, {
                            "shed": True, "retryable": True,
                            "retry_after_ms": t_hint,
                            "reason": "rate",
                            "error": f"tenant {label!r} rate limited; "
                                     f"retry after ~{t_hint}ms"})
                        return
                    _tenant_admitted.labels(
                        tenant=tenant or "default").inc()
                # 6. admission control: early rejection at the bounded
                # queue, with a retry-after hint sized to the backlog —
                # overload sheds at the door, not after a timeout
                depth = outer._queue.qsize()
                if outer.max_queue and depth >= outer.max_queue:
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="queue_full").inc()
                    self._note_reject(msg, "queue_full")
                    hint = int(outer.max_wait_ms * max(
                        1, depth // max(1, outer.batch_size)))
                    if outer.tenancy.enabled:
                        # rate-limited tenants wait out their OWN
                        # refill when it is the longer bound — the
                        # backlog estimate stays for everyone else
                        own = outer.tenancy.bucket(
                            tenant).retry_after_ms()
                        hint = max(hint, own)
                        _tenant_shed.labels(
                            tenant=tenant or "default",
                            reason="queue_full").inc()
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "retry_after_ms": hint,
                        "error": f"server queue full ({depth} waiting, "
                                 f"bound {outer.max_queue}); retry "
                                 f"after ~{hint}ms or another replica"})
                    return
                with outer._accept_lock:
                    draining = outer._draining.is_set()
                    if not draining:
                        outer._accepted += 1
                if draining:
                    # graceful drain: NEW work is turned away at
                    # the door; everything already queued or
                    # in-flight still completes and responds
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="draining").inc()
                    self._note_reject(msg, "draining")
                    self._reply(msg, {
                        "shed": True, "draining": True,
                        "retryable": True,
                        "error": "server draining (shutting "
                                 "down); retry another replica"})
                    return
                req = _Request(msg["uri"], msg["data"], rid=rid,
                               deadline=deadline,
                               trace=msg.get("trace"),
                               pspan=msg.get("pspan"))
                if rid is not None and outer._dedup_cache is not None:
                    outer._dedup_cache.put(rid, req)
                t0 = time.perf_counter()
                t0_wall = time.time()
                req.t_enqueue = t0
                outer._queue.put(req)
                _queue_depth.set(outer._queue.qsize())
                outcome = self._await_and_reply(msg, req, deadline)
                dur = time.perf_counter() - t0
                outer.timers["total"].record(dur)
                # the request's server-side span: queue wait + batch +
                # inference + reply under ITS trace id, so the timeline
                # merger shows where this replica spent the budget.
                # ``outcome`` is what THIS caller was told (a reply-
                # stage timeout included — the slowest requests must
                # not read as successes in the timeline).
                if req.trace is not None:
                    attrs = {"rid": rid, "outcome": outcome}
                    if req.t_dequeue is not None:
                        attrs["queue_wait_s"] = round(
                            req.t_dequeue - t0, 6)
                    emit_span("server.predict", t0_wall, dur,
                              trace=req.trace, parent=req.pspan,
                              ok=outcome == "ok", t0=t0, **attrs)

            def _handle_generate(self, msg):
                """Streaming autoregressive generation
                (docs/llm_serving.md wire format): the reply is a
                SEQUENCE of frames on this connection — ``{id, seq,
                tokens: [...]}`` chunks as the engine emits them, then
                one terminal ``{id, done: true, outcome, n_tokens}``.
                ``resume_from`` skips the first N generated tokens
                (the HA client's failover-resume: decode is greedy and
                deterministic, so a fresh replica regenerates the same
                stream and only the unseen suffix goes on the wire)."""
                eng = outer.llm_engine
                rid = msg.get("id")
                deadline = Deadline.from_ms(msg.get("deadline_ms"))
                if eng is None:
                    self._reply(msg, {
                        "done": True, "outcome": "error",
                        "error": "no llm engine mounted on this "
                                 "replica (generate needs a "
                                 "llama:* model spec)"})
                    return
                if outer.breaker is not None and \
                        not outer.breaker.allow():
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="breaker_open").inc()
                    self._note_reject(msg, "breaker_open")
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "error": "server shedding load (circuit open)"})
                    return
                if outer._draining.is_set():
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="draining").inc()
                    self._note_reject(msg, "draining")
                    self._reply(msg, {
                        "shed": True, "draining": True,
                        "retryable": True,
                        "error": "server draining; retry another "
                                 "replica"})
                    return
                if deadline is not None and deadline.expired():
                    _requests.labels(outcome="expired").inc()
                    _deadline_expired.labels(stage="admission").inc()
                    self._note_reject(msg, "expired_admission")
                    self._reply(msg, {
                        "done": True, "outcome": "expired",
                        "expired": True,
                        "error": "deadline expired before admission"})
                    return
                # disaggregation (docs/disaggregated_serving.md): a
                # ``handoff: [host, port]`` request asks THIS replica
                # to prefill only and push the KV to the decode target;
                # a prefill-role seat sheds everything else retryable
                # so plain streams land on decode/mixed seats
                handoff = msg.get("handoff")
                if eng.role == "prefill" and not handoff:
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason="role").inc()
                    self._note_reject(msg, "role")
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "error": "role=prefill replica serves handoff "
                                 "generates only; retry a decode/mixed "
                                 "replica"})
                    return
                if handoff and eng.role == "decode":
                    # a decode seat never prefills-for-export; run the
                    # request as a plain local generate instead
                    handoff = None
                from zoo_tpu.serving.llm.engine import AdmissionError
                # adoption: a staged kv_migrate payload under this rid
                # means the prompt is already prefilled here — decode
                # starts immediately. A prompt mismatch (id collision)
                # discards the payload; determinism makes the plain
                # re-prefill fallback byte-identical either way.
                adopt = None
                if rid is not None:
                    adopt = eng.pop_adopted(rid)
                    if adopt is not None and adopt.get("prompt") != \
                            [int(t) for t in msg["prompt"]]:
                        adopt = None
                # per-stream sampling params ride the wire; a missing
                # seed derives from the request id server-side, so a
                # failover resume (same rid, another replica) replays
                # the same draws (docs/llm_serving.md)
                sampling = {k: msg[k] for k in
                            ("temperature", "top_k", "top_p", "seed")
                            if msg.get(k) is not None}
                # per-stream speculative budget: caps (never raises)
                # the replica's verify width; 0 = plain decode lanes
                spec_k = msg.get("spec_k")
                trace_id = msg.get("trace")
                try:
                    h = eng.submit(
                        np.asarray(msg["prompt"]),
                        int(msg.get("max_new_tokens", 16)),
                        rid=rid, deadline=deadline,
                        sampling=sampling or None,
                        spec_k=None if spec_k is None else int(spec_k),
                        trace_id=trace_id,
                        parent_span=msg.get("pspan"),
                        handoff=bool(handoff), adopt=adopt,
                        tenant=msg.get("tenant"))
                except AdmissionError as e:
                    # the engine computed retry_after_ms from the
                    # SHEDDING tenant's own bucket (and stamps which
                    # quota tripped); relay both so the client backs
                    # off per-tenant instead of hammering the pool
                    reason = getattr(e, "reason", "queue_full")
                    door = "tenant_rate" if reason == "rate" \
                        else "queue_full"
                    _requests.labels(outcome="shed").inc()
                    _shed.labels(reason=door).inc()
                    self._note_reject(msg, door)
                    self._reply(msg, {
                        "shed": True, "retryable": True,
                        "retry_after_ms": e.retry_after_ms,
                        "reason": reason,
                        "error": str(e)})
                    return
                except (ValueError, KeyError) as e:
                    _requests.labels(outcome="error").inc()
                    self._reply(msg, {"done": True, "outcome": "error",
                                      "error": repr(e)})
                    return
                cursor = max(0, int(msg.get("resume_from") or 0))
                resume_from = cursor
                seq = 0
                t_stream = time.perf_counter()
                t_stream_wall = time.time()
                h.subscribe()
                completed = False
                try:
                    last_progress = time.monotonic()
                    while True:
                        toks, done = h.wait_new(cursor, 0.25)
                        if toks:
                            cursor += len(toks)
                            last_progress = time.monotonic()
                            if not done:
                                self._reply(msg, {"seq": seq,
                                                  "tokens": toks,
                                                  "done": False})
                                seq += 1
                                continue
                        if done:
                            if h.outcome == "handoff":
                                # prefill parked: push the KV payload
                                # to the decode target BEFORE the
                                # terminal frame, so the client's
                                # second leg always finds the staged
                                # adoption (or learns the push failed
                                # and re-prefills elsewhere)
                                migrated = self._push_handoff(
                                    eng, rid, handoff, deadline, msg)
                                _requests.labels(outcome="ok").inc()
                                self._reply(msg, {
                                    "seq": seq, "done": True,
                                    "outcome": "handoff",
                                    "migrated": migrated,
                                    "tokens": [], "n_tokens": 0})
                                completed = True
                                return
                            out = {"seq": seq, "done": True,
                                   "outcome": h.outcome,
                                   "tokens": toks,
                                   "n_tokens": len(h.tokens)}
                            if h.truncated:
                                out["truncated"] = True
                            if h.outcome == "expired":
                                out["expired"] = True
                                _requests.labels(
                                    outcome="expired").inc()
                                _deadline_expired.labels(
                                    stage="stream").inc()
                            elif h.outcome == "ok":
                                _requests.labels(outcome="ok").inc()
                            else:
                                _requests.labels(outcome="error").inc()
                            if h.error:
                                out["error"] = h.error
                            self._reply(msg, out)
                            completed = True
                            return
                        # no progress: enforce the no-deadline reply
                        # bound (a deadline-carrying stream is expired
                        # by the engine itself)
                        if deadline is None and time.monotonic() - \
                                last_progress > outer.request_timeout:
                            _requests.labels(outcome="error").inc()
                            self._reply(msg, {
                                "seq": seq, "done": True,
                                "outcome": "error",
                                "error": "no tokens within "
                                         "$ZOO_SERVE_REQUEST_TIMEOUT "
                                         f"={outer.request_timeout:g}s"})
                            return
                except OSError:
                    # client went away mid-stream; fall through to the
                    # unsubscribe cleanup and stop pushing frames
                    pass
                finally:
                    if h.unsubscribe() <= 0 and not h.done \
                            and not completed:
                        # last reader gone with the stream still
                        # decoding: cancel so its KV blocks free NOW,
                        # not at max_new_tokens
                        eng.cancel(h.id)
                    if trace_id is not None:
                        # this HOP's serving span (one per attempt —
                        # original, hedge, failover resume — each with
                        # its resume cursor): the engine's llm.* spans
                        # nest under the same trace
                        emit_span("server.generate", t_stream_wall,
                                  time.perf_counter() - t_stream,
                                  trace=trace_id,
                                  parent=msg.get("pspan"),
                                  ok=completed, t0=t_stream, rid=rid,
                                  resume_from=resume_from,
                                  sent_tokens=cursor - resume_from,
                                  outcome=h.outcome if completed
                                  else "disconnected")

            def _push_handoff(self, eng, rid, target, deadline, msg):
                """Prefill side of a disaggregated generate: take the
                parked payload, export its KV bytes, and stream them
                to the decode target as ``kv_migrate`` begin/block/
                commit frames (begin/block unacknowledged; the commit
                reply says whether the peer staged the adoption). The
                parked blocks are ALWAYS released before returning —
                on any failure the client falls back to a plain
                re-prefill, which determinism makes byte-identical."""
                t0 = time.perf_counter()
                payload = eng.take_handoff(rid)
                if payload is None or not target:
                    if payload is not None:
                        eng.release_handoff(rid)
                    record_event("kv_handoff_abort", rid=rid,
                                 reason="expired" if payload is None
                                 else "no_target")
                    return False
                ok = False
                err = None
                nbytes = 0
                try:
                    host, port = str(target[0]), int(target[1])
                    # the chaos harness arms this seam to stall the
                    # push so a SIGKILL lands mid-handoff
                    fault_point("serving.kv_migrate.push", rid=rid,
                                blocks=len(payload["blocks"]))
                    exp = getattr(eng.model, "export_kv_blocks", None)
                    kv = None if exp is None else exp(
                        payload["blocks"])
                    sock = socket.create_connection((host, port),
                                                    timeout=5.0)
                    try:
                        try:
                            sock.setsockopt(socket.IPPROTO_TCP,
                                            socket.TCP_NODELAY, 1)
                        except OSError:
                            pass
                        crc = outer._wire_crc
                        begin = {
                            "op": "kv_migrate", "phase": "begin",
                            "id": rid, "crc": 1 if crc else 0,
                            "prompt": payload["prompt"],
                            "first": payload["first"],
                            "sampling": payload["sampling"],
                            "hashes": [h.hex()
                                       for h in payload["hashes"]],
                            "max_new": payload["max_new"],
                            "aux": payload["aux"],
                            "block_size": payload["block_size"],
                            "n_blocks": len(payload["blocks"])}
                        if msg.get("trace") is not None:
                            begin["trace"] = msg["trace"]
                        _send_msg(sock, begin, crc=crc)
                        if kv is not None:
                            step = max(1, int(knob_value(
                                "ZOO_KV_MIGRATE_CHUNK_BLOCKS")))
                            for i in range(0, len(payload["blocks"]),
                                           step):
                                part = {name: a[:, i:i + step]
                                        for name, a in kv.items()}
                                nbytes += sum(int(a.nbytes)
                                              for a in part.values())
                                _send_msg(sock, {
                                    "op": "kv_migrate",
                                    "phase": "block", "id": rid,
                                    "index": i, "kv": part}, crc=crc)
                        commit = {"op": "kv_migrate",
                                  "phase": "commit", "id": rid}
                        if deadline is not None:
                            # deadline propagation: what is left of
                            # the request budget bounds the adoption
                            commit["deadline_ms"] = int(1000 * max(
                                0.0, deadline.remaining()))
                        _send_msg(sock, commit, crc=crc)
                        resp = _recv_msg(sock)
                        ok = bool(resp and resp.get("ok")
                                  and resp.get("adopted"))
                    finally:
                        sock.close()
                except (OSError, FrameCorrupt) as e:
                    err = repr(e)
                finally:
                    eng.release_handoff(rid)
                if ok:
                    _migrated_bytes.inc(nbytes)
                    _handoff_seconds.observe(time.perf_counter() - t0)
                    return True
                record_event("kv_handoff_abort", rid=rid,
                             reason=err or "peer_refused")
                return False

            def _handle_kv_migrate(self, msg):
                """Decode side of the handoff wire: ``begin`` stages a
                sequence's metadata on this connection, ``block``
                frames append its exported KV chunks, ``commit`` hands
                the assembled payload to the engine (the only
                acknowledged phase). The allocator is untouched until
                the matching generate arrives — a pusher that dies
                after commit leaks nothing here."""
                eng = outer.llm_engine
                phase = msg.get("phase")
                rid = msg.get("id")
                if eng is None or not rid:
                    if phase == "commit":
                        self._reply(msg, {
                            "ok": False, "adopted": False,
                            "error": "no llm engine mounted"
                                     if eng is None else
                                     "kv_migrate needs an id"})
                    return
                if phase == "begin":
                    self._migrate[rid] = {"msg": msg, "chunks": []}
                    return
                st = self._migrate.get(rid)
                if phase == "block":
                    if st is not None:
                        st["chunks"].append(
                            (int(msg.get("index") or 0),
                             msg.get("kv") or {}))
                    return
                if phase != "commit":
                    self._reply(msg, {
                        "ok": False, "adopted": False,
                        "error": f"unknown kv_migrate phase {phase!r}"})
                    return
                st = self._migrate.pop(rid, None)
                if st is None:
                    self._reply(msg, {
                        "ok": False, "adopted": False,
                        "error": "commit without a begin on this "
                                 "connection"})
                    return
                deadline = Deadline.from_ms(msg.get("deadline_ms"))
                if deadline is not None and deadline.expired():
                    _deadline_expired.labels(stage="admission").inc()
                    self._reply(msg, {"ok": False, "adopted": False,
                                      "expired": True})
                    return
                b = st["msg"]
                kv = None
                if st["chunks"]:
                    st["chunks"].sort(key=lambda t: t[0])
                    names = sorted(st["chunks"][0][1])
                    try:
                        kv = {name: np.concatenate(
                            [np.asarray(c[1][name])
                             for c in st["chunks"]], axis=1)
                            for name in names}
                    except (KeyError, ValueError) as e:
                        self._reply(msg, {"ok": False,
                                          "adopted": False,
                                          "error": repr(e)})
                        return
                try:
                    payload = {
                        "rid": rid,
                        "prompt": [int(t)
                                   for t in b.get("prompt") or ()],
                        "first": int(b.get("first") or 0),
                        "sampling": b.get("sampling"),
                        "hashes": [bytes.fromhex(h)
                                   for h in b.get("hashes") or ()],
                        "block_size": int(b.get("block_size") or 0),
                        "aux": b.get("aux") or {},
                        "max_new": int(b.get("max_new") or 0),
                        "kv": kv,
                    }
                except (TypeError, ValueError) as e:
                    self._reply(msg, {"ok": False, "adopted": False,
                                      "error": repr(e)})
                    return
                adopted = eng.offer_adopted(payload)
                self._reply(msg, {"ok": True,
                                  "adopted": bool(adopted)})

            def _handle_reload(self, msg):
                """Wire half of :meth:`ServingServer.reload_model`.
                The reply is sent only AFTER the swap (or its failure):
                an ``ok`` means the new version is live on this replica,
                an error means the old model never stopped serving."""
                spec = msg.get("spec")
                if not spec:
                    self._reply(msg, {"error": "reload needs a spec"})
                    return
                try:
                    info = outer.reload_model(
                        spec, version=msg.get("version"),
                        warm=bool(msg.get("warm", True)))
                except Exception as e:  # noqa: BLE001 — the caller
                    # (rolling updater) turns this into a rollback; the
                    # incumbent model is still serving
                    self._reply(msg, {"error": repr(e),
                                      "reload_failed": True})
                    return
                self._reply(msg, {"ok": True, **info})

            def _handle_chaos(self, msg):
                """Arm (or clear) a fault site in THIS replica process —
                the remote half of the deterministic chaos harness
                (docs/fault_tolerance.md). Refused unless the operator
                deliberately armed the door (``ZOO_CHAOS_ALLOW=1`` in
                the replica env, which the chaos smokes set): a
                production replica must never take fault commands off
                an unauthenticated socket."""
                if os.environ.get("ZOO_CHAOS_ALLOW") not in ("1", "true"):
                    self._reply(msg, {
                        "error": "chaos ops disabled on this replica "
                                 "(set ZOO_CHAOS_ALLOW=1 in its env)"})
                    return
                from zoo_tpu.util.resilience import default_injector
                site = msg.get("site")
                if not site:
                    self._reply(msg, {"error": "chaos needs a site"})
                    return
                if msg.get("clear"):
                    default_injector.clear(site)
                    record_event("chaos_clear", site=site)
                    self._reply(msg, {"ok": True, "cleared": site})
                    return
                delay = float(msg.get("delay_ms") or 0.0) / 1000.0
                err = msg.get("error")
                exc = None
                if err == "oserror":
                    exc = OSError(f"injected fault at {site}")
                elif err == "connection":
                    exc = ConnectionResetError(
                        f"injected fault at {site}")
                elif err:
                    self._reply(msg, {
                        "error": f"unknown chaos error kind {err!r} "
                                 "(oserror | connection)"})
                    return
                action = (lambda **_k: time.sleep(delay)) if delay \
                    else None
                if action is None and exc is None:
                    self._reply(msg, {
                        "error": "chaos needs delay_ms, error, or "
                                 "clear"})
                    return
                default_injector.inject(
                    site, exc=exc, action=action,
                    times=(int(msg["times"]) if msg.get("times")
                           is not None else None),
                    p=float(msg.get("p", 1.0)))
                record_event("chaos_arm", site=site,
                             delay_ms=msg.get("delay_ms"),
                             error=err, p=msg.get("p"))
                self._reply(msg, {"ok": True, "site": site})

            def handle(self):
                while True:
                    try:
                        msg, had_crc = _recv_frame(self.request)
                    except FrameCorrupt:
                        # a corrupt REQUEST cannot be trusted for a
                        # reply (id/op unreadable): drop the connection
                        # — the client's retry path redials and the
                        # dedup cache keeps the retry idempotent
                        record_event("corrupt_request_dropped")
                        return
                    if msg is None:
                        return
                    if outer._wire_crc and \
                            (had_crc or msg.get("crc")):
                        # the peer speaks CRC frames (sent one, or
                        # asked via the piggybacked ``crc`` field):
                        # every reply on this connection now carries
                        # the trailer
                        self._crc = True
                    if msg.get("op") == "predict":
                        self._handle_predict(msg)
                    elif msg.get("op") == "generate":
                        self._handle_generate(msg)
                    elif msg.get("op") == "kv_migrate":
                        self._handle_kv_migrate(msg)
                    elif msg.get("op") == "reload":
                        self._handle_reload(msg)
                    elif msg.get("op") == "version":
                        self._reply(msg, {
                            "ok": True,
                            "model_spec": outer.model_spec,
                            "version": outer.version})
                    elif msg.get("op") == "llm_stats":
                        eng = outer.llm_engine
                        self._reply(msg, {"stats": eng.stats()}
                                    if eng is not None else
                                    {"error": "no llm engine"})
                    elif msg.get("op") == "stats":
                        self._reply(msg, {k: t.stats()
                                          for k, t in outer.timers.items()})
                    elif msg.get("op") == "debug_dump":
                        # the flight recorder's bundle, pulled LIVE
                        # (docs/observability.md): ring + metrics +
                        # config + open spans, no process death needed
                        self._reply(msg, {
                            "ok": True,
                            "bundle": flight_recorder().snapshot_bundle(
                                "debug_dump")})
                    elif msg.get("op") == "chaos":
                        self._handle_chaos(msg)
                    elif msg.get("op") == "ping":
                        self._reply(msg, {"ok": True})

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

            def handle_error(inner, request, client_address):
                # under TLS, failed handshakes (plaintext probes,
                # timeouts — all OSError subclasses) are a
                # per-connection event, not a server stack trace;
                # plaintext mode keeps full tracebacks
                import sys as _sys
                exc = _sys.exc_info()[1]
                if outer._ssl_ctx is not None and isinstance(exc,
                                                             OSError):
                    return
                super(Server, inner).handle_error(request,
                                                  client_address)

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address

    # -- model lifecycle ---------------------------------------------------
    def _note_warm_shape(self, row_shape, dtype):
        key = (tuple(int(d) for d in row_shape), np.dtype(dtype).str)
        # under the swap lock: reload_model snapshots this dict while
        # batcher threads keep recording — an unlocked insert/pop could
        # blow up its iteration and fail a perfectly good reload
        with self._swap_lock:
            if key not in self._warm_shapes:
                self._warm_shapes[key] = None
                while len(self._warm_shapes) > 8:
                    self._warm_shapes.popitem(last=False)

    def reload_model(self, spec: str, version: Optional[str] = None,
                     warm: bool = True) -> Dict:
        """Hot-swap to the model at ``spec`` with ZERO downtime: load +
        verify the new model BESIDE the old one (the old model keeps
        serving the whole time), prime it with one padded-batch
        inference at every input signature this server has compiled
        (so the first post-swap request never pays an XLA compile),
        then flip atomically under the batcher's swap lock. Any
        load/verify/warm failure raises WITHOUT flipping — a bad
        candidate can never replace a healthy incumbent.

        This is the wire ``reload`` op's engine and what
        :meth:`zoo_tpu.serving.ha.ReplicaGroup.rolling_update` drives
        one replica at a time."""
        if self.model is None:
            raise RuntimeError("this replica serves the llm generate op "
                               "only; hot-swap reload applies to the "
                               "predict model path")
        if len({id(m) for m in self._replicas}) > 1:
            # models=[...] gave every batcher its OWN copy (models not
            # safe for concurrent predict, or pinned to distinct
            # devices); a single loaded instance cannot honor that —
            # refuse rather than silently regress thread safety
            raise RuntimeError(
                "hot-swap reload is not supported on a server built "
                "with distinct per-replica model copies (models=[...]); "
                "restart the replica process instead")
        t0 = time.perf_counter()
        try:
            loader = self.model_loader
            if loader is None:
                from zoo_tpu.serving.ha import resolve_model_spec
                loader = lambda s: resolve_model_spec(  # noqa: E731
                    s, batch_size=self.batch_size)
            fault_point("serving.reload", spec=spec)
            new_model, loaded_version = loader(spec)
            version = version or loaded_version
            warmed = 0
            if warm:
                with self._swap_lock:
                    shapes = list(self._warm_shapes)
                for row_shape, dtype in shapes:
                    x = np.zeros((self.batch_size,) + row_shape,
                                 np.dtype(dtype))
                    np.asarray(new_model.predict(
                        x, batch_size=self.batch_size))
                    warmed += 1
        except Exception:
            _reloads.labels(outcome="failed").inc()
            raise
        with self._swap_lock:
            previous = self.version
            self.model = new_model
            self._replicas = [new_model] * max(1, len(self._replicas))
            self.version = version
            self.model_spec = spec
        if previous is not None:
            _version_info.labels(version=previous).set(0)
        if version is not None:
            _version_info.labels(version=version).set(1)
        _reloads.labels(outcome="ok").inc()
        return {"version": version, "previous": previous,
                "warmed": warmed,
                "reload_seconds": round(time.perf_counter() - t0, 4)}

    # -- batcher -----------------------------------------------------------
    def _drop_expired(self, req: _Request):
        """Answer an expired request WITHOUT computing it: the budget is
        gone, so inference would be pure waste (the Tail-at-Scale "don't
        do work nobody is waiting for" rule). Counts toward drain
        accounting like any completed request."""
        req.expired = True
        req.error = ("deadline expired before inference "
                     "(dropped unexecuted)")
        _deadline_expired.labels(stage="batch").inc()
        req.event.set()
        with self._inflight_lock:
            self._completed += 1

    def _batch_loop(self, idx: int = 0):
        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            first.t_dequeue = time.perf_counter()
            if first.deadline is not None and first.deadline.expired():
                self._drop_expired(first)
                continue
            t0 = time.perf_counter()
            batch: List[_Request] = [first]
            deadline = time.perf_counter() + self.max_wait_ms / 1000.0
            while len(batch) < self.batch_size:
                remaining = deadline - time.perf_counter()
                # the batch window never burns a member's remaining
                # budget: the tightest propagated deadline in the batch
                # caps how long we keep assembling
                tightest = min(
                    (r.deadline.remaining() for r in batch
                     if r.deadline is not None), default=remaining)
                remaining = min(remaining, tightest)
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                nxt.t_dequeue = time.perf_counter()
                if nxt.deadline is not None and nxt.deadline.expired():
                    self._drop_expired(nxt)
                    continue
                batch.append(nxt)
            # final pre-inference gate: anything that expired while the
            # batch assembled is dropped here, not computed
            live = []
            for r in batch:
                if r.deadline is not None and r.deadline.expired():
                    self._drop_expired(r)
                else:
                    live.append(r)
            batch = live
            self.timers["batch"].record(time.perf_counter() - t0)
            _queue_depth.set(self._queue.qsize())
            if not batch:
                continue
            _batch_occupancy.observe(len(batch))

            with self._inflight_lock:
                self._inflight += 1
            t1 = time.perf_counter()
            try:
                with span("serving.batch", size=len(batch)):
                    fault_point("serving.infer", batch=len(batch))
                    arrays = [np.asarray(r.data) for r in batch]
                    # pad UP to a whole multiple of batch_size so ONE
                    # XLA executable serves every occupancy. Without
                    # this, each distinct request count compiled its
                    # own forward — under concurrent clients the first
                    # window ate up to batch_size compiles, the
                    # multi-second p99 pathology (8.6s at bs8 in
                    # BENCH_r05 while bs32, running second on a warm
                    # jit cache, saw 110ms). One concatenate builds the
                    # padded batch — this is the per-window hot path.
                    real = sum(len(a) for a in arrays)
                    # zero-fill padding (a repeat of the last row would
                    # yield an EMPTY pad when a zero-row request lands
                    # last, silently reintroducing the variable shape);
                    # max() keeps an all-empty window a full batch too
                    padded = max(self.batch_size,
                                 -(-real // self.batch_size)
                                 * self.batch_size)
                    to_stack = arrays if padded == real else arrays + [
                        np.zeros((padded - real,) + arrays[0].shape[1:],
                                 arrays[0].dtype)]
                    stacked = np.concatenate(to_stack, axis=0)
                    # the LIVE model, read under the swap lock so a
                    # concurrent reload flips atomically between
                    # batches — a batch runs wholly on the old or
                    # wholly on the new version, never a mix
                    with self._swap_lock:
                        model = self._replicas[idx]
                    self._note_warm_shape(stacked.shape[1:],
                                          stacked.dtype)
                    preds = model.predict(stacked,
                                          batch_size=self.batch_size)
                    preds = np.asarray(preds)[:real]
                    offset = 0
                    for r, a in zip(batch, arrays):
                        r.result = np.asarray(preds[offset:offset + len(a)])
                        offset += len(a)
                if self.breaker is not None:
                    self.breaker.record_success()
            except Exception as e:  # route the error to every caller
                if self.breaker is not None:
                    self.breaker.record_failure()
                for r in batch:
                    r.error = repr(e)
            self.timers["inference"].record(time.perf_counter() - t1)
            for r in batch:
                r.event.set()
            with self._inflight_lock:
                self._inflight -= 1
                self._completed += len(batch)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ServingServer":
        self._threads = [
            threading.Thread(target=self._server.serve_forever,
                             daemon=True)]
        self._threads += [
            threading.Thread(target=self._batch_loop, args=(i,),
                             daemon=True, name=f"zoo-serving-replica-{i}")
            for i in range(len(self._replicas))]
        for t in self._threads:
            t.start()
        return self

    def drain(self, timeout: Optional[float] = None,
              snapshot_path: str = None) -> bool:
        """Graceful shutdown (the SIGTERM path): stop taking new work,
        finish everything already accepted, flush the metrics snapshot,
        then close. Returns True when every queued/in-flight request was
        answered inside ``timeout`` (``None`` → the
        ``ZOO_SERVE_DRAIN_TIMEOUT_S`` env, default 30 — rolling updates
        budget replica swaps with the SAME knob, so raising it for slow
        LLM streams protects both paths; False = timed out and
        force-closed; the stragglers get their normal timeout error).
        The measured drain time lands on ``zoo_serve_drain_seconds``.

        Order matters: (1) ``_draining`` is raised under the accept
        lock, so no handler can slip a request past the closing door —
        admission and the flag flip are mutually exclusive; (2) wait
        until every accepted request has completed (exact counters — a
        request between queue-pop and batch dispatch still counts as
        outstanding); (3) write the metrics snapshot (``snapshot_path``
        or ``$ZOO_OBS_SNAPSHOT``) so the final request tallies survive
        the process; (4) ``stop()``."""
        if timeout is None:
            timeout = drain_timeout()
        t0 = time.monotonic()
        with self._accept_lock:
            self._draining.set()
            outstanding_at_close = self._accepted
        deadline = t0 + timeout
        drained = False
        while time.monotonic() < deadline:
            with self._inflight_lock:
                done = self._completed
            if done >= outstanding_at_close and \
                    self._queue.qsize() == 0:
                drained = True
                break
            time.sleep(0.01)
        _drain_seconds.observe(time.monotonic() - t0)
        record_event("drain", drained=drained,
                     seconds=round(time.monotonic() - t0, 3))
        path = snapshot_path or knob_value("ZOO_OBS_SNAPSHOT")
        if path:
            try:
                from zoo_tpu.obs.exporters import write_snapshot
                write_snapshot(path)
            except Exception as e:  # noqa: BLE001 — flush is best-effort
                import logging
                logging.getLogger(__name__).warning(
                    "drain: metrics snapshot flush failed: %s", e)
        self.stop()
        return drained

    def install_drain_handler(self, signals=None,
                              timeout: Optional[float] = None,
                              snapshot_path: str = None):
        """Route SIGTERM (default) to :meth:`drain` on a helper thread —
        the orchestrator's stop signal finishes in-flight work instead
        of dropping it. Main-thread only; returns False elsewhere."""
        import signal as _signal
        sigs = signals or (_signal.SIGTERM,)
        try:
            for s in sigs:
                _signal.signal(s, lambda *_: threading.Thread(
                    target=self.drain,
                    kwargs={"timeout": timeout,
                            "snapshot_path": snapshot_path},
                    daemon=True, name="zoo-serving-drain").start())
            return True
        except ValueError:  # not the main thread
            return False

    def stop(self):
        self._stop.set()
        if self.llm_engine is not None:
            # cancels live streams and returns every KV block to the
            # free list before the door closes
            self.llm_engine.stop()
        self._server.shutdown()
        self._server.server_close()
