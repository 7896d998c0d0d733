"""Trace spans: per-process JSONL event log + cross-host trace ids.

:func:`span` is a context manager marking one timed region::

    with span("ckpt.save", step=120):
        ...

Each span emits two JSONL records into the process's trace file — a
``B`` (begin) event and an ``E`` (end) event carrying the monotonic
duration and error flag — with a ``span`` id, its ``parent`` span id
(spans nest per thread), and the process-wide ``trace`` id. One training
step or serving request can therefore be followed across hosts: the
coordinator mints a trace id and :func:`share_trace_id` propagates it to
every process over the same JAX coordination-service KV store that
``rebalance_shards`` uses, so all hosts' trace files stitch on the
shared id.

The JSONL file is off until a sink exists: call :func:`trace_to` or set
``$ZOO_TRACE_DIR``. Two cheaper sinks are ALWAYS on, so the program
times itself with no configuration (docs/observability.md "The span
ring"):

* a process-wide bounded ring of the last ``RING_CAPACITY`` finished
  spans, ``(name, t0, dur_s, thread_id, attrs_or_None)`` with ``t0`` on
  ``time.perf_counter()`` — read it with :func:`recent_spans`; the
  flight recorder dumps its tail into every postmortem bundle and the
  benchmark's ``program_spans`` readers turn it into per-layer metrics;
* while jax is imported, ``jax.profiler.TraceAnnotation("zoo:" +
  name)`` — a no-op outside a profiler session, and inside one the span
  lies in the ``.xplane.pb`` on the profiler's clock beside the
  device's operations.

A :func:`span` with no JSONL sink costs two clock reads, one ring append
and the no-op annotation (under 3 us; ``tests/test_obs_spans.py`` holds
it under 20) — safe to leave in hot paths. :func:`watch_compiles` puts
every XLA compile and lowering into the same ring as ``jit.compile`` /
``jit.lower`` spans.

Request-scoped tracing (docs/observability.md): a serving client mints
one trace id per logical request and it rides the wire; the server
adopts it with :func:`trace_context`, so every span recorded while
handling that request — on any process of the fleet — carries the
REQUEST's trace id instead of the process-wide one, and
``zoo_tpu.obs.timeline`` joins the per-process JSONL files back into
one per-request timeline. :func:`emit_span` / :func:`emit_event` write
complete ("X") and instant ("I") events with an EXPLICIT trace id for
code that works on behalf of many requests at once (the LLM engine's
scheduler thread, the batcher) where thread-local nesting cannot apply.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import logging
import os
import socket
import sys
import threading
import time
import uuid
from typing import Deque, Dict, Iterator, List, Optional

from zoo_tpu.obs.coordination import coordination_client

__all__ = [
    "span", "trace_to", "stop_tracing", "tracing_enabled",
    "current_trace_id", "set_trace_id", "share_trace_id",
    "read_trace", "TRACE_DIR_ENV",
    "trace_context", "ambient_trace_id", "current_span_id",
    "new_trace_id", "emit_span", "emit_event", "active_spans",
    "iter_jsonl", "trace_file_path",
    "recent_spans", "ring_state", "watch_compiles", "RING_CAPACITY",
]

logger = logging.getLogger(__name__)

TRACE_DIR_ENV = "ZOO_TRACE_DIR"

_lock = threading.Lock()
_sink = None            # type: Optional[_TraceLog]
_env_checked = False
_trace_id: Optional[str] = None
_tls = threading.local()  # .stack: span-id stack per thread
#                           .trace: request trace-id override per thread
# spans begun but not yet ended, across every thread — what a crash
# flight-recorder bundle captures as "where was this process when it
# died". Only mutated while a sink exists (span() returns early when
# tracing is off), so the disabled hot path never touches it.
_live_spans: dict = {}
_live_lock = threading.Lock()

# the always-on ring: finished spans only, appended at span END (so the
# ring is ordered by end time). deque.append and next(count) are single
# C calls — no lock, no allocation beyond the tuple.
RING_CAPACITY = 65536
_ring: Deque[tuple] = collections.deque(maxlen=RING_CAPACITY)
_written = itertools.count()
_get_ident = threading.get_ident
_perf_counter = time.perf_counter
_TraceAnnotation = None     # jax.profiler.TraceAnnotation, once jax is in


def _record(name: str, t0: float, dur_s: float, attrs: Optional[dict]):
    _ring.append((name, t0, dur_s, _get_ident(), attrs))
    next(_written)


def _annotation(name: str):
    """An entered ``TraceAnnotation("zoo:" + name)``, or None while jax
    has not been imported by anyone (``obs/`` itself never imports
    it)."""
    global _TraceAnnotation
    cls = _TraceAnnotation
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None),
                      "TraceAnnotation", None)
        if cls is None:     # not imported (or mid-import on a thread)
            return None
        _TraceAnnotation = cls
    ann = cls("zoo:" + name)
    ann.__enter__()
    return ann


class _TraceLog:
    """Append-only JSONL writer for one process's trace events."""

    def __init__(self, dir_path: str):
        os.makedirs(dir_path, exist_ok=True)
        self.path = os.path.join(
            dir_path,
            f"trace-{socket.gethostname()}-{os.getpid()}.jsonl")
        self._f = open(self.path, "a", encoding="utf-8")
        self._wlock = threading.Lock()

    def write(self, event: dict):
        line = json.dumps(event, separators=(",", ":"), default=str)
        try:
            with self._wlock:
                self._f.write(line + "\n")
                self._f.flush()
        except (OSError, ValueError) as e:
            # telemetry must never fail the instrumented operation (a
            # full disk, or stop_tracing() racing a span in another
            # thread) — and an error raised from span()'s finally would
            # even MASK the operation's own exception
            logger.debug("trace write dropped: %s", e)

    def close(self):
        with self._wlock:
            try:
                self._f.close()
            except OSError:
                pass


def trace_to(dir_path: str) -> str:
    """Start writing span events under ``dir_path``; returns the trace
    file path for this process."""
    global _sink
    with _lock:
        if _sink is not None:
            _sink.close()
        _sink = _TraceLog(dir_path)
        return _sink.path


def stop_tracing():
    global _sink, _env_checked
    with _lock:
        if _sink is not None:
            _sink.close()
        _sink = None
        _env_checked = True  # an explicit stop beats the env default


def _active_sink() -> "Optional[_TraceLog]":  # zoo-lint: config-parse
    global _sink, _env_checked
    if _sink is not None:
        return _sink
    if _env_checked:
        return None
    with _lock:
        if _sink is None and not _env_checked:
            _env_checked = True
            d = os.environ.get(TRACE_DIR_ENV)
            if d:
                try:
                    _sink = _TraceLog(d)
                except OSError as e:  # bad dir must not kill the caller
                    logger.warning("cannot open trace dir %s: %s", d, e)
        return _sink


def tracing_enabled() -> bool:
    return _active_sink() is not None


def trace_file_path() -> Optional[str]:
    """This process's trace file path (None while tracing is off)."""
    sink = _active_sink()
    return sink.path if sink is not None else None


# ------------------------------------------------------------- trace ids

def new_trace_id() -> str:
    """A fresh request-scoped trace id (what a serving client mints per
    logical request before putting it on the wire)."""
    return uuid.uuid4().hex


def current_trace_id() -> str:
    """The ACTIVE trace id: the thread's adopted request trace inside a
    :func:`trace_context`, else this process's own id (minted on first
    use)."""
    tid = getattr(_tls, "trace", None)
    if tid is not None:
        return tid
    global _trace_id
    with _lock:
        if _trace_id is None:
            _trace_id = uuid.uuid4().hex
        return _trace_id


def ambient_trace_id() -> Optional[str]:
    """The thread's adopted REQUEST trace id, or None outside any
    :func:`trace_context` (never mints; the wire stamps only explicit
    request traces, not the ambient process id)."""
    return getattr(_tls, "trace", None)


def current_span_id() -> Optional[str]:
    """The innermost open span id on this thread (for parenting a
    remote child over the wire), or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


@contextlib.contextmanager
def trace_context(trace_id: Optional[str],
                  parent_span: Optional[str] = None) -> Iterator[None]:
    """Adopt ``trace_id`` for this thread: every :func:`span` inside
    carries the request's trace id (and parents under ``parent_span``,
    the caller's span id from the wire) instead of the process-wide
    trace. ``trace_id=None`` is a no-op passthrough, so wire handlers
    can wrap unconditionally."""
    if trace_id is None:
        yield
        return
    prev = getattr(_tls, "trace", None)
    _tls.trace = str(trace_id)
    st = _stack()
    pushed = parent_span is not None
    if pushed:
        st.append(str(parent_span))
    try:
        yield
    finally:
        if pushed:
            st.pop()
        _tls.trace = prev


def set_trace_id(trace_id: str):
    global _trace_id
    with _lock:
        _trace_id = str(trace_id)


_share_generation = 0
_share_gen_lock = threading.Lock()


def share_trace_id(timeout_s: float = 30.0) -> str:
    """Adopt one cluster-wide trace id (collective: call on every
    process). Process 0 publishes its trace id through the coordination
    service; everyone else blocks for it and adopts it, so all hosts'
    span events stitch into one distributed trace. Single-process: just
    returns the local id."""
    import jax

    if jax.process_count() == 1:
        return current_trace_id()
    client = coordination_client()
    if client is None:
        raise RuntimeError(
            "share_trace_id needs the JAX coordination service "
            "(jax.distributed.initialize) in multi-process mode")
    global _share_generation
    with _share_gen_lock:
        _share_generation += 1
        gen = _share_generation
    key = f"zoo:obs:trace:{gen}"
    if jax.process_index() == 0:
        client.key_value_set(key, current_trace_id())
    tid = client.blocking_key_value_get(key, int(timeout_s * 1000))
    if isinstance(tid, bytes):
        tid = tid.decode()
    set_trace_id(tid)
    return tid


# ----------------------------------------------------------------- spans

def _stack() -> List[str]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class span:
    """Timed, nested trace region: ``with span("ckpt.save", step=3) as
    sid``. Always lands in the ring and (jax imported) under a ``zoo:``
    profiler annotation; with a JSONL sink it also writes the B/E event
    pair and yields the span id (None without a sink). Exceptions
    propagate; the end event records ``ok: false``. Attributes known
    only at the end are added with :meth:`note` before the block
    exits."""

    __slots__ = ("name", "attrs", "_t0", "_ann", "_sink", "_ev")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs or None
        self._sink = None

    def note(self, **attrs):
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self) -> Optional[str]:
        sid = None
        sink = _active_sink()
        if sink is not None:
            sid = self._begin(sink)
        self._ann = _annotation(self.name)
        self._t0 = _perf_counter()
        return sid

    def __exit__(self, exc_type, exc, tb):
        t0 = self._t0
        dur = _perf_counter() - t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _record(self.name, t0, dur, self.attrs)
        if self._sink is not None:
            self._end(dur, exc_type is None)
        return False

    # -- the JSONL sink (only with a trace dir) ----------------------------
    def _begin(self, sink: "_TraceLog") -> str:
        sid = uuid.uuid4().hex[:16]
        st = _stack()
        ev = {"ev": "B", "name": self.name, "trace": current_trace_id(),
              "span": sid, "parent": st[-1] if st else None,
              "pid": os.getpid(), "ts": time.time()}
        if self.attrs:
            ev["attrs"] = dict(self.attrs)
        sink.write(ev)
        with _live_lock:
            _live_spans[sid] = ev
        st.append(sid)
        self._sink, self._ev = sink, ev
        return sid

    def _end(self, dur: float, ok: bool):
        sink, ev = self._sink, self._ev
        self._sink = None
        _stack().pop()
        with _live_lock:
            _live_spans.pop(ev["span"], None)
        sink.write({"ev": "E", "name": self.name, "trace": ev["trace"],
                    "span": ev["span"], "ts": time.time(),
                    "dur_s": dur, "ok": ok})


def active_spans() -> List[dict]:
    """Begin events of every span currently OPEN in this process (any
    thread) — the "where was it" a flight-recorder bundle captures."""
    with _live_lock:
        return list(_live_spans.values())


def emit_span(name: str, ts: float, dur_s: float,
              trace: Optional[str] = None,
              parent: Optional[str] = None, ok: bool = True,
              span_id: Optional[str] = None,
              t0: Optional[float] = None,
              **attrs) -> Optional[str]:
    """Write one COMPLETE ("X") span event: started at wall ``ts``,
    lasted ``dur_s``. For recorders that time a region themselves on
    behalf of a specific request (the engine's scheduler working a
    stream, a client attempt thread) where a nested :func:`span` cannot
    carry the right identity. ``t0`` is the same start on
    ``time.perf_counter()``: with it the span also lands in the ring
    (a call without it is JSONL-only). ``trace=None`` falls back to
    the active trace id. Returns the span id (None without a sink)."""
    if t0 is not None:
        _record(name, t0, float(dur_s), attrs or None)
    sink = _active_sink()
    if sink is None:
        return None
    sid = span_id if span_id is not None else uuid.uuid4().hex[:16]
    ev = {"ev": "X", "name": name,
          "trace": trace if trace is not None else current_trace_id(),
          "span": sid, "parent": parent, "pid": os.getpid(),
          "ts": ts, "dur_s": float(dur_s), "ok": bool(ok)}
    if attrs:
        ev["attrs"] = attrs
    sink.write(ev)
    return sid


def emit_event(name: str, trace: Optional[str] = None,
               parent: Optional[str] = None, **attrs) -> Optional[str]:
    """Write one INSTANT ("I") event (admission, preemption, a shed —
    things with a moment but no duration). Same identity rules as
    :func:`emit_span`."""
    sink = _active_sink()
    if sink is None:
        return None
    sid = uuid.uuid4().hex[:16]
    ev = {"ev": "I", "name": name,
          "trace": trace if trace is not None else current_trace_id(),
          "span": sid, "parent": parent, "pid": os.getpid(),
          "ts": time.time()}
    if attrs:
        ev["attrs"] = attrs
    sink.write(ev)
    return sid


# ------------------------------------------------------------- the ring

def recent_spans(name: Optional[str] = None, since: Optional[float] = None,
                 until: Optional[float] = None) -> List[tuple]:
    """The ring's spans ``(name, t0, dur_s, thread_id, attrs_or_None)``
    whose START lies in ``[since, until)`` (``perf_counter``), oldest
    end first; ``name`` keeps one span name. Check :func:`ring_state`
    before trusting a window: spans older than the ring's reach are
    simply gone."""
    out = list(_ring)       # one C call: a consistent copy under the GIL
    if name is not None:
        out = [r for r in out if r[0] == name]
    if since is not None:
        out = [r for r in out if r[1] >= since]
    if until is not None:
        out = [r for r in out if r[1] < until]
    return out


def ring_state() -> Dict[str, object]:
    """``capacity``, ``written`` (spans ever recorded) and ``oldest_t0``
    (start of the oldest span still held; None when empty). The ring
    has dropped spans iff ``written > capacity``; what it dropped ENDED
    before the oldest held span did."""
    try:
        oldest = _ring[0][1]
    except IndexError:
        oldest = None
    return {"capacity": RING_CAPACITY,
            # count's repr ("count(41)") is the one read of its value
            # that neither advances it nor is deprecated (copy/pickle)
            "written": int(repr(_written)[6:-1]),
            "oldest_t0": oldest}


_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/core/compile/jaxpr_trace_duration": "jit.lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
}
_compile_watch_lock = threading.Lock()
_compile_watched = False


def watch_compiles() -> bool:
    """Idempotent: from now on every XLA backend compile (or persistent-
    cache load) is a ring span ``jit.compile`` and every trace/lowering
    a ``jit.lower``, each with attr ``fun`` (``jit(epoch_fn)``) and
    ``t0 = now - duration``; compiles also bump
    ``zoo_jit_compiles_total`` / ``zoo_jit_compile_seconds_total``.
    "Which step recompiled, and what did it cost" for serving and
    training alike. Does nothing (and may be called again) until jax
    has been imported: a jax-free process has nothing to compile."""
    global _compile_watched
    with _compile_watch_lock:
        if _compile_watched or "jax" not in sys.modules:
            return False
        import jax.monitoring
        from zoo_tpu.obs.metrics import counter
        compiles = counter(
            "zoo_jit_compiles_total",
            "XLA backend compiles (or persistent-cache loads) this "
            "process ran")
        seconds = counter(
            "zoo_jit_compile_seconds_total",
            "Wall seconds spent in XLA backend compiles / cache loads")

        def on_duration(event: str, duration: float, **kwargs):
            name = _COMPILE_EVENTS.get(event)
            if name is None:
                return
            _record(name, _perf_counter() - duration, float(duration),
                    {"fun": kwargs.get("fun_name")})
            if name == "jit.compile":
                compiles.inc()
                seconds.inc(float(duration))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _compile_watched = True
        return True


def iter_jsonl(path: str) -> Iterator[dict]:
    """Yield every parseable JSON object from a JSONL file, skipping
    torn or truncated lines. A crash mid-write is an EXPECTED event for
    trace files and flight-recorder spills (a SIGKILL can land between
    any two bytes), so a half-written tail, an interleaved torn line,
    or invalid UTF-8 from a partial flush must never take the readable
    prefix down with it. A missing/unreadable file yields nothing."""
    try:
        f = open(path, encoding="utf-8", errors="replace")
    except OSError:
        return
    with f:
        while True:
            try:
                line = f.readline()
            except (OSError, ValueError):
                return  # unreadable remainder: keep what we have
            if not line:
                return
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue  # torn write: skip, keep the rest
            if isinstance(obj, dict):
                yield obj


def read_trace(dir_path: str) -> List[dict]:
    """Load every span event under ``dir_path`` (all hosts' files),
    sorted by wall timestamp — the offline-analysis read-back. Torn or
    truncated lines (a replica killed mid-write) are skipped, never
    raised."""
    events: List[dict] = []
    if not os.path.isdir(dir_path):
        return events
    for fname in sorted(os.listdir(dir_path)):
        if not (fname.startswith("trace-") and fname.endswith(".jsonl")):
            continue
        events.extend(iter_jsonl(os.path.join(dir_path, fname)))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events
