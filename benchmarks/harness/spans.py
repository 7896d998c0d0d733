"""Spans and counts the benchmark takes itself, around the calls into
each layer of the program.

A span is (name, start, duration) on ``time.perf_counter``; while a
profiler trace is being taken each span is also a
``jax.profiler.TraceAnnotation``, so it lies on the profiler's clock
beside the device's operations. Spans stay in memory; readers take
them when the run ends.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.counts: Dict[str, List[Tuple[float, float]]] = {}
        self.samples: Dict[str, List[Tuple[float, float]]] = {}
        self.annotate = False
        self._restore: List[Tuple[object, str, object]] = []

    # -- recording ---------------------------------------------------------
    def add_span(self, name: str, start: float, dur: float):
        with self._lock:
            self.spans.setdefault(name, []).append((start, dur))

    def add_count(self, name: str, value: float, at: Optional[float] = None):
        with self._lock:
            self.counts.setdefault(name, []).append(
                (time.perf_counter() if at is None else at, float(value)))

    def add_sample(self, name: str, value: float):
        with self._lock:
            self.samples.setdefault(name, []).append(
                (time.perf_counter(), float(value)))

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_span(name, t0, time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    # -- wrapping the program's calls --------------------------------------
    def wrap(self, obj, attr: str, name: str,
             after: Optional[Callable] = None):
        """Run ``obj.attr`` inside a span ``name`` from now on;
        ``after(args, kwargs, result, t0, t1)`` sees every call."""
        inner = getattr(obj, attr)

        @functools.wraps(inner)
        def outer(*args, **kwargs):
            t0 = time.perf_counter()
            with self.span(name):
                out = inner(*args, **kwargs)
            if after is not None:
                after(args, kwargs, out, t0, time.perf_counter())
            return out

        setattr(obj, attr, outer)
        self._restore.append((obj, attr, inner))

    def unwrap_all(self):
        for obj, attr, inner in reversed(self._restore):
            setattr(obj, attr, inner)
        self._restore.clear()

    # -- reading -----------------------------------------------------------
    def spans_in(self, name: str, t0: float, t1: float) -> List[float]:
        """Durations (s) of the spans ``name`` that started in
        [t0, t1)."""
        with self._lock:
            return [d for s, d in self.spans.get(name, ()) if t0 <= s < t1]

    def count_in(self, name: str, t0: float, t1: float) -> float:
        with self._lock:
            return sum(v for s, v in self.counts.get(name, ())
                       if t0 <= s < t1)

    def n_in(self, name: str, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for s, _ in self.counts.get(name, ())
                       if t0 <= s < t1)

    def samples_in(self, name: str, t0: float, t1: float) -> List[float]:
        with self._lock:
            return [v for s, v in self.samples.get(name, ())
                    if t0 <= s < t1]


class GaugeSampler:
    """One thread that reads some gauges of the program's metrics
    registry a few times a second (a gauge holds only its last value,
    and a mean needs readings)."""

    def __init__(self, rec: Recorder, read: Callable[[], Dict[str, float]],
                 every_s: float = 0.05):
        self._rec, self._read, self._every = rec, read, every_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-gauges")

    def _run(self):
        while not self._stop.wait(self._every):
            for name, value in self._read().items():
                self._rec.add_sample(name, value)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


class GcWatch:
    """Times every pass of the cyclic garbage collector. A pass holds
    the interpreter's lock, so every thread of the program stands still
    for as long as it lasts; each becomes a span ``host.gc<generation>``
    of the recorder when the watch stops.

    The hook keeps a list of its own and takes no lock: a pass can
    begin on a thread that is inside ``Recorder._lock`` (any allocation
    there may start one), and a hook that asked for that lock would
    wait for its own thread for ever."""

    def __init__(self, rec: Recorder):
        self._rec, self._t = rec, None
        self._passes: List[Tuple[int, float, float]] = []

    def _seen(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self._passes.append((info["generation"], self._t,
                                 time.perf_counter() - self._t))
            self._t = None

    def start(self):
        gc.callbacks.append(self._seen)
        return self

    def stop(self):
        if self._seen in gc.callbacks:
            gc.callbacks.remove(self._seen)
        passes, self._passes = self._passes, []
        for gen, t, dur in passes:
            self._rec.add_span(f"host.gc{gen}", t, dur)

    def said(self, t0: float, t1: float) -> dict:
        """Passes by generation and the time they held in [t0, t1),
        once the watch has stopped."""
        out, durs = {}, []
        for gen in (0, 1, 2):
            d = self._rec.spans_in(f"host.gc{gen}", t0, t1)
            out[f"gen{gen}"] = len(d)
            durs += d
        out["total_ms"] = sum(durs) * 1e3
        out["max_ms"] = max(durs, default=0.0) * 1e3
        return out
