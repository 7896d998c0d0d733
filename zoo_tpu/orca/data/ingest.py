"""Staged async ingest: overlap fetch / decode / device placement.

The transport gap measured in BENCH_r05 — NCF at 5.07M samples/s
device-side but 1.91M end-to-end — is serial plumbing, not bandwidth:
each leg of the ingest path (network fetch, host decode/slice,
``jax.device_put``) waited for the previous one. This module chains the
legs into a pipeline of :class:`~zoo_tpu.orca.data.cache.
DoubleBufferedIterator` stages, one daemon thread per stage, so stage
``i`` of item ``k`` runs while stage ``i-1`` prepares item ``k+1`` —
device transfer of shard *k* overlaps the network fetch of shard *k+1*,
the same overlap the reference gets from Spark's prefetching iterators
feeding BigDL's per-executor miniBatch queues.

Every stage records its busy time into the
``zoo_shard_pipeline_stage_seconds{stage=...}`` histogram, and a
:class:`PipelineStats` passed to :func:`staged_pipeline` accumulates
per-stage busy seconds so callers
(``scripts/check_data_plane.py``) can report the **overlap ratio** —
total stage-busy seconds divided by pipeline wall time; 1.0 means the
stages ran back-to-back serially, above 1.0 means real overlap.

Used by:

* :func:`zoo_tpu.orca.data.plane.rebalance_shards` (``stage_fn=`` —
  device placement streams behind the shard exchange);
* the estimator feed (``pipeline/api/keras/engine/topology.py``): the
  host-fed superbatch path splits its old slice+put staging thread into
  a slice stage and a device-put stage, so ``fit`` steps on batch ``k``
  while batch ``k+1`` transfers and batch ``k+2`` is sliced.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from zoo_tpu.obs.metrics import gauge, histogram
from zoo_tpu.orca.data.cache import DoubleBufferedIterator

__all__ = ["PipelineStats", "StagedPipeline", "staged_pipeline",
           "async_device_ingest", "ReadaheadController",
           "StagingBufferPool"]

logger = logging.getLogger(__name__)

_stage_seconds = histogram(
    "zoo_shard_pipeline_stage_seconds",
    "Busy time per ingest pipeline stage (fetch / decode / slice / "
    "device put)", labels=("stage",))


class PipelineStats:
    """Per-stage busy-seconds accumulator + wall clock for one pipeline.

    ``overlap_ratio()`` = sum of stage busy time / wall time since the
    pipeline started. A perfectly serial pipeline scores ~1.0; each
    fully-hidden stage adds ~its share above that. Thread-safe — stages
    record from their own daemon threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.busy: Dict[str, float] = {}
        self.items: Dict[str, int] = {}
        self._t0 = time.perf_counter()
        self._t_end: Optional[float] = None

    def record(self, stage: str, dt: float):
        with self._lock:
            self.busy[stage] = self.busy.get(stage, 0.0) + dt
            self.items[stage] = self.items.get(stage, 0) + 1

    def finish(self):
        """Pin the wall clock (called when the pipeline is exhausted or
        closed; idempotent — first call wins)."""
        if self._t_end is None:
            self._t_end = time.perf_counter()

    def wall(self) -> float:
        return (self._t_end or time.perf_counter()) - self._t0

    def busy_total(self) -> float:
        with self._lock:
            return sum(self.busy.values())

    def overlap_ratio(self) -> float:
        wall = self.wall()
        if wall <= 0:
            return float("nan")
        return self.busy_total() / wall


def _timed_source(source: Iterable[Any],
                  stats: Optional[PipelineStats]):
    """Record the time spent blocked on the raw source's ``next()`` as
    the ``source`` stage (the network-fetch leg when the source is a
    streaming fetch generator) — without it the overlap ratio would
    miss the very leg the pipeline exists to hide."""
    it = iter(source)
    child = _stage_seconds.labels(stage="source")
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        dt = time.perf_counter() - t0
        child.observe(dt)
        if stats is not None:
            stats.record("source", dt)
        yield item


class StagedPipeline:
    """A chain of double-buffered stages over ``source``.

    Iterating yields fully-staged items; ``close()`` (or exiting the
    context manager) stops every stage thread, outermost first, so an
    early-exiting consumer cannot strand a producer pinning staged
    device buffers."""

    def __init__(self, source: Iterable[Any],
                 stages: List[Tuple[str, Optional[Callable[[Any], Any]]]],
                 depth: int = 2, stats: Optional[PipelineStats] = None):
        self.stats = stats
        self._iters: List[DoubleBufferedIterator] = []
        it: Iterable[Any] = _timed_source(source, self.stats)
        for name, fn in stages:
            it = DoubleBufferedIterator(it,
                                        stage_fn=self._timed(name, fn),
                                        depth=depth)
            self._iters.append(it)
        self._tail = it

    def _timed(self, name: str, fn: Optional[Callable[[Any], Any]]):
        stats = self.stats
        child = _stage_seconds.labels(stage=name)

        def run(item):
            t0 = time.perf_counter()
            out = fn(item) if fn is not None else item
            dt = time.perf_counter() - t0
            child.observe(dt)
            if stats is not None:
                stats.record(name, dt)
            return out

        return run

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._tail)
        except StopIteration:
            if self.stats is not None:
                self.stats.finish()
            raise

    def close(self):
        # outermost first: stop consumers before their producers so the
        # inner close never races a stage thread mid-put
        for it in reversed(self._iters):
            it.close()
        if self.stats is not None:
            self.stats.finish()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def staged_pipeline(source: Iterable[Any],
                    stages: List[Tuple[str,
                                       Optional[Callable[[Any], Any]]]],
                    depth: int = 2,
                    stats: Optional[PipelineStats] = None
                    ) -> StagedPipeline:
    """Chain ``stages`` = [(name, fn-or-None), ...] over ``source``.

    Each stage gets its own staging thread and a bounded queue of
    ``depth`` in-flight items. A stage with ``fn=None`` is a pure
    prefetch stage — useful to give a slow *source* (a network fetch
    generator) its own thread so downstream stages overlap it."""
    return StagedPipeline(source, stages, depth=depth, stats=stats)


_readahead_gauge = gauge(
    "zoo_shard_readahead",
    "Live readahead knob values chosen by the adaptive controller",
    labels=("knob",))


class ReadaheadController:
    """Close the loop between :class:`PipelineStats` and the fetch
    knobs: grow/shrink ``config.concurrency`` and ``config.multiget``
    toward the point where the fetch leg fully hides under
    decode + device placement.

    The signal is the *window share* of the ``source`` stage (the
    network-fetch leg) in pipeline wall time since the last decision —
    deltas, not cumulative totals, so late-exchange behavior is not
    damped by early-exchange history:

    * share > ``high`` — the pipeline is starving on fetch. Double the
      fetch concurrency first (parallelism is the cheap lever), then
      the multi-get chunk (fewer round trips per byte, at the cost of
      coarser retry granularity).
    * share < ``low`` — fetch is already fully hidden with room to
      spare: step concurrency back down one worker. Narrower readahead
      means fewer staged shards pinned in host memory, and the
      asymmetric walk (×2 up, −1 down) keeps the controller from
      oscillating.

    ``config`` is the single mutation point (`ExchangeConfig`; env
    parsed once at its construction): :func:`~zoo_tpu.orca.data.plane.
    iter_fetch` re-reads it when carving each chunk, so decisions take
    effect mid-exchange without tearing anything down. Thread-safe —
    ``on_chunk`` is called from fetch worker threads. The decision
    trail is kept on ``decisions`` (and exported through the
    ``zoo_shard_readahead`` gauge) so benches report what the
    controller actually did rather than asserting it."""

    def __init__(self, config, stats: Optional[PipelineStats] = None,
                 min_chunk: int = 4, max_chunk: int = 256,
                 min_concurrency: int = 1, max_concurrency: int = 32,
                 window: int = 4, high: float = 0.55, low: float = 0.25):
        self.config = config
        self.stats = stats
        self.min_chunk, self.max_chunk = min_chunk, max_chunk
        self.min_concurrency = min_concurrency
        self.max_concurrency = max_concurrency
        self.window = max(1, window)
        self.high, self.low = high, low
        self.decisions: List[Tuple[int, int]] = []
        self._lock = threading.Lock()
        self._chunks = 0
        self._last_wall = 0.0
        self._last_src = 0.0

    def on_chunk(self, ngids: int, nbytes: int, seconds: float):
        with self._lock:
            self._chunks += 1
            if self._chunks % self.window:
                return
            self._decide()

    def _decide(self):
        st = self.stats
        if st is None:
            return
        wall = st.wall()
        src = st.busy.get("source", 0.0)
        dw = wall - self._last_wall
        ds = src - self._last_src
        if dw <= 0:
            return
        self._last_wall, self._last_src = wall, src
        share = ds / dw
        cfg = self.config
        if share > self.high:
            if cfg.concurrency < self.max_concurrency:
                cfg.concurrency = min(self.max_concurrency,
                                      cfg.concurrency * 2)
            elif cfg.multiget < self.max_chunk:
                cfg.multiget = min(self.max_chunk, cfg.multiget * 2)
            else:
                return
        elif share < self.low:
            # unwind in reverse order of growth: width first, then the
            # chunk back toward its floor (fine retry granularity costs
            # nothing once fetch is fully hidden)
            if cfg.concurrency > self.min_concurrency:
                cfg.concurrency -= 1
            elif cfg.multiget > self.min_chunk:
                cfg.multiget = max(self.min_chunk, cfg.multiget // 2)
            else:
                return
        else:
            return
        self.decisions.append((cfg.concurrency, cfg.multiget))
        _readahead_gauge.labels(knob="concurrency").set(cfg.concurrency)
        _readahead_gauge.labels(knob="multiget").set(cfg.multiget)
        logger.debug("readahead: source share %.2f -> concurrency=%d "
                     "multiget=%d", share, cfg.concurrency, cfg.multiget)


# ------------------------------------------------- staged host buffers


def _misaligned_empty(shape, dtype) -> np.ndarray:
    """Host buffer whose data pointer is deliberately NOT 16-byte
    aligned (addr % 16 == 8). XLA:CPU's zero-copy ``device_put`` fast
    path only engages for suitably aligned host buffers (16- or 64-byte
    depending on version), and whether a given numpy allocation lands
    aligned is allocator luck — "does device_put copy?" is a property
    of the ALLOCATION, not the backend. Staging buffers must always be
    copied (an aliased buffer's reuse would mutate the device value),
    so make the property deterministic: an 8-mod-16 address never
    qualifies for zero-copy yet satisfies every real dtype's (<=8-byte)
    alignment."""
    dt = np.dtype(dtype)
    count = 1
    for s in shape:
        count *= int(s)
    nbytes = count * dt.itemsize
    if dt.itemsize > 8 or not nbytes:
        return np.empty(shape, dt)  # exotic/empty: the probe decides
    raw = np.empty(nbytes + 16, np.uint8)
    off = (8 - raw.ctypes.data % 16) % 16
    return raw[off:off + nbytes].view(dt).reshape(shape)


def _buffer_aliased_on_device(buf: np.ndarray) -> bool:
    """Directly test whether ``jax.device_put`` aliases THIS buffer's
    memory: put a head view, mutate the host bytes, read the device
    value back. The zero-copy decision keys on the data pointer, so
    the head answers for the whole buffer — a per-buffer test, because
    a process-global probe of one throwaway array provably flips with
    that array's own (random) alignment."""
    if not buf.size:
        return False
    import jax
    head = buf.reshape(-1).view(np.uint8)[:16]
    head[0] = 0
    dev = jax.device_put(head)
    jax.block_until_ready(dev)
    head[0] = 255
    return int(np.asarray(dev)[0]) == 255


class StagingBufferPool:
    """Rotating preallocated host staging buffers for the host-fed
    superbatch feed — the double-buffered ``device_put`` leg of the
    ingest path.

    Without it, every superbatch slice allocates fresh host arrays
    (allocator churn + cold pages on the DMA path). With it, the slice
    stage writes each superbatch into one of ``nbufs`` preallocated
    buffers via ``np.take(..., out=...)``, and the put stage returns
    the buffer to the pool only after ``block_until_ready`` confirms
    the host→device transfer read it — so the DMA of batch *k* safely
    overlaps the slicing of batch *k+1* into a different buffer.

    FIFO discipline: the pipeline's stages hand items over in order
    (one slice thread, one put thread), so ``recycle()`` frees the
    oldest outstanding buffer with no per-item bookkeeping. ``nbufs``
    must exceed the pipeline's maximum in-flight items (slice holds 1,
    each stage queue holds ``depth``, put holds 1 → 3 at depth 1;
    default 4 leaves margin).

    Safety: a reused buffer must never be aliased by ``device_put``
    (XLA:CPU zero-copies suitably aligned host arrays — recycling an
    aliased buffer would mutate the live device value). Buffers are
    therefore allocated OFF the zero-copy alignment
    (:func:`_misaligned_empty`) and ``maybe_create`` additionally
    probes each one (:func:`_buffer_aliased_on_device`), returning
    ``None`` — plain slicing — if any still aliases. The
    ``ZOO_FEED_STAGING`` env kill switch forces ``None`` outright.
    """

    def __init__(self, arrs, rows: int, nbufs: int = 4):
        self._slots = [[_misaligned_empty((rows,) + a.shape[1:], a.dtype)
                        for a in arrs] for _ in range(nbufs)]
        self._free: "queue.Queue" = queue.Queue()
        for i in range(nbufs):
            self._free.put(i)
        self._inflight: List[int] = []
        self._lock = threading.Lock()
        self._gen = 0
        self.rows = rows

    @staticmethod
    def maybe_create(arrs, rows: int, nbufs: int = 4,  # zoo-lint: config-parse
                     max_bytes: int = 2 << 30) -> Optional[
                         "StagingBufferPool"]:
        mode = os.environ.get("ZOO_FEED_STAGING", "auto").lower()
        if mode in ("0", "off"):
            return None
        if rows <= 0 or not arrs:
            return None
        if any(not isinstance(a, np.ndarray) or a.dtype.hasobject
               for a in arrs):
            return None
        row_bytes = sum(a[:1].nbytes for a in arrs)
        if row_bytes * rows * nbufs > max_bytes:
            return None  # the pool would dwarf the dataset's own copies
        pool = StagingBufferPool(arrs, rows, nbufs=nbufs)
        # every _misaligned_empty buffer shares the same deterministic
        # 8-mod-16 alignment, so ONE probe answers for all of them —
        # per-buffer probes are only needed for the np.empty fallback
        # (itemsize > 8), whose alignment genuinely is allocator luck.
        # Each probe is a blocking device round trip; probing all
        # nbufs x n_arrays buffers would tax every fit() start.
        to_probe, probed_misaligned = [], False
        for slot in pool._slots:
            for b in slot:
                if b.dtype.itemsize > 8:
                    to_probe.append(b)
                elif not probed_misaligned and b.size:
                    probed_misaligned = True
                    to_probe.append(b)
        try:
            aliased = any(_buffer_aliased_on_device(b) for b in to_probe)
        except Exception as e:  # noqa: BLE001 — the feed must start
            # either way, but a probe that cannot run on this backend
            # is something to see, not to swallow
            logger.warning("staging buffers disabled: the alias probe "
                           "failed on this backend: %r", e)
            return None
        if aliased:
            logger.info("staging buffers disabled: jax.device_put "
                        "aliases a staging buffer on this backend")
            return None
        return pool

    def take(self, arrs, idx, gen: Optional[int] = None,
             timeout: float = 30.0) -> List[np.ndarray]:
        """Slice ``arrs[i][idx]`` into the next free buffer; returns
        views sized to ``len(idx)`` (the ragged-tail superbatch just
        uses a prefix of the buffer).

        ``gen`` is the generation token :meth:`reset` returned. A call
        carrying a superseded token gets plain freshly-allocated slices
        and never touches the pool — the caller is a zombie stage
        thread from a torn-down pipeline (``DoubleBufferedIterator.
        close()`` does not join), and letting it occupy a slot would
        hand the NEW pipeline's buffers to output nobody consumes."""
        with self._lock:
            superseded = gen is not None and gen != self._gen
        idx = np.asarray(idx)
        if superseded:
            return [a[idx] for a in arrs]
        try:
            slot = self._free.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(
                f"staging buffer pool starved for {timeout:g}s — the "
                "device_put stage stopped recycling (stuck transfer?)"
            ) from None
        n = len(idx)
        out = []
        for a, buf in zip(arrs, self._slots[slot]):
            view = buf[:n]
            np.take(a, idx, axis=0, out=view)
            out.append(view)
        with self._lock:
            if gen is not None and gen != self._gen:
                # reset() ran while we held the slot: hand it straight
                # back so the new generation keeps full capacity, and
                # give the zombie caller throwaway copies instead of
                # views into a slot the new pipeline may now be filling
                self._free.put(slot)
                return [a[idx] for a in arrs]
            self._inflight.append(slot)
        return out

    def recycle(self, gen: Optional[int] = None):
        """The oldest outstanding buffer's transfer is complete: make
        it available to the slice stage again. A superseded ``gen``
        token is a no-op — a zombie device_put thread finishing after
        :meth:`reset` must not free the new generation's oldest
        in-flight slot mid-DMA."""
        with self._lock:
            if gen is not None and gen != self._gen:
                return
            slot = self._inflight.pop(0) if self._inflight else None
        if slot is not None:
            self._free.put(slot)

    def reset(self) -> int:
        """Free every outstanding buffer and start a new generation
        (epoch boundary / after a pipeline teardown mid-epoch).
        Returns the new generation token; stage closures pass it back
        to :meth:`take`/:meth:`recycle` so threads surviving a
        non-joining teardown are fenced off from the new epoch's
        slots."""
        with self._lock:
            stale, self._inflight = self._inflight, []
            self._gen += 1
            gen = self._gen
        for slot in stale:
            self._free.put(slot)
        return gen


def async_device_ingest(shards: Iterable[Any], put_fn=None,
                        depth: int = 2,
                        stats: Optional[PipelineStats] = None
                        ) -> StagedPipeline:
    """Iterate ``shards`` with device placement running one item ahead.

    ``put_fn`` defaults to ``jax.device_put`` (applied to the whole
    shard pytree). The source iterable is drained on a prefetch thread
    and placement happens on a second stage thread, so the consumer's
    compute, the device transfer, and the source's own work (e.g. a
    streaming shard fetch) all overlap."""
    if put_fn is None:
        import jax
        put_fn = jax.device_put
    return staged_pipeline(iter(shards),
                           [("fetch", None), ("device_put", put_fn)],
                           depth=depth, stats=stats)
