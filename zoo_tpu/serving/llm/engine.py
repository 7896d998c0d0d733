"""Iteration-level (continuous) batching engine for autoregressive
decode.

The scheduler the PR 7 tentpole was named after: instead of forming a
batch of requests and draining it to completion (request-level batching
— every finished sequence idles its seat until the slowest member
ends), the engine re-schedules **every decode iteration**:
finished/expired/aborted streams free their slot and KV blocks, waiting
requests are admitted into free slots the same tick, and the ONE
fixed-shape decode executable runs over whatever mix of old and new
sequences the slots hold (Orca's in-flight batching, OSDI '22).

This revision rebuilds the tick itself around the device:

* **Overlapped tick pipeline** (the engine's one loop) — the
  scheduler never blocks on a tick's result. Tick N+1's input tokens
  are tick N's ON-DEVICE output batch (``model.decode_step`` chains
  them without a host round trip; freshly admitted slots override
  their lane with the prefill token via a host mask), so the scheduler
  runs sweep/admit/grow-or-preempt for the next tick while the device
  executes the current one, and a dedicated readback thread streams
  each finished batch out to subscribers. At most two ticks are in
  flight; every dispatched lane carries a ``(slot, handle, epoch)``
  snapshot, and a lane whose slot was re-assigned (finish, expiry,
  preemption) between dispatch and readback is discarded on arrival —
  sampling is a pure function of (seed, token index), so any token a
  discard loses is re-drawn bit-identically after the resume. Deadline
  enforcement (every scheduler pass) and youngest-first preemption are
  unchanged, and the decode executable census stays at exactly 1.
* **Chunked prefill** (``ZOO_LLM_PREFILL_CHUNK``) — prompts are fed in
  fixed-size chunks, at most one prefill budget per tick, interleaved
  with decode, so a long prompt no longer freezes every live stream
  for its whole prefill. A mid-prefill slot simply doesn't decode yet.
* **Per-stream sampling** — temperature/top-k/top-p/seed ride the
  stream (env defaults via ``ZOO_LLM_SAMPLING``), are applied on
  device through per-slot parameter lanes, and the per-sequence PRNG
  seed is checkpointed in the sequence's block-table entry
  (:meth:`BlockAllocator.set_aux`) so preempt-resume and failover
  replay the same draws.
* **Speculative decoding** (``ZOO_LLM_SPEC_K`` / model ``spec_k``) —
  the n-gram prompt-lookup drafter proposes up to k continuation
  tokens per stream and ONE fixed-shape ``slots x (k+1)`` VERIFY
  executable scores them all in a single device pass; the engine
  emits the longest accepted prefix plus the model's own next token.
  Every emitted token is the canonical per-position sample (same
  stateless PRNG key plain decode would use), so speculative streams
  are byte-identical to non-speculative ones — greedy and seeded —
  and rejection is a pure length reset (rejected rows' cache writes
  are position-masked garbage the next append overwrites). Draft
  spans are funded from the free list only (never by preempting
  another stream); deadlines, preemption, prefix caching, int8 KV,
  and the overlap pipeline compose unchanged (verify batches are
  host-fed and gate per seat — the accept length decides the next
  base position).

PR 5's serving semantics apply per stream: a propagated
:class:`Deadline` is checked at submission (dead-on-arrival), at
admission, and every scheduler pass (mid-stream expiry frees the slot
immediately); the waiting queue is bounded (overload sheds at the door
with ``retryable``); a duplicate request id joins the live stream
instead of decoding twice. Admission is additionally gated on the KV
free list — a request only enters a slot when its prompt's blocks plus
one decode block exist (:meth:`BlockAllocator.can_admit`).

When a RUNNING sequence needs its next block and the pool is dry, the
youngest-admitted victim is **preempted**: blocks freed, stream pushed
back to the head of the waiting queue, and (because decode — greedy or
seeded — is deterministic) re-prefilled later from prompt+generated
with no client-visible artifact beyond latency.

The model behind the engine is any adapter with the
:class:`~zoo_tpu.serving.llm.model.PagedLlamaModel` surface
(``prefill`` / ``decode_step`` / ``read_tokens`` / shape attrs), so
scheduler tests run against a pure-python fake without importing jax.
The scheduler thread runs :meth:`LLMEngine._pass` and hands what it
dispatched to the readback thread's :meth:`LLMEngine._land`; a test
that wants a deterministic schedule calls the same two on its own
thread, engine not started (``tests/llm_tick.py``).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import queue as _queue
import threading
import time
import zlib
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from zoo_tpu.obs.flight import record_event
from zoo_tpu.obs.metrics import counter, gauge, histogram
from zoo_tpu.obs.tracing import (
    emit_event,
    emit_span,
    span,
    watch_compiles,
)
from zoo_tpu.serving.llm.kv_cache import (
    BlockAllocator,
    prefix_block_hashes,
)
from zoo_tpu.serving.llm.speculative import PromptLookup, accept_length
from zoo_tpu.serving.tenancy import registry as tenant_registry
from zoo_tpu.common.knobs import value as knob_value
from zoo_tpu.util.resilience import Deadline, env_int

_tokens = counter(
    "zoo_llm_tokens_total", "Tokens processed by the LLM engine "
    "(prefill = prompt tokens, decode = generated tokens)",
    labels=("kind",))
_steps = counter(
    "zoo_llm_decode_steps_total",
    "Fixed-shape decode iterations executed")
_ttft = histogram(
    "zoo_llm_ttft_seconds",
    "Time from stream submission to its first generated token")
# per-stream token-cadence families (docs/observability.md): tick-phase
# timing says how busy the ENGINE is; these say what each REQUEST
# experienced — the p99s the SLO watchdog burns against
_inter_token = histogram(
    "zoo_llm_inter_token_seconds",
    "Gap between consecutive generated tokens of one stream, as "
    "observed at the engine's readback (what a streaming client feels "
    "between frames)")
_stream_ttft = histogram(
    "zoo_llm_stream_ttft_seconds",
    "Per-stream time-to-first-token by final outcome (streams that "
    "never produced a token observe their full lifetime under their "
    "terminal outcome)", labels=("outcome",))
_occupancy = gauge(
    "zoo_llm_slot_occupancy",
    "Decode slots holding a live sequence right now")
_waiting = gauge(
    "zoo_llm_waiting_streams", "Streams queued behind admission "
    "(no free slot or no free KV blocks)")
_preempts = counter(
    "zoo_llm_preempt_total",
    "Running streams evicted to free KV blocks (re-queued, resumed by "
    "re-prefill)")
_streams = counter(
    "zoo_llm_streams_total", "Finished streams by outcome "
    "(ok / expired / cancelled / error)", labels=("outcome",))
_dedup = counter(
    "zoo_llm_stream_dedup_total",
    "Duplicate stream ids joined to an existing stream instead of "
    "decoding twice")
# tick-pipeline families (docs/llm_serving.md): where each engine tick
# spends its time, and how much of the wall clock the device is busy —
# the overlap the async pipeline exists to create
_tick_seconds = histogram(
    "zoo_llm_tick_seconds",
    "Per-phase engine tick latency (schedule = sweep/admit/grow host "
    "work, prefill = prompt chunk executions, decode = dispatch-to-"
    "ready device time, readback = applying a ready batch to streams)",
    labels=("phase",))
_overlap_ratio = gauge(
    "zoo_llm_tick_overlap_ratio",
    "Device-busy time / wall time over the recent decode window (1.0 "
    "= the scheduler never leaves the device idle)")
# prefix-cache families (docs/llm_serving.md): prompt tokens whose KV
# was reused from a cached prefix vs computed fresh, and the HBM cost
# of one cached token under the active cache dtype
_prefix_hits = counter(
    "zoo_llm_prefix_cache_hit_tokens_total",
    "Prompt tokens admitted onto CACHED prefix blocks (prefill skipped "
    "straight past them)")
_prefix_misses = counter(
    "zoo_llm_prefix_cache_miss_tokens_total",
    "Prompt tokens prefilled fresh while prefix caching was enabled")
_kv_bytes_per_token = gauge(
    "zoo_llm_kv_bytes_per_token",
    "HBM bytes one cached token costs (K+V rows across layers, plus "
    "int8 scale rows) under the engine model's KV cache dtype")
_weight_bytes = gauge(
    "zoo_llm_weight_bytes",
    "HBM bytes the engine model's resident weight tree holds (its dot "
    "weights are bf16 on a TPU: docs/llm_serving.md, Weights)")
# speculative-decoding families (docs/llm_serving.md): how many tokens
# the drafter proposed, how many the verify pass accepted (the
# amortization the feature exists for), the per-pass accept-length
# distribution, and how often the drafter had anything to propose
_spec_proposed = counter(
    "zoo_llm_spec_proposed_tokens_total",
    "Draft tokens proposed by the n-gram prompt-lookup drafter and "
    "scored by a verify pass")
_spec_accepted = counter(
    "zoo_llm_spec_accepted_tokens_total",
    "Draft tokens accepted by the verify pass (each one is a decoded "
    "token that cost no extra HBM pass)")
_spec_accept_len = histogram(
    "zoo_llm_spec_accept_len",
    "Accepted-prefix length per verify pass with a non-empty draft "
    "(0 = the first draft token already mismatched)",
    buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0))
_spec_hit_rate = gauge(
    "zoo_llm_spec_draft_hit_rate",
    "Fraction of decode lanes the prompt-lookup drafter produced at "
    "least one proposal for (cumulative, republished from "
    "engine.stats())")
# multitenancy families (docs/multitenancy.md): per-tenant admission,
# shedding, preemption, and live resource occupancy — the isolation
# the QoS layer exists to make observable
_tenant_admitted = counter(
    "zoo_tenant_admitted_total",
    "Requests admitted past the tenant token bucket, per tenant",
    labels=("tenant",))
_tenant_shed = counter(
    "zoo_tenant_shed_total",
    "Requests shed per tenant and reason (rate = the tenant's own "
    "token bucket ran dry, queue_full = the shared waiting queue was "
    "at bound, slots/kv = per-tenant quota)", labels=("tenant", "reason"))
_tenant_preempted = counter(
    "zoo_tenant_preempted_total",
    "Streams preempted per OWNING tenant and reason (kv = pool "
    "pressure, class = displaced by a higher-priority tenant)",
    labels=("tenant", "reason"))
_tenant_kv = gauge(
    "zoo_tenant_kv_blocks",
    "Live KV blocks owned per tenant partition",
    labels=("tenant",))
_tenant_slots = gauge(
    "zoo_tenant_decode_slots",
    "Decode slots held per tenant right now", labels=("tenant",))

#: The scheduler thread's LEAF spans (docs/observability.md "The span
#: catalogue"): disjoint, and together they cover one pass of the loop,
#: so a device-idle gap under the scheduler always has one name. The
#: ``llm.model.*`` spans nest inside ``prefill`` / ``dispatch``; the
#: two ``llm.readback.*`` spans are the readback thread's.
TICK_LEAF_SPANS = (
    "llm.tick.lock_wait", "llm.tick.sweep_admit", "llm.tick.prefill",
    "llm.tick.grow_build", "llm.tick.inflight_wait", "llm.tick.dispatch",
    "llm.tick.idle", "llm.tick.reseed",
    "llm.readback.device", "llm.readback.apply",
)


class AdmissionError(RuntimeError):
    """Retryable door rejection (waiting queue full, or the tenant's
    admission bucket ran dry); mirrors the predict path's shed
    contract. ``retry_after_ms`` is computed from the SHEDDING
    tenant's own bucket refill when tenancy is on — one tenant's
    flood never inflates another tenant's hint."""

    def __init__(self, msg: str, retry_after_ms: int = 100,
                 tenant: str = "", reason: str = "queue_full"):
        super().__init__(msg)
        self.retry_after_ms = retry_after_ms
        self.tenant = tenant
        self.reason = reason


def stream_seed(rid: str) -> int:
    """Deterministic per-stream PRNG seed from the request id: stable
    across processes and replicas, so an HA failover-with-resume
    (same rid, fresh replica) replays the same sampling draws."""
    return zlib.crc32(rid.encode("utf-8")) & 0xFFFFFFFF


def parse_sampling(spec, rid: str) -> Tuple[float, int, float, int]:
    """Normalize a sampling request to ``(temperature, top_k, top_p,
    seed)``. ``spec`` may be None (greedy unless ``ZOO_LLM_SAMPLING``
    sets deployment defaults), a dict with any of
    ``temperature``/``top_k``/``top_p``/``seed``, or an env-style
    string ``"temperature=0.8,top_k=40,top_p=0.95,seed=7"``. A missing
    seed derives from the request id (:func:`stream_seed`)."""
    merged: Dict[str, float] = {}
    # env < spec precedence, default owned by the knob registry
    # (the engine and the docs promise ONE definition site)
    env = knob_value("ZOO_LLM_SAMPLING")
    for source in (env, spec):
        if not source:
            continue
        if isinstance(source, str):
            parts = {}
            for kv in source.split(","):
                if not kv.strip():
                    continue
                if "=" not in kv:
                    raise ValueError(
                        f"malformed sampling component {kv!r} "
                        "(expected key=value)")
                k, v = kv.split("=", 1)
                parts[k.strip()] = v.strip()
            source = parts
        unknown = set(source) - {"temperature", "top_k", "top_p", "seed"}
        if unknown:
            raise ValueError(f"unknown sampling keys {sorted(unknown)}")
        merged.update(source)
    temp = float(merged.get("temperature", 0.0))
    topk = int(merged.get("top_k", 0))
    topp = float(merged.get("top_p", 1.0))
    if temp < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temp}")
    if not (0.0 < topp <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {topp}")
    seed = int(merged["seed"]) & 0xFFFFFFFF if "seed" in merged \
        else stream_seed(rid)
    return temp, topk, topp, seed


class GenHandle:
    """One stream: the scheduler appends tokens, any number of
    subscribers read them by cursor (a duplicate request id or a
    resumed failover attempt replays from its own cursor — frames are
    never consumed destructively)."""

    def __init__(self, rid: str, prompt: np.ndarray, max_new: int,
                 deadline: Optional[Deadline],
                 sampling: Tuple[float, int, float, int] = None,
                 spec_k: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None,
                 tenant: str = ""):
        self.id = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new = int(max_new)
        self.deadline = deadline
        # QoS identity (docs/multitenancy.md): which tenant's bucket
        # admitted this stream, whose quota its slot/KV count against,
        # and whose priority class the preemption order reads. Empty =
        # the unlabeled default tenant (the pre-tenancy behavior).
        self.tenant = tenant or ""
        # request-scoped trace identity (rides the wire from the HA
        # client): every engine lifecycle event for this stream is
        # stamped with it, so the timeline merger can join this
        # replica's work into the request's fleet-wide trace
        self.trace_id = trace_id
        self.parent_span = parent_span
        # per-stream speculative budget: None = the engine default,
        # 0 = no drafting for this stream (it still rides the verify
        # batch with an empty draft — plain decode), 1..k = a cap
        self.spec_k = spec_k
        # lazily-built incremental prompt-lookup index (the drafter
        # runs every decode tick — rescanning the context each pass
        # would put O(context) work on the scheduler hot path). Owned
        # by the engine, mutated only under its lock.
        self.lookup: Optional[PromptLookup] = None
        self.lookup_len = 0   # generated tokens already indexed
        self.sampling = sampling if sampling is not None else \
            (0.0, 0, 1.0, stream_seed(rid))
        self.tokens: List[int] = []
        self.outcome: Optional[str] = None   # None=live
        self.error: Optional[str] = None
        self.truncated = False
        self.created = time.perf_counter()
        self.created_wall = time.time()
        self.first_token_at: Optional[float] = None
        self.last_token_at: Optional[float] = None
        self.admitted_at: Optional[float] = None
        self.preempts = 0
        self.prefill_chunks = 0   # prefill ticks this stream was fed in
        self.cancelled = threading.Event()
        self._cond = threading.Condition()
        self._subs = 0  # live server-side stream loops on this handle
        # scheduler-side state (owned by the engine under its lock)
        self.gen_count = 0        # tokens APPLIED (pushed) so far
        self.sched_count = 0      # tokens dispatched to the device so
        #                           far (>= gen_count under overlap;
        #                           the gap is in-flight speculation)
        self.admit_seq = -1       # admission order; preemption victims
        #                           are picked youngest-first
        self.effective_prompt: Optional[np.ndarray] = None  # after
        #                           preemption: prompt + generated
        # prefix-cache state, set at each admission (a resumed stream
        # re-hashes its GROWN effective prompt and re-matches on
        # whatever replica admits it; hashed_len is the cache key)
        self.block_hashes: list = []
        self.hashed_len = -1
        self.cache_hit_tokens = 0
        # disaggregation (docs/disaggregated_serving.md): hold_handoff
        # parks the stream after prefill (outcome "handoff") instead of
        # decoding; adopt carries an incoming kv_migrate payload so
        # admission binds the migrated blocks and enters decode with
        # ZERO local prefill work
        self.hold_handoff = False
        self.adopt: Optional[Dict] = None

    @property
    def done(self) -> bool:
        return self.outcome is not None

    def push(self, tok: int):
        with self._cond:
            self.tokens.append(int(tok))
            now = time.perf_counter()
            if self.first_token_at is None:
                self.first_token_at = now
                _ttft.observe(now - self.created)
            else:
                # per-stream cadence: the gap a streaming client felt
                # between this frame and the previous one (readback
                # path — preemption pauses and failover stalls land
                # here, which is exactly the point)
                _inter_token.observe(now - self.last_token_at)
            self.last_token_at = now
            self._cond.notify_all()

    def finish(self, outcome: str, error: Optional[str] = None):
        with self._cond:
            if self.outcome is not None:
                return
            self.outcome = outcome
            self.error = error
            # the drafter index is decode-time state; finished handles
            # live on in the dedup LRU and must not pin it
            self.lookup = None
            self._cond.notify_all()
        _streams.labels(outcome=outcome).inc()
        now = time.perf_counter()
        # ttft by outcome: a stream that died waiting observes its whole
        # lifetime (the latency its caller actually paid for nothing)
        _stream_ttft.labels(outcome=outcome).observe(
            (self.first_token_at or now) - self.created)
        record_event("llm_stream_end", rid=self.id, outcome=outcome,
                     tokens=len(self.tokens), preempts=self.preempts,
                     tenant=self.tenant or None, error=error)
        emit_span("llm.stream", self.created_wall, now - self.created,
                  trace=self.trace_id, parent=self.parent_span,
                  ok=outcome == "ok", t0=self.created, rid=self.id,
                  outcome=outcome,
                  tokens=len(self.tokens), preempts=self.preempts,
                  tenant=self.tenant or None)

    def cancel(self):
        """Client-side abort (connection dropped, caller gone): the
        scheduler frees the slot and KV blocks at its next sweep."""
        self.cancelled.set()
        with self._cond:
            self._cond.notify_all()

    def wait_new(self, cursor: int, timeout: Optional[float]
                 ) -> tuple:
        """Block until tokens beyond ``cursor`` exist or the stream
        ends. Returns ``(new_tokens, done)``; on timeout both are
        empty/False so the caller can re-check its own deadline."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                if len(self.tokens) > cursor:
                    return self.tokens[cursor:], self.outcome is not None
                if self.outcome is not None:
                    return [], True
                rem = None if end is None else end - time.monotonic()
                if rem is not None and rem <= 0:
                    return [], False
                self._cond.wait(rem if rem is None or rem < 0.5
                                else 0.5)

    def subscribe(self) -> int:
        """Register a streaming reader (a server handler, a joined
        duplicate, a hedge). The stream is only auto-cancelled when the
        LAST reader drops — a hedge loser's disconnect must not kill
        the winner's stream."""
        with self._cond:
            self._subs += 1
            return self._subs

    def unsubscribe(self) -> int:
        with self._cond:
            self._subs -= 1
            return self._subs

    def ttft(self) -> Optional[float]:
        return None if self.first_token_at is None else \
            self.first_token_at - self.created


class _Slot:
    __slots__ = ("handle", "last_token", "position", "phase",
                 "prefill_pos", "epoch", "host_token", "use_host",
                 "pending_copy", "spec_inflight")

    def __init__(self):
        self.handle: Optional[GenHandle] = None
        self.last_token = 0
        self.spec_inflight = False  # a verify batch for this seat is
        #                          dispatched but not yet applied: the
        #                          next pass must not re-dispatch it
        self.position = 0        # cache index the NEXT incoming token
        #                          will be written at
        self.phase = "decode"    # "prefill" while chunks are pending
        self.prefill_pos = 0     # prompt tokens already fed (starts at
        #                          the first UNCACHED token on a
        #                          prefix-cache hit)
        self.pending_copy = None  # (src, dst) CoW device copy owed
        #                          before this slot's next prefill write
        self.epoch = 0           # bumped whenever the slot is cleared:
        #                          an in-flight lane snapshot from an
        #                          older epoch is discarded on readback
        self.host_token = 0      # prefill token for the first decode
        self.use_host = False    # next tick feeds host_token, not the
        #                          on-device chain


class LLMEngine:
    """``LLMEngine(model).start()`` → ``submit()`` streams until
    ``stop()``.

    One scheduler: free slots are filled every pass and the tick
    pipeline is double-buffered against the device. ``model`` must
    expose the ``decode_step`` / ``read_tokens`` dispatch surface.
    ``mode`` and ``overlap`` take the one value each that names this
    scheduler (``"continuous"``; ``None`` / ``True``) and are neither
    stored nor reported."""

    def __init__(self, model, mode: str = "continuous",
                 max_waiting: Optional[int] = None,
                 overlap: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 role: Optional[str] = None,
                 tenancy=None):
        # mode / overlap: kept because the benchmark's harness passes
        # them (benchmarks/harness/serve_cell.py); they leave the
        # signature when it stops (ROADMAP D13)
        if mode != "continuous":
            raise ValueError(
                f"unknown scheduling mode {mode!r}: the engine has one "
                "scheduler, \"continuous\"")
        if overlap not in (None, True):
            raise ValueError(
                f"overlap={overlap!r}: the engine has one tick "
                "pipeline, the overlapped one (pass None or True)")
        missing = [m for m in ("decode_step", "read_tokens")
                   if not hasattr(model, m)]
        if missing:
            raise TypeError(
                f"{type(model).__name__} lacks {' and '.join(missing)}: "
                "the engine dispatches every tick through "
                "model.decode_step and reads it back through "
                "model.read_tokens")
        watch_compiles()    # no-op for the jax-free synthetic model
        self.model = model
        # disaggregated serving (docs/disaggregated_serving.md): the
        # replica's role in a mixed pool. "prefill" parks finished
        # prompts for kv_migrate handoff instead of decoding them,
        # "decode" adopts migrated KV, "mixed" (default) does both.
        if role is None:
            role = knob_value("ZOO_LLM_ROLE")
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"unknown replica role {role!r} (expected prefill, "
                "decode, or mixed)")
        self.role = role
        # speculative decoding: the engine drafts with the n-gram
        # prompt-lookup drafter and scores through the model's VERIFY
        # executable; the budget can never exceed the model's fixed
        # verify width (spec_k at model construction), and an engine
        # built with spec_k=0 on a spec-capable model runs plain
        # decode (the bench A/B rig)
        model_k = int(getattr(model, "spec_k", 0) or 0)
        if spec_k is None:
            spec_k = model_k
        self.spec_k = max(0, min(int(spec_k), model_k))
        self._spec = self.spec_k > 0 and hasattr(model, "verify_step")
        if spec_ngram is None:
            spec_ngram = env_int("ZOO_LLM_SPEC_NGRAM", 3)
        self.spec_ngram = max(1, int(spec_ngram))
        # drafter/accept accounting (stats(); the process-global
        # counters feed /metrics)
        self._spec_lanes = 0           # verify lanes dispatched
        self._spec_drafted_lanes = 0   # ... with a non-empty draft
        self._spec_proposed_n = 0
        self._spec_accepted_n = 0
        if prefix_cache is None:
            prefix_cache = knob_value("ZOO_LLM_PREFIX_CACHE")
        self.prefix_cache = bool(prefix_cache)
        # a model that keeps a per-slot recurrent state beside its
        # paged cache (the skeleton's ``UNPAGED_LEAVES``, the one
        # declaration of it): its prefills are handed the slot, and what
        # would need a state stored, rolled back or shipped is refused
        self._stateful = bool(getattr(model, "UNPAGED_LEAVES", ()))
        if self._stateful and self.prefix_cache:
            raise ValueError(
                f"{type(model).__name__} keeps a per-slot recurrent "
                "state: a prefix cache over it is not built (a shared "
                "prefix has no stored state); prefix_cache must be off")
        if self._stateful and self.role != "mixed":
            raise ValueError(
                f"{type(model).__name__} keeps a per-slot recurrent "
                "state: kv_migrate of a stateful sequence is not built, "
                f"so the replica role must be mixed (got {role!r})")
        self.max_waiting = max_waiting if max_waiting is not None else \
            env_int("ZOO_LLM_MAX_WAITING", 256)
        # multitenancy (docs/multitenancy.md): the QoS registry every
        # admission/scheduling decision consults. Disabled (no tenant
        # config) it is inert and the scheduler below is bit-identical
        # to the pre-tenancy FIFO / youngest-first machinery.
        self.tenancy = tenancy if tenancy is not None \
            else tenant_registry()
        # served decode+prefill tokens per tenant — the weighted-fair
        # scheduler admits the eligible tenant with the lowest
        # served/weight ratio (guarded-by: _lock)
        self._tenant_served: Dict[str, int] = {}
        self._tenant_gauged: set = set()
        self.allocator = BlockAllocator(model.num_blocks,
                                        model.block_size,
                                        prefix_cache=self.prefix_cache)
        # engine-local hit/miss tallies (stats()); the process-global
        # counters feed /metrics
        self._hit_tokens = 0
        self._miss_tokens = 0
        self._kv_bpt = getattr(model, "kv_bytes_per_token", None)
        if self._kv_bpt:
            _kv_bytes_per_token.set(float(self._kv_bpt))
        self._had_streams = False   # as of the last _publish
        self._weight_bytes = getattr(model, "weight_bytes", None)
        if self._weight_bytes:
            _weight_bytes.set(float(self._weight_bytes))
        self._slots = [_Slot() for _ in range(model.num_slots)]
        self._wait: Deque[GenHandle] = collections.deque()  # guarded-by: _lock
        # ONE reentrant state lock: the scheduler holds it across each
        # pass, the readback thread holds it while applying a batch —
        # slot/queue state is never observed half-mutated by either
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._admit_counter = 0
        # id → handle for every live stream plus an LRU of finished
        # ones: a duplicate id (retry / same-replica hedge) REPLAYS the
        # stream instead of re-decoding it
        # guarded-by: _lock
        self._by_id: "collections.OrderedDict[str, GenHandle]" = \
            collections.OrderedDict()
        self._finished_cap = env_int("ZOO_LLM_FINISHED_CACHE", 256)
        self._decode_steps = 0
        self._generated = 0
        # chunked prefill: tokens of prompt fed per tick (0 = whole
        # prompts at admission, the pre-chunking behavior)
        self._chunk = int(getattr(model, "prefill_chunk_size", 0) or 0)
        self._prefill_budget = env_int("ZOO_LLM_PREFILL_BUDGET",
                                       self._chunk) if self._chunk else 0
        # tick pipeline: what a pass dispatched waits on _rbq for its
        # landing; at most two ticks in flight; _prev_batch is the last
        # decode tick's ON-DEVICE output, the next tick's input (owned
        # by whoever runs the passes: the scheduler thread)
        self._rbq: "_queue.Queue" = _queue.Queue()
        self._inflight = threading.Semaphore(2)
        self._rb_thread: Optional[threading.Thread] = None
        self._prev_batch = None
        self._busy_win: Deque[Tuple[float, float]] = \
            collections.deque(maxlen=64)
        # set (under the lock) when a dispatch or readback failed: the
        # on-device token chain references a failed computation and
        # must be re-seeded from host state before the next dispatch
        self._chain_broken = False
        # disaggregation state (guarded-by: _lock). _handoffs parks a
        # prefilled sequence's payload (blocks still OWNED by the
        # allocator) until the server pushes it to the decode replica
        # and releases it; _adopted stages incoming kv_migrate payloads
        # until the matching generate arrives. Both age out on the
        # migrate TTL so a dead peer can never pin KV blocks forever.
        self._handoffs: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()
        self._adopted: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()
        self._handoff_ttl = max(
            0.05, float(knob_value("ZOO_KV_MIGRATE_TTL_MS")) / 1000.0)
        self._adopted_cap = 64
        self._handoffs_out = 0
        self._handoffs_in = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "LLMEngine":
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="zoo-llm-scheduler")
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
        # everything still live is cancelled and its blocks freed — the
        # pool must account to zero on shutdown
        with self._lock:
            live = [s.handle for s in self._slots if s.handle] + \
                list(self._wait)
            self._wait.clear()
            for s in self._slots:
                s.handle = None
                s.epoch += 1
        for h in live:
            self.allocator.free(h.id)
            h.finish("cancelled", "engine stopped")
        # parked handoffs hold blocks with no slot: free them too —
        # the pool must account to zero on shutdown
        with self._lock:
            parked = list(self._handoffs)
            self._handoffs.clear()
            self._adopted.clear()
        for rid in parked:
            self.allocator.free(rid)
        self._publish()

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               rid: Optional[str] = None,
               deadline: Optional[Deadline] = None,
               sampling=None, spec_k: Optional[int] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None,
               handoff: bool = False,
               adopt: Optional[Dict] = None,
               tenant: Optional[str] = None) -> GenHandle:
        """Queue one generation. ``sampling``: None (greedy, or the
        ``ZOO_LLM_SAMPLING`` deployment default), or a dict/string with
        ``temperature``/``top_k``/``top_p``/``seed`` — a missing seed
        derives deterministically from the request id, so retries and
        failover resumes replay the same draws. ``spec_k`` caps this
        stream's speculative draft budget (None = the engine default,
        0 = no drafting for this stream; it cannot raise the engine's
        verify width). ``trace_id``/``parent_span`` stamp every engine
        lifecycle event for this stream with the request's wire trace
        (docs/observability.md). Raises :class:`AdmissionError` when
        the waiting queue is full (retryable shed), ``ValueError`` for
        a prompt no prefill path can hold.

        ``handoff=True`` prefills only: the stream parks with outcome
        ``"handoff"`` and its KV blocks held for :meth:`take_handoff`.
        ``adopt`` binds an incoming kv_migrate payload instead of
        prefilling (docs/disaggregated_serving.md)."""
        if spec_k is not None and int(spec_k) < 0:
            raise ValueError("spec_k must be >= 0")
        if self._stateful and (handoff or adopt is not None):
            raise ValueError(
                "kv_migrate of a sequence with a per-slot recurrent "
                "state is not built: no handoff from, no adoption into "
                f"{type(self.model).__name__}")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size > self.model.max_prompt_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds the largest "
                f"prefill capacity ({self.model.max_prompt_len})")
        usable = self.allocator.num_blocks - 1
        if self.allocator.blocks_for_tokens(prompt.size + 1) > usable:
            # can_admit() could NEVER pass: without this check the
            # request would park at the head of the waiting queue
            # forever, wedging everything behind it
            raise ValueError(
                f"prompt of {prompt.size} tokens needs more KV blocks "
                f"than the whole pool holds ({usable} usable x "
                f"{self.allocator.block_size} tokens)")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if rid is None:
            import uuid
            rid = uuid.uuid4().hex
        params = parse_sampling(sampling, rid)
        tenant = tenant or ""
        with self._lock:
            prior = self._by_id.get(rid)
            if prior is not None:
                # a duplicate id joins the live stream — never charged
                # to the tenant bucket (retries and failover resumes
                # must not be double-billed)
                _dedup.inc()
                return prior
            if self.tenancy.enabled:
                ok, hint = self.tenancy.admit(tenant)
                if not ok:
                    label = tenant or "default"
                    _tenant_shed.labels(tenant=label,
                                        reason="rate").inc()
                    record_event("tenant_shed", rid=rid, tenant=label,
                                 reason="rate", retry_after_ms=hint)
                    raise AdmissionError(
                        f"tenant {label!r} rate limited "
                        f"(refill in {hint}ms)",
                        retry_after_ms=hint, tenant=tenant,
                        reason="rate")
            if len(self._wait) >= self.max_waiting:
                hint = 200
                if self.tenancy.enabled:
                    # the hint is THIS tenant's bucket refill, never
                    # the flooding tenant's backlog: a rate-limited
                    # flooder backs off on its own refill while a
                    # within-rate tenant retries on the generic hint
                    own = self.tenancy.bucket(tenant).retry_after_ms()
                    hint = own if own > 1 else 200
                    _tenant_shed.labels(tenant=tenant or "default",
                                        reason="queue_full").inc()
                raise AdmissionError(
                    f"llm waiting queue full ({len(self._wait)} "
                    f"streams, bound {self.max_waiting}); retry "
                    "another replica",
                    retry_after_ms=hint, tenant=tenant)
            if self.tenancy.enabled:
                _tenant_admitted.labels(
                    tenant=tenant or "default").inc()
            h = GenHandle(rid, prompt, max_new_tokens, deadline,
                          sampling=params,
                          spec_k=None if spec_k is None else
                          int(spec_k),
                          trace_id=trace_id, parent_span=parent_span,
                          tenant=tenant)
            h.hold_handoff = bool(handoff)
            h.adopt = adopt
            self._by_id[rid] = h
            self._trim_finished_locked()
            self._wait.append(h)
            _waiting.set(len(self._wait))
        self._wake.set()
        return h

    def get(self, rid: str) -> Optional[GenHandle]:
        with self._lock:
            return self._by_id.get(rid)

    def cancel(self, rid: str) -> bool:
        h = self.get(rid)
        if h is None or h.done:
            return False
        h.cancel()
        self._wake.set()
        return True

    def _trim_finished_locked(self):
        # caller holds self._lock. Finished handles age out of the dedup map
        # oldest-first; live handles are never evicted.
        while len(self._by_id) > self._finished_cap:
            for k, h in self._by_id.items():
                if h.done:
                    del self._by_id[k]
                    break
            else:
                return

    # -- scheduler ---------------------------------------------------------
    def _tick_flight(self):
        """Every 128th decode step drops a tick summary into the crash
        flight ring — a postmortem bundle then shows what the engine
        was running (occupancy, backlog, token count) in its last
        seconds, at a cost that never lands on every tick."""
        if self._decode_steps % 128:
            return
        record_event("engine_tick", steps=self._decode_steps,
                     occupancy=sum(1 for s in self._slots if s.handle),
                     waiting=len(self._wait),
                     generated=self._generated)

    def _publish(self):
        with self._lock:
            active = sum(1 for s in self._slots if s.handle)
            _occupancy.set(active)
            _waiting.set(len(self._wait))
            streams = bool(active or self._wait)
            drained = self._had_streams and not streams
            self._had_streams = streams
            if self.tenancy.enabled:
                slots_by: Dict[str, int] = {}
                for t, n in self._slots_by_tenant().items():
                    k = t or "default"
                    slots_by[k] = slots_by.get(k, 0) + n
                kv_by: Dict[str, int] = {}
                for t, n in self.allocator.used_by_tenant().items():
                    k = t or "default"
                    kv_by[k] = kv_by.get(k, 0) + n
                live = set(slots_by) | set(kv_by)
                # include previously-gauged tenants at 0 so the gauges
                # never hold a stale occupancy after a tenant drains
                for t in self._tenant_gauged | live:
                    _tenant_slots.labels(tenant=t).set(
                        slots_by.get(t, 0))
                    _tenant_kv.labels(tenant=t).set(kv_by.get(t, 0))
                self._tenant_gauged |= live
        # republished on every scheduler mutation so the ACTIVELY
        # serving engine owns the process-global gauge — a second
        # engine constructed in the same process (bench A/B rigs,
        # hot-swap pairs) only displaces it until the next tick
        if self._kv_bpt:
            _kv_bytes_per_token.set(float(self._kv_bpt))
        if self._weight_bytes:
            _weight_bytes.set(float(self._weight_bytes))
        if drained:
            # the last stream of a burst just left: collect the burst's
            # young garbage NOW, while no token waits on any thread of
            # this process, so the collector's next pass does not fall
            # at an arbitrary allocation of whoever runs next (the
            # caller's bookkeeping after its last answer, or the first
            # tick of the next burst)
            gc.collect(0)

    def _finish_slot(self, slot: _Slot, outcome: str,
                     error: Optional[str] = None):
        h = slot.handle
        slot.handle = None
        slot.epoch += 1   # any in-flight lane for this seat is stale now
        self.allocator.free(h.id)
        h.finish(outcome, error)

    def _expired(self, h: GenHandle) -> bool:
        return h.deadline is not None and h.deadline.expired()

    def _sweep(self):
        """Free slots whose stream is done for out-of-band reasons
        (client cancel, deadline expiry), and expire parked handoff /
        staged adoption state past the migrate TTL — a dead peer can
        never pin KV blocks forever."""
        for slot in self._slots:
            h = slot.handle
            if h is None:
                continue
            if h.cancelled.is_set():
                self._finish_slot(slot, "cancelled", "stream aborted")
            elif self._expired(h):
                self._finish_slot(
                    slot, "expired",
                    "deadline expired mid-stream (generation stopped, "
                    f"{h.gen_count} tokens emitted)")
        now = time.perf_counter()
        for rid in [r for r, p in self._handoffs.items()
                    if not p.get("taken")
                    and now - p["t0"] > self._handoff_ttl]:
            self._handoffs.pop(rid, None)
            self.allocator.free(rid)
            record_event("kv_handoff_abort", rid=rid, reason="ttl")
        for rid in [r for r, p in self._adopted.items()
                    if now - p["staged_at"] > self._handoff_ttl]:
            self._adopted.pop(rid, None)

    def _slots_by_tenant(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for s in self._slots:
            if s.handle is not None:
                t = s.handle.tenant
                out[t] = out.get(t, 0) + 1
        return out

    def _pop_next_waiter(self) -> Optional[GenHandle]:
        """Under self._lock: the next stream to admit. Tenancy off =
        plain FIFO (``popleft`` — the exact pre-tenancy order).
        Tenancy on = weighted-fair deficit pick: among tenants whose
        slot/KV quotas have headroom, the lowest priority-class number
        wins, then the lowest served-work/weight ratio; within a
        tenant, its oldest waiter (per-tenant FIFO). Tenants over
        quota are skipped entirely, so one tenant's backlog never
        parks the queue head in front of everyone else."""
        if not self._wait:
            return None
        reg = self.tenancy
        if not reg.enabled:
            return self._wait.popleft()
        slots_by = self._slots_by_tenant()
        kv_by = self.allocator.used_by_tenant()
        best = None
        best_key = None
        for h in self._wait:
            cfg = reg.config(h.tenant)
            if h.cancelled.is_set() or self._expired(h):
                # dead anyway — let it through so the admission loop
                # finishes it and frees the queue entry
                best = h
                break
            if cfg.max_slots and \
                    slots_by.get(h.tenant, 0) >= cfg.max_slots:
                continue
            if cfg.max_kv_blocks:
                prompt = h.effective_prompt \
                    if h.effective_prompt is not None else h.prompt
                need = self.allocator.blocks_for_tokens(
                    len(prompt) + 1)
                if kv_by.get(h.tenant, 0) + need > cfg.max_kv_blocks:
                    continue
            key = (cfg.priority,
                   self._tenant_served.get(h.tenant, 0) / cfg.weight)
            if best_key is None or key < best_key:
                best, best_key = h, key
        if best is not None:
            self._wait.remove(best)
        return best

    def _admit(self):
        for slot in self._slots:
            if slot.handle is not None:
                continue
            with self._lock:
                h = self._pop_next_waiter()
            if h is None:
                break
            if h.cancelled.is_set():
                h.finish("cancelled", "aborted while queued")
                continue
            if self._expired(h):
                h.finish("expired", "deadline expired in the waiting "
                                    "queue (never admitted)")
                continue
            prompt = h.effective_prompt if h.effective_prompt \
                is not None else h.prompt
            if self.allocator.blocks_for_tokens(len(prompt) + 1) > \
                    self.allocator.num_blocks - 1:
                # a preempted stream whose prompt+generated context
                # outgrew the whole pool: no future free list satisfies
                # it, so end it loudly instead of parking it forever
                h.finish("error",
                         f"resumed context of {len(prompt)} tokens "
                         "exceeds the whole KV pool")
                continue
            if self.tenancy.enabled and h.tenant:
                # tag the sequence's tenant partition BEFORE any block
                # moves: its freed prefix blocks park there and its
                # allocations evict from it first
                self.allocator.set_tenant(h.id, h.tenant)
            if h.adopt is not None:
                # migrated stream: bind the adopted table and enter
                # decode directly — no prefill work at all
                if not self._bind_adopted(slot, h, prompt):
                    with self._lock:
                        self._wait.appendleft(h)
                    break
                continue
            # prefix cache: hash the prompt's full blocks and probe for
            # the longest cached run. At least the LAST prompt token is
            # always recomputed (its forward pass produces the first
            # generated token), so an aligned full-prompt hit recomputes
            # one token into a copy-on-write fork of its final block.
            # Hashes are cached on the handle so a block-gated head
            # re-attempted every tick doesn't re-hash a long prompt
            # each pass (the effective prompt only ever changes by
            # GROWING on a preempt-resume, so length is the identity).
            hashes = []
            if self.prefix_cache:
                if h.block_hashes and h.hashed_len == len(prompt):
                    hashes = h.block_hashes
                else:
                    # tenant-salted chain: distinct tenants can never
                    # match each other's cache entries (empty salt for
                    # unlabeled traffic — the pre-tenancy hashes)
                    hashes = prefix_block_hashes(
                        prompt, self.allocator.block_size,
                        salt=self.tenancy.salt(h.tenant))
                    h.block_hashes = hashes
                    h.hashed_len = len(prompt)
            matched = self.allocator.match_prefix(hashes)
            start = min(matched * self.allocator.block_size,
                        len(prompt) - 1)
            if not self.allocator.can_admit(
                    len(prompt), cached_blocks=matched,
                    needs_cow=matched * self.allocator.block_size
                    > start):
                # KV pressure: requeue at the head and stop admitting
                # this tick — FIFO order is preserved and the gauge
                # shows the door is block-gated, not slot-gated
                with self._lock:
                    self._wait.appendleft(h)
                break
            if not self._bind_blocks(slot, h, prompt, hashes):
                with self._lock:   # raced another allocator client
                    self._wait.appendleft(h)
                break
            # the per-sequence sampling state rides the block-table
            # entry: a scheduler that migrates/resumes the sequence
            # replays the same PRNG draws from (seed, token index).
            # Aux is PER-SEQUENCE, never per-block — prefix sharing
            # must not alias one stream's replay state into another's.
            self.allocator.set_aux(h.id, seed=h.sampling[3],
                                   resumed_at=len(prompt))
            slot.handle = h
            slot.epoch += 1
            slot.spec_inflight = False  # any stale verify batch for
            #                          this seat died with the epoch
            self._admit_counter += 1
            h.admit_seq = self._admit_counter
            h.admitted_at = time.perf_counter()
            self._span_queue_wait(h, len(prompt))
            self._note_served(h, len(prompt) - h.cache_hit_tokens)
            emit_event("llm.admit", trace=h.trace_id,
                       parent=h.parent_span, rid=h.id,
                       queue_wait_s=round(h.admitted_at - h.created, 6),
                       prompt_tokens=int(len(prompt)),
                       cache_hit_tokens=int(h.cache_hit_tokens),
                       cow_fork=slot.pending_copy is not None,
                       resumed=h.effective_prompt is not None,
                       tenant=h.tenant or None)
            # admission only BINDS the slot and blocks; the device
            # prefill itself (whole prompt, suffix past the cached
            # prefix, or chunks across ticks) runs in _prefill_tick
            # OUTSIDE the engine lock, so submit() and the readback
            # thread never stall behind a long prompt
            slot.phase = "prefill"
            slot.prefill_pos = h.cache_hit_tokens
            slot.position = 0
        if self.tenancy.enabled:
            self._preempt_for_class()
        self._publish()

    @staticmethod
    def _span_queue_wait(h: GenHandle, prompt_tokens: int):
        """``llm.queue_wait``: created -> this admission, while the
        stream still owes its first token — with ``llm.prefill_total``
        the two halves of its TTFT. (A stream preempted before its
        first token is re-admitted and records again, from ``created``:
        the LAST record is the one that sums to the TTFT.)"""
        if h.first_token_at is None:
            wait = h.admitted_at - h.created
            emit_span("llm.queue_wait", time.time() - wait, wait,
                      trace=h.trace_id, parent=h.parent_span,
                      t0=h.created, rid=h.id,
                      prompt_tokens=int(prompt_tokens),
                      preempts=h.preempts)

    def _note_served(self, h: GenHandle, n: int):
        """Charge ``n`` tokens of service to the stream's tenant — the
        denominator the weighted-fair pick normalizes by weight."""
        if n > 0 and self.tenancy.enabled:
            self._tenant_served[h.tenant] = \
                self._tenant_served.get(h.tenant, 0) + int(n)

    def _preempt_for_class(self):
        """Cross-class preemption (docs/multitenancy.md): when every
        slot is held and a waiter of a strictly HIGHER priority class
        (lower number) is eligible (within its own quotas), evict the
        lowest-class youngest running stream to make room — a paid
        tier displaces best-effort streams, never a peer. One victim
        per pass keeps the churn bounded; the freed slot admits the
        high-class waiter on the very next scheduler pass, and the
        victim resumes byte-identically via the ordinary re-prefill
        path."""
        reg = self.tenancy
        with self._lock:
            if not self._wait or \
                    any(s.handle is None for s in self._slots):
                return
            slots_by = self._slots_by_tenant()
            best_cls = None
            for h in self._wait:
                if h.cancelled.is_set() or self._expired(h):
                    continue
                cfg = reg.config(h.tenant)
                if cfg.max_slots and \
                        slots_by.get(h.tenant, 0) >= cfg.max_slots:
                    continue
                if best_cls is None or cfg.priority < best_cls:
                    best_cls = cfg.priority
            if best_cls is None:
                return
            victim = None
            victim_key = None
            for slot in self._slots:
                hh = slot.handle
                if hh is None:
                    continue
                c = reg.config(hh.tenant).priority
                if c <= best_cls:
                    continue   # same or higher priority: never evicted
                key = (c, hh.admit_seq)
                if victim_key is None or key > victim_key:
                    victim, victim_key = slot, key
            if victim is not None:
                self._preempt(victim, reason="class")

    def _bind_blocks(self, slot: _Slot, h: GenHandle,
                     prompt: np.ndarray, hashes: list) -> bool:
        """Bind ``h``'s KV blocks: acquire the longest cached prefix
        (refcount bumps — a shared block is counted ONCE in the pool),
        allocate the private remainder, and fork the final matched
        block when the recompute write would land inside it
        (copy-on-write; the device copy is owed via
        ``slot.pending_copy`` and dispatched before the first prefill
        write). Returns False with everything released on an
        allocation race."""
        bs = self.allocator.block_size
        got = self.allocator.acquire_prefix(h.id, hashes)
        start = min(len(got) * bs, len(prompt) - 1)
        need = self.allocator.blocks_for_tokens(len(prompt)) - len(got)
        if need > 0 and self.allocator.allocate(h.id, need) is None:
            self.allocator.free(h.id)
            return False
        slot.pending_copy = None
        if len(got) * bs > start:
            # aligned full-prompt hit: the recomputed last token writes
            # into the final MATCHED block — fork it first
            try:
                slot.pending_copy = self.allocator.make_writable(
                    h.id, len(got) - 1)
            except MemoryError:
                self.allocator.free(h.id)
                return False
        h.cache_hit_tokens = start
        if self.prefix_cache:
            self._hit_tokens += start
            self._miss_tokens += len(prompt) - start
            _prefix_hits.inc(start)
            _prefix_misses.inc(len(prompt) - start)
        return True

    def _enter_decode(self, slot: _Slot, h: GenHandle, first: int,
                      prompt_len: int):
        """Prompt fully prefilled: push the first generated token and
        arm the slot for the decode chain (first tick host-fed)."""
        if h.hold_handoff:
            self._park_handoff(slot, h, first, prompt_len)
            return
        # publish the prompt's full blocks under their content hashes —
        # every later stream carrying the same prefix binds them
        # instead of re-prefilling (first writer wins, so a CoW fork
        # never shadows the shared original)
        self.allocator.register_blocks(h.id, h.block_hashes)
        slot.phase = "decode"
        slot.position = prompt_len
        slot.last_token = first
        slot.host_token = first
        slot.use_host = True
        emit_event("llm.first_token", trace=h.trace_id,
                   parent=h.parent_span, rid=h.id)
        owed = h.first_token_at is None
        h.push(first)
        if owed and h.admitted_at is not None:
            took = h.first_token_at - h.admitted_at
            emit_span("llm.prefill_total", time.time() - took, took,
                      trace=h.trace_id, parent=h.parent_span,
                      t0=h.admitted_at, rid=h.id,
                      prompt_tokens=int(prompt_len),
                      chunks=h.prefill_chunks, preempts=h.preempts)
        h.gen_count += 1
        h.sched_count += 1
        self._generated += 1
        self._note_served(h, 1)
        _tokens.labels(kind="decode").inc()
        eos = getattr(self.model, "eos_id", None)
        if h.gen_count >= h.max_new or \
                (eos is not None and first == eos):
            self._finish_slot(slot, "ok")

    # -- disaggregated handoff (docs/disaggregated_serving.md) -------------
    def _park_handoff(self, slot: _Slot, h: GenHandle, first: int,
                      prompt_len: int):
        """Prompt fully prefilled on a handoff stream: publish the
        prefix locally, park the migration payload with the KV blocks
        still OWNED, release the slot, and finish the stream with
        outcome ``"handoff"`` — the server then pushes the payload to
        the decode replica and calls :meth:`release_handoff`. Under
        self._lock (the _apply_prefill path)."""
        self.allocator.register_blocks(h.id, h.block_hashes)
        prompt = h.effective_prompt if h.effective_prompt is not None \
            else h.prompt
        payload = {
            "rid": h.id,
            "prompt": [int(t) for t in prompt],
            "first": int(first),
            "sampling": list(h.sampling),
            "hashes": list(h.block_hashes),
            "blocks": self.allocator.blocks_of(h.id),
            "block_size": self.allocator.block_size,
            "aux": self.allocator.get_aux(h.id),
            "max_new": h.max_new,
            "tenant": h.tenant,
            "t0": time.perf_counter(),
        }
        self._handoffs[h.id] = payload
        # the SLOT frees now; the BLOCKS stay owned until
        # release_handoff (or the TTL sweep) frees them — hashed
        # blocks then park on the prefix LRU, so the prefill replica
        # keeps serving the prefix locally too
        slot.handle = None
        slot.epoch += 1
        self._handoffs_out += 1
        record_event("kv_migrate_out", rid=h.id,
                     blocks=len(payload["blocks"]),
                     prompt_tokens=int(prompt_len))
        h.finish("handoff")
        self._publish()

    def take_handoff(self, rid: str) -> Optional[Dict]:
        """The parked payload for ``rid``, marked in-push so the TTL
        sweep leaves its blocks alone until :meth:`release_handoff`;
        None when nothing is parked (expired, already released)."""
        with self._lock:
            payload = self._handoffs.get(rid)
            if payload is not None:
                payload["taken"] = True
            return payload

    def release_handoff(self, rid: str) -> bool:
        """Free a parked handoff's blocks (pushed to the decode
        replica — or the push died and the client will fall back to a
        plain re-prefill elsewhere)."""
        with self._lock:
            payload = self._handoffs.pop(rid, None)
        if payload is None:
            return False
        self.allocator.free(rid)
        return True

    def offer_adopted(self, payload: Dict) -> bool:
        """Stage an incoming kv_migrate payload until its generate
        arrives (bounded LRU; ages out on the migrate TTL). The
        allocator is untouched here, so a peer that dies after commit
        but before the generate lands leaks nothing. Refused (False)
        when the payload cannot be decoded faithfully here — block
        geometry mismatch, this model holds real KV state and the
        payload carries none, or this model keeps a per-slot recurrent
        state, which no payload carries."""
        if int(payload.get("block_size") or 0) != \
                self.allocator.block_size or self._stateful:
            return False
        if hasattr(self.model, "import_kv_blocks") and \
                payload.get("kv") is None:
            return False
        payload = dict(payload)
        payload["staged_at"] = time.perf_counter()
        with self._lock:
            self._adopted[str(payload["rid"])] = payload
            while len(self._adopted) > self._adopted_cap:
                self._adopted.popitem(last=False)
        return True

    def pop_adopted(self, rid: str) -> Optional[Dict]:
        """Claim the staged payload for ``rid`` (None = never staged /
        aged out — the caller submits a plain re-prefill, which by
        determinism yields the identical stream)."""
        with self._lock:
            payload = self._adopted.pop(rid, None)
        if payload is None:
            return None
        if time.perf_counter() - payload["staged_at"] > \
                self._handoff_ttl:
            return None
        return payload

    def _bind_adopted(self, slot: _Slot, h: GenHandle,
                      prompt: np.ndarray) -> bool:
        """Admission for a migrated stream: bind the adopted block
        table (aliasing any locally-matchable prefix run), import the
        wire KV bytes into the fresh blocks, and enter decode DIRECTLY
        with the prefill replica's first token — zero prefill device
        calls, so a pure-decode replica's compile census stays at the
        one decode executable. Returns False when the pool cannot fund
        the table yet (requeue, same contract as can_admit). Under
        self._lock."""
        payload = h.adopt
        hashes = [bytes(x) for x in payload.get("hashes") or ()]
        n_blocks = self.allocator.blocks_for_tokens(len(prompt) + 1)
        got = self.allocator.adopt_blocks(h.id, hashes, n_blocks)
        if got is None:
            return False
        table, n_reused = got
        h.adopt = None
        h.block_hashes = hashes
        h.hashed_len = len(prompt)
        kv = payload.get("kv")
        fn = getattr(self.model, "import_kv_blocks", None)
        if kv is not None and fn is not None:
            # fresh rows only: locally-aliased prefix blocks already
            # hold byte-identical K/V (the hash-match guarantee)
            fn(table[n_reused:], kv, start=n_reused)
        bs = self.allocator.block_size
        local_hit = min(n_reused * bs, len(prompt) - 1)
        h.cache_hit_tokens = local_hit
        if self.prefix_cache and local_hit:
            # aliased rows are genuine prefix-cache hits; the migrated
            # remainder is neither hit nor miss — no prefill ran
            self._hit_tokens += local_hit
            _prefix_hits.inc(local_hit)
        self.allocator.set_aux(h.id, seed=h.sampling[3],
                               resumed_at=len(prompt))
        slot.handle = h
        slot.epoch += 1
        slot.spec_inflight = False
        slot.pending_copy = None
        self._admit_counter += 1
        h.admit_seq = self._admit_counter
        h.admitted_at = time.perf_counter()
        self._span_queue_wait(h, len(prompt))
        self._handoffs_in += 1
        emit_event("llm.admit", trace=h.trace_id,
                   parent=h.parent_span, rid=h.id,
                   queue_wait_s=round(h.admitted_at - h.created, 6),
                   prompt_tokens=int(len(prompt)),
                   cache_hit_tokens=int(local_hit),
                   cow_fork=False, resumed=False, adopted=True,
                   tenant=h.tenant or None)
        record_event("kv_migrate_in", rid=h.id,
                     blocks=len(table) - n_reused, reused=n_reused)
        self._enter_decode(slot, h, int(payload["first"]), len(prompt))
        return True

    def _select_prefill(self) -> List[tuple]:
        """Under the lock: claim this tick's prefill work — whole
        prompts (chunking off), or up to one budget of chunks, oldest
        admission first. Claiming advances ``prefill_pos`` so the next
        select never double-feeds; the device calls themselves run
        outside the lock (:meth:`_run_prefill`)."""
        pending = sorted(
            (s for s in self._slots
             if s.handle is not None and s.phase == "prefill"),
            key=lambda s: s.handle.admit_seq)
        budget = self._prefill_budget if self._chunk else None
        work = []
        for slot in pending:
            h = slot.handle
            prompt = h.effective_prompt if h.effective_prompt \
                is not None else h.prompt
            n = len(prompt)
            start = slot.prefill_pos
            if start >= n:
                continue   # fed, result still in flight this tick
            if budget is None:
                take = n - start   # whole prompt, or the whole novel
                #                    suffix past a cached prefix
            else:
                if budget <= 0:
                    break
                take = min(self._chunk, n - start)
                budget -= take
            slot.prefill_pos = start + take
            copy = slot.pending_copy
            slot.pending_copy = None
            work.append((slot, h, slot.epoch, prompt, start, take, n,
                         self._table_row(self.allocator.blocks_of(
                             h.id)), copy))
        return work

    def _run_prefill(self, work) -> List[tuple]:
        """OUTSIDE the lock: execute the claimed prefill device calls
        (submit() and the readback thread keep flowing while a long
        prompt runs). Returns per-item results for _apply_prefill."""
        results = []
        for slot, h, epoch, prompt, start, take, n, row, copy in work:
            t0 = time.perf_counter()
            t0_wall = time.time()
            # a stateful model builds the sequence's state where its
            # decode ticks will find it: in its slot's row
            kw = {"slot": self._slots.index(slot)} if self._stateful \
                else {}
            try:
                if copy is not None:
                    # the copy-on-write device copy owed from
                    # admission: duplicate the shared block's bytes
                    # BEFORE this sequence's first write lands in the
                    # fork. Dispatch order on the one device stream
                    # also orders it before any later re-use of the
                    # source block. A model without copy_block cannot
                    # serve a forked block — fail THIS stream loudly
                    # (the except below error-finishes it) rather than
                    # silently decode over a zeroed prefix.
                    fn = getattr(self.model, "copy_block", None)
                    if fn is None:
                        raise RuntimeError(
                            "prefix-cache CoW fork needs "
                            "model.copy_block and this model has none")
                    fn(*copy)
                if self._chunk:
                    tok = self.model.prefill_chunk(
                        prompt[start:start + take], start, n, row,
                        sampling=h.sampling, **kw)
                elif start == 0:
                    tok = self.model.prefill(prompt, row,
                                             sampling=h.sampling, **kw)
                else:
                    # cache-hit prompt in a bucketed config: feed the
                    # novel suffix through the ONE chunk executable
                    # (the bucket executable can only start at 0; the
                    # chunk path attends over the resident cached
                    # prefix by construction)
                    C = int(getattr(self.model, "suffix_chunk_size", 0)
                            or self.model.block_size)
                    tok = None
                    for s0 in range(start, start + take, C):
                        tok = self.model.prefill_chunk(
                            prompt[s0:min(s0 + C, n)], s0, n, row,
                            sampling=h.sampling, **kw)
                if start + take >= n:
                    # the prompt's last chunk: wait for its first
                    # generated token, the one host sync of the prefill
                    # path. A chunk before it is never waited for (the
                    # device stream orders it before whatever reads its
                    # rows): the device goes on with what is queued
                    # behind it, this pass's decode tick next
                    with span("llm.model.prefill_sync"):
                        tok = int(tok)
            except Exception as e:  # noqa: BLE001 — a prefill failure
                # must end THIS stream loudly, not kill the scheduler
                # thread with every stream hanging
                results.append((slot, h, epoch, start, take, n, None,
                                e))
                continue
            dur = time.perf_counter() - t0
            _tick_seconds.labels(phase="prefill").observe(dur)
            _tokens.labels(kind="prefill").inc(take)
            h.prefill_chunks += 1
            emit_span("llm.prefill", t0_wall, dur, trace=h.trace_id,
                      parent=h.parent_span, t0=t0, rid=h.id,
                      start=int(start), tokens=int(take), total=int(n))
            results.append((slot, h, epoch, start, take, n, tok, None))
        return results

    def _apply_prefill(self, results):
        """Under the lock: land prefill results. A slot that moved on
        while the device ran (cancel/expiry/preemption bumped the
        epoch) is skipped — its K/V writes are overwritten before any
        new owner reads them, same argument as in-flight decode
        lanes."""
        for slot, h, epoch, start, take, n, tok, err in results:
            if slot.handle is not h or slot.epoch != epoch or h.done:
                continue
            if err is not None:
                self._finish_slot(slot, "error",
                                  f"prefill failed: {err!r}")
                continue
            if start + take >= n:
                self._enter_decode(slot, h, tok, n)
        self._publish()

    def _prefill_tick(self):
        """One tick of prompt feeding: long prompts advance a chunk per
        tick while every live stream keeps decoding — the anti-stall
        the chunk executable exists for. Lock is held only around the
        claim and the apply, never across the device. Returns the
        number of prompt chunks it ran."""
        with self._lock:
            work = self._select_prefill()
        if not work:
            return 0
        results = self._run_prefill(work)
        with self._lock:
            self._apply_prefill(results)
        return len(work)

    def _table_row(self, blocks: Sequence[int]) -> np.ndarray:
        row = np.zeros((self.model.max_blocks_per_seq,), np.int32)
        row[:len(blocks)] = blocks
        return row

    def _grow_or_preempt(self) -> None:
        """Every decoding slot must own the block its next write lands
        in (position // block_size). When the free list is dry, evict
        the youngest-admitted stream and retry; a stream that cannot
        even self-fund (alone and out of pool) errors out."""
        bs = self.model.block_size
        for slot in self._slots:
            h = slot.handle
            if h is None or slot.phase != "decode":
                continue
            needed = slot.position // bs + 1
            while True:
                if needed > self.model.max_blocks_per_seq:
                    # block table is full: the sequence hit the context
                    # ceiling — a truncated-but-successful stream. With
                    # ticks in flight, wait until every dispatched
                    # token has been applied so none are dropped.
                    if h.sched_count == h.gen_count:
                        h.truncated = True
                        self._finish_slot(slot, "ok")
                    break
                have = len(self.allocator.blocks_of(h.id))
                if have >= needed:
                    break
                if self.allocator.allocate(h.id, 1) is not None:
                    continue
                victim = self._pick_victim(exclude=h)
                if victim is None:
                    if self.tenancy.enabled and any(
                            s.handle is not None and s.handle is not h
                            for s in self._slots):
                        # every other live stream outranks h: requeue
                        # h itself (byte-identical resume) rather than
                        # evict a higher-priority tenant's KV — or end
                        # h with an error it did nothing to earn
                        self._preempt(slot)
                        break
                    self._finish_slot(
                        slot, "error",
                        "kv cache exhausted: sequence cannot grow and "
                        "no other stream is preemptible")
                    break
                self._preempt(victim)

    def _pick_victim(self, exclude: GenHandle) -> Optional[_Slot]:
        """The stream to evict when ``exclude`` needs a block the pool
        cannot fund: youngest-admitted WITHIN the lowest priority
        class (tenancy on — and never a class that outranks
        ``exclude``'s own); plain youngest-first when tenancy is off
        (every key ties at class 0, leaving exactly the pre-tenancy
        order)."""
        reg = self.tenancy
        ex_cls = reg.config(exclude.tenant).priority \
            if reg.enabled else 0
        best = None
        best_key = None
        for slot in self._slots:
            if slot.handle is None or slot.handle is exclude:
                continue
            if reg.enabled:
                c = reg.config(slot.handle.tenant).priority
                if c < ex_cls:
                    continue   # outranks the grower: never its victim
                key = (c, slot.handle.admit_seq)
            else:
                key = (0, slot.handle.admit_seq)
            if best_key is None or key > best_key:
                best, best_key = slot, key
        return best

    def _preempt(self, slot: _Slot, reason: str = "kv"):
        """Evict a running stream: free its blocks and requeue it with
        prompt := original prompt + everything generated so far.
        Decode (greedy or seeded sampling — the PRNG key is a pure
        function of seed and token index, and the seed was
        checkpointed with the block-table entry) is deterministic, so
        the re-prefilled continuation matches what the stream would
        have produced — subscribers just see a pause. Tokens dispatched
        but not yet read back are dropped with the slot epoch and
        re-drawn identically after the resume."""
        h = slot.handle
        resumed = np.concatenate(
            [h.prompt, np.asarray(h.tokens, np.int32)])
        if len(resumed) > self.model.max_prompt_len:
            # cannot re-prefill a context longer than the prefill path
            # can hold; end it as truncated-ok rather than wedge the
            # pool
            h.truncated = True
            self._finish_slot(slot, "ok")
            return
        # replay alignment: everything past the APPLIED tokens is
        # regenerated from the checkpointed (seed, token index) state
        aux = self.allocator.get_aux(h.id)
        assert aux is None or aux.get("seed") == h.sampling[3]
        h.effective_prompt = resumed
        h.sched_count = h.gen_count
        h.preempts += 1
        slot.handle = None
        slot.epoch += 1
        self.allocator.free(h.id)
        _preempts.inc()
        if self.tenancy.enabled:
            _tenant_preempted.labels(tenant=h.tenant or "default",
                                     reason=reason).inc()
        emit_event("llm.preempt", trace=h.trace_id,
                   parent=h.parent_span, rid=h.id,
                   generated=int(h.gen_count), reason=reason,
                   tenant=h.tenant or None)
        record_event("llm_preempt", rid=h.id,
                     generated=int(h.gen_count), reason=reason,
                     tenant=h.tenant or None)
        with self._lock:
            self._wait.appendleft(h)

    def _build_tick(self):
        """Assemble the fixed-shape decode operands for every decoding
        slot (one lane per slot; idle/prefilling lanes write to the
        trash block and are never read). Continuing lanes are fed from
        the previous tick's on-device batch; a lane fresh from prefill
        (or re-seeded after a failed tick) overrides its own with
        ``slot.host_token`` once.
        Advances positions/sched counters — the caller WILL dispatch.
        Returns None when no lane decodes this tick."""
        S = self.model.num_slots
        host = np.zeros((S,), np.int32)
        use = np.zeros((S,), bool)
        tables = np.zeros((S, self.model.max_blocks_per_seq), np.int32)
        positions = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        topks = np.zeros((S,), np.int32)
        topps = np.ones((S,), np.float32)
        seeds = np.zeros((S,), np.uint32)
        snapshot = []
        for i, slot in enumerate(self._slots):
            h = slot.handle
            if h is None or slot.phase != "decode" or h.done:
                continue
            ctx = getattr(self.model, "max_context",
                          self.model.max_blocks_per_seq *
                          self.model.block_size)
            if h.sched_count >= h.max_new or slot.position >= ctx:
                # everything is dispatched (or the table is full):
                # this lane idles until readback settles its fate
                continue
            snapshot.append((i, h, slot.epoch))
            tables[i] = self._table_row(
                self.allocator.blocks_of(h.id))
            positions[i] = slot.position
            if slot.use_host:
                use[i] = True
                host[i] = slot.host_token
                slot.use_host = False
            t, k, p, s = h.sampling
            temps[i], topks[i], topps[i], seeds[i] = t, k, p, s
            slot.position += 1
            h.sched_count += 1
        if not snapshot:
            return None
        return (host, use, tables, positions,
                (temps, topks, topps, seeds), snapshot)

    def _fail_lanes(self, snapshot, err: BaseException):
        """A dispatched batch's tokens are unrecoverable (dispatch or
        readback raised): end the affected streams LOUDLY. Skipping
        silently would leave a one-token hole in each stream and a
        sched/gen gap that wedges the slot (and its KV blocks) forever.
        Under self._lock."""
        for i, h, epoch in snapshot:
            slot = self._slots[i]
            if slot.handle is h and slot.epoch == epoch and not h.done:
                self._finish_slot(
                    slot, "error",
                    f"decode tick failed, stream tokens lost: {err!r}")
        self._chain_broken = True
        self._publish()

    def _apply_tokens(self, snapshot, arr: np.ndarray):
        """Apply one readback batch to its streams. A lane whose slot
        moved on (finish / expiry / preemption bumped the epoch) is
        discarded — its token is either unwanted or will be re-drawn
        bit-identically by the resume."""
        eos = getattr(self.model, "eos_id", None)
        for i, h, epoch in snapshot:
            slot = self._slots[i]
            if slot.handle is not h or slot.epoch != epoch or h.done:
                continue
            tok = int(arr[i])
            slot.last_token = tok
            h.push(tok)
            h.gen_count += 1
            self._generated += 1
            self._note_served(h, 1)
            _tokens.labels(kind="decode").inc()
            if h.gen_count >= h.max_new or \
                    (eos is not None and tok == eos):
                self._finish_slot(slot, "ok")
        self._publish()

    # -- speculative decoding ----------------------------------------------
    def _draft_for(self, h: GenHandle) -> np.ndarray:
        """Up to the stream's spec budget of drafted continuation
        tokens from the n-gram prompt-lookup drafter, matched against
        prompt + everything generated (which always ends with the last
        emitted token — the verify pass's row 0). The per-stream index
        is built once and extended incrementally as tokens land, so
        drafting stays O(k) per tick. Under self._lock (push() only
        ever appends to ``h.tokens`` from under the same lock)."""
        k = self.spec_k if h.spec_k is None else min(h.spec_k,
                                                     self.spec_k)
        if k <= 0:
            return np.zeros((0,), np.int32)
        if h.lookup is None:
            h.lookup = PromptLookup(h.prompt, self.spec_ngram)
        if h.lookup_len < len(h.tokens):
            h.lookup.extend(h.tokens[h.lookup_len:])
            h.lookup_len = len(h.tokens)
        return h.lookup.propose(k)

    def _build_verify_tick(self):
        """Under the lock: assemble ONE fixed-shape verify batch —
        (slots, spec_k + 1) candidate rows, row 0 the incoming token,
        rows 1.. the drafter's proposals, zero-padded. The draft span
        is funded from the FREE list only (``grow_to`` — speculation
        never preempts another stream) and clamped to owned blocks,
        the context ceiling, and the stream's remaining budget, so
        every token the accept step can emit has a REAL cache row.
        A seat with a verify batch still in flight idles until the
        readback applies it (accept length decides the next base
        position, so spec lanes cannot chain on-device)."""
        S = self.model.num_slots
        T = self.spec_k + 1
        ctx = getattr(self.model, "max_context",
                      self.model.max_blocks_per_seq *
                      self.model.block_size)
        tokens = np.zeros((S, T), np.int32)
        tables = np.zeros((S, self.model.max_blocks_per_seq), np.int32)
        positions = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        topks = np.zeros((S,), np.int32)
        topps = np.ones((S,), np.float32)
        seeds = np.zeros((S,), np.uint32)
        snapshot = []
        for i, slot in enumerate(self._slots):
            h = slot.handle
            if h is None or slot.phase != "decode" or h.done:
                continue
            if slot.spec_inflight:
                continue
            if h.sched_count >= h.max_new or slot.position >= ctx:
                continue
            draft = self._draft_for(h)
            if len(draft):
                cap_tokens = self.allocator.grow_to(
                    h.id, min(slot.position + len(draft) + 1, ctx))
                cap = min(cap_tokens - 1 - slot.position,
                          ctx - 1 - slot.position,
                          h.max_new - h.gen_count - 1)
                draft = draft[:max(0, cap)]
            tokens[i, 0] = slot.last_token
            if len(draft):
                tokens[i, 1:1 + len(draft)] = draft
            tables[i] = self._table_row(self.allocator.blocks_of(h.id))
            positions[i] = slot.position
            t, k, p, s = h.sampling
            temps[i], topks[i], topps[i], seeds[i] = t, k, p, s
            slot.spec_inflight = True
            snapshot.append((i, h, slot.epoch,
                             [int(x) for x in draft]))
        if not snapshot:
            return None
        return (tokens, tables, positions,
                (temps, topks, topps, seeds), snapshot)

    def _apply_spec(self, snapshot, arr: np.ndarray):
        """Apply one verify readback: emit the longest accepted prefix
        plus the model's own next token. ``arr[i, j]`` is the CANONICAL
        token after the context extended by the first ``j`` draft
        tokens (sampled with the same stateless key non-speculative
        decode would use), so the emitted stream is byte-identical to
        plain decode by construction; rejected rows' cache writes are
        dead weight the position mask hides until the next pass
        overwrites them (rollback = length reset). A lane whose slot
        moved on (epoch bumped) is discarded, exactly like a decode
        lane."""
        eos = getattr(self.model, "eos_id", None)
        for i, h, epoch, draft in snapshot:
            slot = self._slots[i]
            if slot.handle is not h or slot.epoch != epoch or h.done:
                continue
            slot.spec_inflight = False
            out = arr[i]
            n_draft = len(draft)
            accept = accept_length(draft, out)
            self._spec_lanes += 1
            if n_draft:
                self._spec_drafted_lanes += 1
                self._spec_proposed_n += n_draft
                self._spec_accepted_n += accept
                _spec_proposed.inc(n_draft)
                _spec_accepted.inc(accept)
                _spec_accept_len.observe(accept)
            for tok in (int(t) for t in out[:accept + 1]):
                slot.position += 1
                slot.last_token = tok
                h.push(tok)
                h.gen_count += 1
                h.sched_count = h.gen_count
                self._generated += 1
                self._note_served(h, 1)
                _tokens.labels(kind="decode").inc()
                if h.gen_count >= h.max_new or \
                        (eos is not None and tok == eos):
                    self._finish_slot(slot, "ok")
                    break
        if self._spec_lanes:
            _spec_hit_rate.set(self._spec_drafted_lanes /
                               self._spec_lanes)
        self._publish()

    # -- the loop's own spans (docs/observability.md) ----------------------
    @contextlib.contextmanager
    def _held(self, leaf: str):
        """``with self._lock`` for the scheduler loop, the wait for the
        lock (``llm.tick.lock_wait``) timed apart from what runs under
        it (``leaf``): a scheduler stalled behind the readback thread's
        apply reads differently from one doing its own work."""
        with span("llm.tick.lock_wait"):
            self._lock.acquire()
        try:
            with span(leaf):
                yield
        finally:
            self._lock.release()

    @contextlib.contextmanager
    def _applying(self):
        """``with self._lock`` round the landing of a ready batch, as
        one ``llm.readback.apply`` span whose ``lock_wait_s`` says how
        much of it was the wait for the scheduler to let go."""
        sp = span("llm.readback.apply")
        with sp:
            t = time.perf_counter()
            with self._lock:
                sp.note(lock_wait_s=time.perf_counter() - t)
                yield

    def _timed_prefill_tick(self):
        """The loop's ``_prefill_tick()`` as its ``llm.tick.prefill``
        leaf (its two short lock holds included), ``chunks`` = prompt
        chunks run."""
        sp = span("llm.tick.prefill")
        with sp:
            sp.note(chunks=self._prefill_tick())

    @staticmethod
    def _span_tick_decode(t_dispatch: float, t_ready: float):
        """``llm.tick.decode``: one tick from its dispatch to its
        tokens on the host — the value the ``decode`` phase of
        ``zoo_llm_tick_seconds`` buckets, unbucketed."""
        dur = t_ready - t_dispatch
        emit_span("llm.tick.decode", time.time() - dur, dur,
                  t0=t_dispatch)

    def _span_tick_schedule(self, t0: float, dur: float):
        """``llm.tick.schedule``: the host's scheduling work of one
        pass (sweep/admit + grow/build, prefill excluded) — what the
        ``schedule`` phase of ``zoo_llm_tick_seconds`` buckets."""
        _tick_seconds.labels(phase="schedule").observe(dur)
        emit_span("llm.tick.schedule",
                  time.time() - (time.perf_counter() - t0), dur, t0=t0)

    # -- the tick pipeline -------------------------------------------------
    def _note_busy(self, t_start: float, t_ready: float):
        """Record one tick's device-busy interval and refresh the
        overlap gauge over the recent window (busy intervals are
        clipped to start after the previous ready, so two in-flight
        ticks never double-count the same wall time)."""
        last = self._busy_win[-1][0] if self._busy_win else 0.0
        busy = max(0.0, t_ready - max(t_start, last))
        self._busy_win.append((t_ready, busy))
        ratio = self._window_ratio()
        if ratio is not None:
            _overlap_ratio.set(ratio)

    def _window_ratio(self) -> Optional[float]:
        """THIS engine's device-busy / wall over the recent window.
        ``stats()`` reads this (not the process-global gauge: two
        engines in one process — a hot-swap pair, in-process HA test
        rigs — would otherwise report each other's ratio)."""
        if len(self._busy_win) < 2:
            return None
        win = list(self._busy_win)
        wall = win[-1][0] - win[0][0]
        if wall <= 0:
            return None
        return min(1.0, sum(b for _, b in win[1:]) / wall)

    def _land(self, item):
        """A landing: one dispatched tick's tokens come to the host and
        reach their streams. The readback thread runs it for every item
        the scheduler put on ``_rbq``."""
        kind, batch, snapshot, t_dispatch = item
        try:
            with span("llm.readback.device"):
                arr = self.model.read_tokens(batch)
        except Exception as e:  # noqa: BLE001 — these lanes'
            # tokens are gone (and the donated-cache chain may be
            # poisoned): end the streams loudly and tell the
            # dispatcher to re-seed the device token chain
            with self._lock:
                self._fail_lanes(
                    snapshot if kind == "decode" else
                    [(i, h, ep) for i, h, ep, _ in snapshot], e)
            self._inflight.release()
            self._wake.set()
            return
        t_ready = time.perf_counter()
        self._span_tick_decode(t_dispatch, t_ready)
        _tick_seconds.labels(phase="decode").observe(
            t_ready - t_dispatch)
        self._note_busy(t_dispatch, t_ready)
        with self._applying():
            if kind == "spec":
                self._apply_spec(snapshot, np.asarray(arr))
            else:
                self._apply_tokens(snapshot, arr)
        _tick_seconds.labels(phase="readback").observe(
            time.perf_counter() - t_ready)
        self._decode_steps += 1
        _steps.inc()
        self._tick_flight()
        self._inflight.release()
        self._wake.set()

    def _readback_loop(self):
        while True:
            item = self._rbq.get()
            if item is None:
                return
            self._land(item)

    def _pass(self):
        """A pass of the scheduler: sweep and admit, feed one budget of
        prompt chunks, grow or preempt, build and dispatch ONE decode
        (or verify) batch. Returns what the landing needs — ``(kind,
        batch, snapshot, t_dispatch)``, which the scheduler thread puts
        on ``_rbq`` — or None when nothing was dispatched (no decodable
        lane, a failed dispatch, or the engine is stopping).

        The pipeline is double-buffered: tick N+1's continuing lanes
        consume tick N's ON-DEVICE output batch (``_prev_batch``), so
        the steady-state hot path moves slots x 1 ids to the host and
        nothing to the device but block tables and positions, and the
        host schedules tick N+1 while the device executes tick N and
        the readback thread streams tick N-1's tokens out."""
        with span("llm.tick.lock_wait"), self._lock:
            broken = self._chain_broken
        if broken:
            # drain the pipeline first — every still-in-flight
            # batch chained on the failed computation will fail
            # its own readback and error-finish its own lanes —
            # then re-seed the SURVIVING decode slots (streams
            # never in a failed batch) from their last APPLIED
            # token and restart the device chain from host state
            with span("llm.tick.reseed"):
                grabbed = 0
                while grabbed < 2 and not self._stop.is_set():
                    if self._inflight.acquire(timeout=0.5):
                        grabbed += 1
                with self._lock:
                    self._chain_broken = False
                    for slot in self._slots:
                        if slot.handle is not None and \
                                slot.phase == "decode":
                            slot.use_host = True
                            slot.host_token = slot.last_token
                self._prev_batch = None
                for _ in range(grabbed):
                    self._inflight.release()
            if self._stop.is_set():
                return None
        # bound the pipeline depth: at most 2 ticks in flight.
        # The pass waits for its place BEFORE it feeds a prompt
        # chunk, so that the chunk queues behind one tick and
        # one chunk on the device and not behind two of each
        with span("llm.tick.inflight_wait"):
            while not self._inflight.acquire(timeout=0.5):
                if self._stop.is_set():
                    return None
        t0 = time.perf_counter()
        with self._held("llm.tick.sweep_admit"):
            self._sweep()
            self._admit()
        t1 = time.perf_counter()
        # device prefill runs UNLOCKED: submissions and token
        # readback keep flowing while a long prompt feeds
        self._timed_prefill_tick()
        t2 = time.perf_counter()
        with self._held("llm.tick.grow_build"):
            self._grow_or_preempt()
            built = self._build_verify_tick() if self._spec \
                else self._build_tick()
        self._span_tick_schedule(
            t0, (t1 - t0) + (time.perf_counter() - t2))
        if built is None:
            self._inflight.release()
            # no decodable lane: break the device token chain
            # (every post-idle admission is host-fed anyway).
            # The wait also parks the loop when the waiting queue is
            # only KV-gated; submit() sets _wake, so a fresh request
            # still admits at once
            self._prev_batch = None
            with span("llm.tick.idle"):
                self._wake.wait(0.005)
            self._wake.clear()
            return None
        t_d = time.perf_counter()
        if self._spec:
            # verify batches are host-fed (the accept length
            # decides each seat's next base position, so spec
            # lanes cannot chain on-device). In steady state
            # every ready seat rides ONE batch and the next
            # build waits for its apply — pipeline depth 1,
            # NOT the decode path's double-buffering: a verify
            # pass streams the weights once for ALL seats, so
            # splitting seats across alternating batches would
            # double the HBM bill per token. Speculation must
            # win on accept amortization (which is why it is
            # opt-in, not default); only seats entering decode
            # mid-pass form a second in-flight batch.
            tokens, tables, positions, lanes, snapshot = built
            try:
                with span("llm.tick.dispatch"):
                    batch = self.model.verify_step(
                        tokens, tables, positions, lanes)
            except Exception as e:  # noqa: BLE001
                with self._lock:
                    self._fail_lanes([(i, h, ep) for i, h, ep,
                                      _ in snapshot], e)
                self._inflight.release()
                return None
            self._prev_batch = None
            return ("spec", batch, snapshot, t_d)
        host, use, tables, positions, lanes, snapshot = built
        try:
            with span("llm.tick.dispatch"):
                self._prev_batch = self.model.decode_step(
                    self._prev_batch, host, use, tables, positions,
                    lanes)
        except Exception as e:  # noqa: BLE001 — consuming a
            # poisoned prev batch / cache raises here; fail the
            # built lanes loudly and re-seed instead of letting
            # the scheduler thread die with streams hanging
            with self._lock:
                self._fail_lanes(snapshot, e)
            self._inflight.release()
            return None
        return ("decode", self._prev_batch, snapshot, t_d)

    def _loop(self):
        """The scheduler thread: a pass, and what it dispatched goes to
        the readback thread for its landing."""
        self._rb_thread = threading.Thread(
            target=self._readback_loop, daemon=True,
            name="zoo-llm-readback")
        self._rb_thread.start()
        try:
            while not self._stop.is_set():
                item = self._pass()
                if item is not None:
                    self._rbq.put(item)
        finally:
            self._prev_batch = None
            self._rbq.put(None)
            if self._rb_thread is not None:
                self._rb_thread.join(timeout=10)

    # -- introspection -----------------------------------------------------
    def stats(self) -> Dict:
        out = {"slots": self.model.num_slots,
               # tensor-parallel ways the model spans (1 = replicated
               # single-device weights — the pre-mesh layout)
               "tp": getattr(self.model, "tp", 1),
               # the device the model's cache lives on, as jax reports
               # it (None for the jax-free synthetic model)
               "device": getattr(self.model, "device_info", None),
               "prefill_chunk": self._chunk,
               "decode_attention_impl": getattr(
                   self.model, "decode_attention_impl", "host"),
               "prefill_attention_impl": getattr(
                   self.model, "prefill_attention_impl", "host"),
               # bytes-per-token multipliers (this PR): what the cache
               # stores tokens as (auto's pick is recorded, never
               # silent) and how the prefix cache is doing
               "kv_cache_dtype": getattr(
                   self.model, "kv_cache_dtype", "f32"),
               "kv_cache_dtype_requested": getattr(
                   self.model, "kv_cache_dtype_requested", "f32"),
               "kv_bytes_per_token": getattr(
                   self.model, "kv_bytes_per_token", None),
               # what the dot weights are held as (bfloat16 on a TPU,
               # the caller's dtype elsewhere) and the resident bytes
               # of the whole tree (None for the jax-free synthetic)
               "weight_dtype": getattr(self.model, "weight_dtype", None),
               "weight_bytes": self._weight_bytes,
               "prefix_cache": self.prefix_cache,
               "prefix_hit_tokens": self._hit_tokens,
               "prefix_miss_tokens": self._miss_tokens,
               # speculative decoding (this PR): the active draft
               # budget (0 = off), drafter coverage, and the
               # amortization actually won — accepted / proposed
               "spec_k": self.spec_k if self._spec else 0,
               "spec_ngram": self.spec_ngram,
               "spec_proposed_tokens": self._spec_proposed_n,
               "spec_accepted_tokens": self._spec_accepted_n,
               "spec_accept_rate": (
                   self._spec_accepted_n / self._spec_proposed_n
                   if self._spec_proposed_n else 0.0),
               "spec_draft_hit_rate": (
                   self._spec_drafted_lanes / self._spec_lanes
                   if self._spec_lanes else 0.0),
               # disaggregation (docs/disaggregated_serving.md): the
               # replica's role and kv_migrate traffic both ways —
               # llm_stats publishes these, and the HA client's
               # role/occupancy routing reads them
               "role": self.role,
               "handoffs_out": self._handoffs_out,
               "handoffs_in": self._handoffs_in,
               "parked_handoffs": len(self._handoffs),
               "active": sum(1 for s in self._slots if s.handle),
               "waiting": len(self._wait),
               "decode_steps": self._decode_steps,
               "overlap_ratio": self._window_ratio() or 0.0,
               "generated_tokens": self._generated,
               "qos": self.tenancy.enabled}
        if self.tenancy.enabled:
            with self._lock:
                slots_by = self._slots_by_tenant()
                kv_by = self.allocator.used_by_tenant()
                waiting_by: Dict[str, int] = {}
                for w in self._wait:
                    waiting_by[w.tenant] = waiting_by.get(w.tenant, 0) + 1
                names = set(slots_by) | set(kv_by) | set(waiting_by) \
                    | set(self._tenant_served)
                out["tenants"] = {
                    (t or "default"): {
                        "slots": slots_by.get(t, 0),
                        "kv_blocks": kv_by.get(t, 0),
                        "waiting": waiting_by.get(t, 0),
                        "served_tokens": self._tenant_served.get(t, 0),
                    } for t in sorted(names)}
        out.update(self.allocator.stats())
        if hasattr(self.model, "moe_expert_visits"):
            # a mixture of experts in the decode step: experts that had
            # a live row and the rows computed, over all decode ticks
            out["moe_expert_visits"] = self.model.moe_expert_visits
            out["moe_rows"] = self.model.moe_rows
        if self._stateful:
            # a recurrent state beside the paged cache, and (where the
            # model selects pages) what the decode ticks attended of
            # what was resident
            for key in ("state_bytes", "state_bytes_per_slot",
                        "state_resets", "state_steps",
                        "sparse_pages_attended", "sparse_pages_resident"):
                if hasattr(self.model, key):
                    out[key] = getattr(self.model, key)
        if hasattr(self.model, "operand_transfers"):
            # host -> device operand hand-offs by call: one a dispatch
            out["operand_transfers"] = dict(self.model.operand_transfers)
        if hasattr(self.model, "compile_counts"):
            out["compiles"] = self.model.compile_counts()
        return out
