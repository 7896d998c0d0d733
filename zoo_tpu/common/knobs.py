# zoo-lint: jax-free
# zoo-lint: config-parse
"""The central ``ZOO_*`` knob registry.

Fourteen PRs of growth accreted ~100 environment knobs, each parsed at
its read site and documented (or not) by hand in whichever doc page the
PR touched. This module is the single declarative source of truth the
``zoo-lint`` knob-contract pass (:mod:`zoo_tpu.analysis.knob_pass`)
checks the tree against:

* every ``ZOO_*`` name read anywhere in ``zoo_tpu/`` / ``scripts/``
  must be registered here (rule ``KNOB-UNDECLARED``);
* every registered knob must still be read somewhere (``KNOB-DEAD``);
* every non-``internal`` knob must appear in its owning doc page
  (``KNOB-UNDOCUMENTED``), and the marked knob tables in
  docs/data_plane.md, docs/serving_ha.md, docs/llm_serving.md and
  docs/fault_tolerance.md are *generated* from this registry
  (``KNOB-DOC-DRIFT``; ``scripts/zoo_lint.py --fix-docs`` rewrites
  them).

Registration is metadata-first: most read sites keep their existing
parse helpers (``env_int``/``env_float`` from
:mod:`zoo_tpu.util.resilience`, or a ``# zoo-lint: config-parse``
annotated constructor). For knobs whose *default* must be defined in
exactly one place across modules (the PR 7 "env < spec < kwargs"
promise — ``ZOO_LLM_SPEC_K`` and ``ZOO_LLM_SAMPLING`` used to default
independently in the model and the engine), call :func:`value`, which
parses the environment with the registered type and default.

stdlib-only and jax-free: the lint runner imports this module, and the
lint runner itself is asserted to never pull in jax.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, Iterable, Optional, Tuple

__all__ = ["Knob", "KNOBS", "get", "value", "all_knobs",
           "knobs_for_table", "render_table", "TABLE_DOCS"]

logger = logging.getLogger(__name__)

_TYPES = ("int", "float", "bool", "str")

#: docs whose ZOO_* knob tables are generated from this registry (the
#: marked regions ``<!-- zoo-knob-table:<group> begin/end -->``)
TABLE_DOCS = ("docs/data_plane.md", "docs/serving_ha.md",
              "docs/llm_serving.md", "docs/fault_tolerance.md",
              "docs/disaggregated_serving.md", "docs/multitenancy.md")


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered ``ZOO_*`` environment knob.

    ``doc`` is the owning documentation page (repo-relative); the knob's
    name must appear there. ``table`` places the knob in that page's
    generated knob table (only pages in :data:`TABLE_DOCS` carry one);
    ``also`` cross-lists it in other pages' generated tables — an
    entry is ``(doc, table)`` or ``(doc, table, help)`` when the
    cross-listing needs page-specific semantics (e.g. the shard-plane
    vs serving-plane reading of ``ZOO_WIRE_CRC``). ``internal`` knobs are set by the platform itself (worker env
    wiring, test rigs) and are exempt from the doc requirement — the
    justification lives in ``help``. ``show`` overrides how the default
    renders in doc tables (e.g. ``unset (greedy)``).
    """

    name: str
    type: str
    default: object
    help: str
    doc: Optional[str] = None
    table: Optional[str] = None
    also: Tuple[Tuple[str, str], ...] = ()
    internal: bool = False
    show: Optional[str] = None

    def read(self, env=None):
        """Parse this knob from ``env`` (default ``os.environ``) with
        the registered type and default — the one shared parse path for
        knobs whose default must not be duplicated across modules.

        Semantics match the tree's conventions: unset/empty → default;
        malformed numerics warn and fall back (the
        ``resilience.env_float`` contract); bools treat
        ``0/false/off/no`` as False and anything else as True.
        """
        if env is None:
            env = os.environ
        raw = env.get(self.name)
        if raw is None or raw == "":
            return self.default
        if self.type == "str":
            return raw
        if self.type == "bool":
            return raw.strip().lower() not in ("0", "false", "off", "no")
        try:
            return int(float(raw)) if self.type == "int" else float(raw)
        except ValueError:
            logger.warning("bad %s=%r; using %s", self.name, raw,
                           self.default)
            return self.default

    @property
    def default_str(self) -> str:
        if self.show is not None:
            return self.show
        if self.default is None:
            return "unset"
        if self.type == "bool":
            return "1" if self.default else "0"
        return str(self.default)


KNOBS: Dict[str, Knob] = {}


def _k(name: str, type: str, default, help: str, doc=None, table=None,
       also=(), internal=False, show=None):
    if name in KNOBS:
        raise ValueError(f"duplicate knob registration {name!r}")
    if type not in _TYPES:
        raise ValueError(f"{name}: unknown knob type {type!r}")
    if not internal and doc is None:
        raise ValueError(f"{name}: non-internal knobs need an owning doc")
    KNOBS[name] = Knob(name, type, default, help, doc, table,
                       tuple(also), internal, show)


def get(name: str) -> Knob:
    """The registered :class:`Knob`; raises ``KeyError`` with a fix
    hint for unregistered names."""
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"{name} is not in the knob registry "
            "(zoo_tpu/common/knobs.py) — register it with its type, "
            "default and owning doc") from None


def value(name: str, env=None):
    """Parse knob ``name`` from the environment (see
    :meth:`Knob.read`). The registry entry is the single owner of the
    knob's default."""
    return get(name).read(env)


def all_knobs() -> Tuple[Knob, ...]:
    return tuple(KNOBS.values())


def knobs_for_table(doc: str, table: str,
                    registry: Optional[Dict[str, Knob]] = None
                    ) -> Tuple[Tuple[Knob, str], ...]:
    """``(knob, help text)`` rows for the
    ``<!-- zoo-knob-table:<table> -->`` region of ``doc`` — owned
    entries first, then cross-listed ones (which may carry a
    page-specific help override), both in registration order."""
    knobs = (registry if registry is not None else KNOBS).values()
    rows = [(k, k.help) for k in knobs
            if k.doc == doc and k.table == table]
    for k in knobs:
        for entry in k.also:
            if tuple(entry[:2]) == (doc, table):
                rows.append((k, entry[2] if len(entry) > 2
                             else k.help))
    return tuple(rows)


def render_table(doc: str, table: str,
                 registry: Optional[Dict[str, Knob]] = None) -> str:
    """The generated markdown rows (no header) for one knob table."""
    return "\n".join(
        f"| `{k.name}` | {k.default_str} | {help} |"
        for k, help in knobs_for_table(doc, table, registry))


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------
# Ordering inside each block is the order rows render in the generated
# doc tables.

_DP = "docs/data_plane.md"
_HA = "docs/serving_ha.md"
_LLM = "docs/llm_serving.md"
_FT = "docs/fault_tolerance.md"
_OBS = "docs/observability.md"
_LC = "docs/model_lifecycle.md"
_MC = "docs/multichip.md"
_DISAGG = "docs/disaggregated_serving.md"
_TEN = "docs/multitenancy.md"

# -- data plane (docs/data_plane.md, generated table "data-plane") ----------
_k("ZOO_SHARD_FETCH_CONCURRENCY", "int", 4,
   "initial threads fanning multi-get chunks across peers", _DP,
   "data-plane")
_k("ZOO_SHARD_POOL_SIZE", "int", 4,
   "idle pooled connections kept per peer", _DP, "data-plane")
_k("ZOO_SHARD_MULTIGET", "int", 32,
   "initial gids per multi-get chunk (retry granularity)", _DP,
   "data-plane")
_k("ZOO_SHARD_LANE", "str", "auto",
   "`auto` probe-and-prefer shm on same host; `tcp` never negotiate "
   "the lane; `shm` force (loud failure otherwise)", _DP, "data-plane")
_k("ZOO_SHARD_WIRE_DTYPE", "str", "off",
   "`bf16`/`int8` narrowing of f32 payloads — LOSSY, opt-in", _DP,
   "data-plane")
_k("ZOO_SHARD_WIRE_COMPRESS", "str", "off",
   "`zlib`/`lz4` per-array compression (kept only when smaller)", _DP,
   "data-plane")
_k("ZOO_SHARD_READAHEAD", "str", "adaptive",
   "`static` pins concurrency/chunk to their initial values", _DP,
   "data-plane")
_k("ZOO_SHARD_SHM_DIR", "str", None,
   "segment directory (falls back to the tempdir)", _DP, "data-plane",
   show="/dev/shm")
_k("ZOO_FEED_STAGING", "str", "auto",
   "rotating staging buffers in the fit feed (`off` to disable; "
   "buffers are allocated off XLA's zero-copy alignment and each is "
   "probed — auto-disabled unless `device_put` provably copies every "
   "one)", _DP, "data-plane")

# -- serving HA (docs/serving_ha.md, generated table "serve") ---------------
_k("ZOO_SERVE_REQUEST_TIMEOUT", "float", 120.0,
   "server reply bound (seconds) for requests with **no** propagated "
   "deadline", _HA, "serve")
_k("ZOO_SERVE_HANDSHAKE_TIMEOUT", "float", 10.0,
   "TLS handshake bound (seconds)", _HA, "serve")
_k("ZOO_SERVE_MAX_QUEUE", "int", 1024,
   "batcher queue bound; `0` = unbounded (no early shed)", _HA, "serve")
_k("ZOO_SERVE_DEDUP_CACHE", "int", 1024,
   "request-id LRU entries; `0` disables dedup", _HA, "serve")
_k("ZOO_SERVE_DEADLINE_MS", "float", 30000.0,
   "HA client default end-to-end budget; `<= 0` = none", _HA, "serve")
_k("ZOO_SERVE_HEDGE", "bool", True,
   "HA client hedging on/off", _HA, "serve")
_k("ZOO_SERVE_HEDGE_DELAY_MS", "float", 0.0,
   "hedge delay; `0` = track p95 (50 ms until warmed)", _HA, "serve")
_k("ZOO_SERVE_BREAKER_RECOVERY", "float", 1.0,
   "client-side per-replica breaker recovery (seconds)", _HA, "serve")
_k("ZOO_SERVE_DRAIN_TIMEOUT_S", "float", 30.0,
   "graceful-drain budget; also the per-replica in-flight budget in "
   "`rolling_update`", _HA, "serve")
_k("ZOO_SERVE_AB_SPLIT", "str", "",
   "client A/B split, e.g. `v2=0.1,v3=0.05` (rest unpinned)", _LC,
   show="—")

# -- LLM serving (docs/llm_serving.md, generated table "llm") ---------------
_k("ZOO_LLM_SLOTS", "int", 8,
   "decode slots (the fixed decode batch shape)", _LLM, "llm")
_k("ZOO_LLM_BLOCK_SIZE", "int", 16, "tokens per KV block", _LLM, "llm")
_k("ZOO_LLM_KV_BLOCKS", "int", 128,
   "pool size (block 0 is reserved)", _LLM, "llm")
_k("ZOO_LLM_MAX_BLOCKS_PER_SEQ", "int", 32,
   "block-table width = context ceiling / block_size", _LLM, "llm")
_k("ZOO_LLM_PREFILL_BUCKETS", "str", "32/128/512",
   "prompt-length buckets (one prefill executable each)", _LLM, "llm",
   show="`32/128/512`")
_k("ZOO_LLM_PREFILL_CHUNK", "int", 0,
   "chunked prefill: feed prompts in N-token slices interleaved with "
   "decode; collapses the bucket census to ONE chunk executable",
   _LLM, "llm", show="0 (off)")
_k("ZOO_LLM_PREFILL_BUDGET", "int", 0,
   "prompt tokens fed per tick when chunking", _LLM, "llm",
   show="chunk size")
_k("ZOO_LLM_PREFIX_CACHE", "bool", False,
   "content-hash block reuse with copy-on-write (spec: "
   "`prefix_cache=1`): a shared prompt prefix costs its KV blocks "
   "once across streams, prefill starts at the first uncached token",
   _LLM, "llm", show="0 (off)")
_k("ZOO_LLM_KV_DTYPE", "str", "f32",
   "KV cache storage dtype (spec: `kv=`): `bf16` halves cache bytes, "
   "`int8` halves again with per-block-row absmax scales, `auto` "
   "picks int8 on TPU and records the choice", _LLM, "llm",
   show="`f32`")
_k("ZOO_LLM_SPEC_K", "int", 0,
   "speculative decoding (spec: `spec_k=N`): the verify executable "
   "scores up to N drafted tokens per slot per pass; per-request "
   "`spec_k` on the wire caps (never raises) it", _LLM, "llm",
   show="0 (off)")
_k("ZOO_LLM_SPEC_NGRAM", "int", 3,
   "longest suffix n-gram the prompt-lookup drafter matches (spec: "
   "`spec_ngram=N`)", _LLM, "llm")
_k("ZOO_LLM_SAMPLING", "str", "",
   "deployment-default sampling, e.g. "
   "`temperature=0.8,top_k=40,top_p=0.95`; per-request params "
   "override", _LLM, "llm", show="unset (greedy)")
_k("ZOO_LLM_DECODE_IMPL", "str", "auto",
   "decode attention kernel: `flash` (paged Pallas) / `dense` (gather "
   "reference)", _LLM, "llm", show="`auto`")
_k("ZOO_LLM_PREFILL_IMPL", "str", "auto",
   "chunk/verify attention kernel (spec: `prefill_impl=`): `flash` "
   "(paged flash-prefill Pallas) / `dense` (gather anchor)", _LLM,
   "llm", show="`auto`")
_k("ZOO_LLM_DECODE_SPLITS", "int", 4,
   "split-KV parallelism width of the flash-decode kernel", _LLM,
   "llm")
_k("ZOO_LLM_SEED", "int", 0,
   "weight seed for spec-built params", _LLM, "llm")
_k("ZOO_LLM_EOS", "int", None,
   "eos token id (stops a stream early)", _LLM, "llm", show="unset")
_k("ZOO_LLM_MAX_WAITING", "int", 256,
   "waiting-queue bound (overflow sheds retryable)", _LLM, "llm")
_k("ZOO_LLM_FINISHED_CACHE", "int", 256,
   "finished-stream dedup LRU", _LLM, "llm")
_k("ZOO_LLAMA_FLASH_MIN_SEQ", "int", 512,
   "seq length where `attention_impl=\"auto\"` switches to the Pallas "
   "flash kernel", _LLM, "llm")
_k("ZOO_LLAMA_ATTN_IMPL", "str", "",
   "force `dense`/`flash`/`ring` for A/B runs", _LLM, "llm",
   show="unset")

# -- disaggregated serving (docs/disaggregated_serving.md, table "disagg") --
_k("ZOO_LLM_ROLE", "str", "mixed",
   "replica role (spec: `role=`): `prefill` parks finished prompts "
   "for `kv_migrate` handoff instead of decoding, `decode` adopts "
   "migrated KV, `mixed` does both", _DISAGG, "disagg",
   show="`mixed`")
_k("ZOO_KV_MIGRATE_TTL_MS", "float", 2000.0,
   "how long a parked handoff (prefill side) or a staged adoption "
   "payload (decode side) survives before the sweep frees its blocks",
   _DISAGG, "disagg")
_k("ZOO_KV_MIGRATE_MIN_TOKENS", "int", 16,
   "prompts shorter than this skip the handoff path and run "
   "mixed/decode-local prefill (migration overhead isn't worth it)",
   _DISAGG, "disagg")
_k("ZOO_KV_MIGRATE_CHUNK_BLOCKS", "int", 4,
   "KV blocks packed per `kv_migrate` block frame on the wire",
   _DISAGG, "disagg")
_k("ZOO_ROUTE_PREFIX_WEIGHT", "float", 1.0,
   "routing weight of the prefix-affinity signal (estimated cached "
   "prefix fraction at the seat) in the HA client's plan order",
   _DISAGG, "disagg")
_k("ZOO_ROUTE_OCC_WEIGHT", "float", 0.5,
   "routing weight of decode occupancy (busy slots / total slots "
   "from `llm_stats`) — penalizes loaded seats", _DISAGG, "disagg")

# -- multi-tenant QoS (docs/multitenancy.md, table "tenancy") ---------------
_k("ZOO_QOS", "bool", True,
   "`0` disables the whole tenancy layer even with a tenant config — "
   "admission, fairness, preemption, and cache partitioning all fall "
   "back to the anonymous single-pool behavior", _TEN, "tenancy")
_k("ZOO_TENANT_CONFIG", "str", "",
   "tenant spec: `name:field=..,..;name2:..` with fields `weight` "
   "(fair-share), `class` (priority, lower preempts higher), `rate` "
   "(req/s token bucket, 0 = unlimited), `burst` (bucket depth), `kv` "
   "(live KV-block quota), `slots` (decode-slot quota); empty = "
   "tenancy off", _TEN, "tenancy", show="— (tenancy off)")
_k("ZOO_TENANT_DEFAULT_WEIGHT", "float", 1.0,
   "fair-share weight for unlisted/unlabeled tenants", _TEN, "tenancy")
_k("ZOO_TENANT_DEFAULT_CLASS", "int", 1,
   "priority class for unlisted/unlabeled tenants (lower = more "
   "important)", _TEN, "tenancy")
_k("ZOO_TENANT_DEFAULT_RATE", "float", 0.0,
   "admission rate (req/s) for unlisted/unlabeled tenants (0 = "
   "unlimited)", _TEN, "tenancy")
_k("ZOO_TENANT", "str", None,
   "the tenant id `HAServingClient` stamps on every request it sends "
   "(per-call `tenant=` overrides)", _TEN, "tenancy", show="unset")
_k("ZOO_TENANT_AB_PINS", "str", "",
   "per-tenant version pins for the HA client, `gold=v2,free=v1` — a "
   "pinned tenant's traffic bypasses the fractional "
   "`ZOO_SERVE_AB_SPLIT`", _TEN, "tenancy", show="—")
_k("ZOO_TENANT_BACKOFF_CAP_MS", "float", 2000.0,
   "ceiling on how long the HA client honors a rate-shed "
   "`retry_after_ms` hint before retrying", _TEN, "tenancy")
_k("ZOO_SLO_TENANT_SHED_RATE", "float", None,
   "per-tenant shed-rate ceiling (0..1) the SLO watchdog evaluates "
   "each window, published as `zoo_tenant_burn_rate`", _TEN, "tenancy",
   show="off")

# -- training guard (docs/fault_tolerance.md, generated table "guard") ------
_k("ZOO_GUARD", "bool", True,
   "`0` disables the guard estimators attach", _FT, "guard")
_k("ZOO_GUARD_MAX_SKIPS", "int", 8,
   "consecutive skipped steps before rollback", _FT, "guard")
_k("ZOO_GUARD_SPIKE_FACTOR", "float", 10.0,
   "window-loss multiple over the rolling median that triggers "
   "rollback", _FT, "guard")
_k("ZOO_GUARD_WINDOW", "int", 32,
   "rolling-loss window (boundaries)", _FT, "guard")
_k("ZOO_GUARD_MIN_WINDOW", "int", 5,
   "boundaries before spike detection arms", _FT, "guard")
_k("ZOO_GUARD_ROLLBACK_BUDGET", "int", 3,
   "rollbacks before `TrainingDiverged`", _FT, "guard")
_k("ZOO_GUARD_LR_BACKOFF", "float", 0.5,
   "LR multiplier on rollback resume", _FT, "guard")
_k("ZOO_GUARD_CHECK_EVERY", "int", 1,
   "read the device counter every N boundaries", _FT, "guard")
_k("ZOO_GUARD_MAX_GNORM", "float", None,
   "optional hard gradient-norm ceiling", _FT, "guard", show="off")
_k("ZOO_GUARD_QUARANTINE", "str", None,
   "journal path", _FT, "guard",
   show="`<model_dir>/guard/quarantine.jsonl`")
_k("ZOO_PREEMPT", "str", "SIGTERM",
   "preemption signal name; `none` disables", _FT, "guard",
   show="`SIGTERM`")

# -- gray failure / chaos (docs/fault_tolerance.md, table "gray") -----------
_k("ZOO_WIRE_CRC", "bool", True,
   "CRC trailers on both wire planes (negotiated; `0` disables)", _FT,
   "gray",
   also=((_DP, "data-plane",
          "per-array CRC trailer over the transported bytes (shm "
          "segments included), negotiated in the hello; a mismatch "
          "refetches the chunk instead of decoding garbage "
          "([fault_tolerance.md §6](fault_tolerance.md))"),
         (_HA, "serve",
          "CRC trailer on every serving frame (negotiated per "
          "connection; [fault_tolerance.md §6](fault_tolerance.md))")))
_k("ZOO_EJECT", "bool", True,
   "gray-failure ejection in the HA client", _FT, "gray")
_k("ZOO_EJECT_FACTOR", "float", 3.0,
   "outlier bar: multiple of the healthy-peer median EWMA", _FT,
   "gray")
_k("ZOO_EJECT_MIN_MS", "float", 25.0,
   "absolute floor — nothing under it is an outlier", _FT, "gray")
_k("ZOO_EJECT_MIN_SAMPLES", "int", 5,
   "samples before a seat can be classified", _FT, "gray")
_k("ZOO_EJECT_EWMA_ALPHA", "float", 0.35,
   "latency/error EWMA smoothing", _FT, "gray")
_k("ZOO_EJECT_PROBATION_S", "float", 1.5,
   "sustained degradation before probation → ejected", _FT, "gray")
_k("ZOO_EJECT_PROBE_S", "float", 0.5,
   "canary cadence on probation seats", _FT, "gray")
_k("ZOO_EJECT_READMIT_S", "float", 1.0,
   "ejected → probation backoff base (doubles per consecutive "
   "ejection)", _FT, "gray")
_k("ZOO_EJECT_READMIT_MAX_S", "float", 30.0, "backoff cap", _FT, "gray")
_k("ZOO_EJECT_ERROR_RATE", "float", 0.6,
   "EWMA error rate that triggers probation on its own", _FT, "gray")
_k("ZOO_QUARANTINE_PROBE_S", "float", 5.0,
   "quarantine probe-respawn backoff base", _FT, "gray")
_k("ZOO_QUARANTINE_PROBE_MAX_S", "float", 60.0,
   "probe backoff cap", _FT, "gray")
_k("ZOO_QUARANTINE_HEAL_S", "float", 30.0,
   "probe uptime that re-admits the seat", _FT, "gray")
_k("ZOO_CHAOS_SPEC", "str", "",
   "the storm's fault schedule (grammar above)", _FT, "gray", show="—")
_k("ZOO_CHAOS_SEED", "int", 0,
   "seed resolving every draw in the schedule", _FT, "gray")
_k("ZOO_CHAOS_ALLOW", "bool", False,
   "`1` lets a replica honor wire `chaos` ops", _FT, "gray",
   show="unset")
_k("ZOO_FAULT_SEED", "int", None,
   "deterministic seed for the fault-injection registry's p-draws "
   "(replay-exact chaos schedules)", _FT, "gray", show="unset")
_k("ZOO_HEARTBEAT_FILE", "str", None,
   "per-process heartbeat stamp file (set by the supervisor for every "
   "worker; hung-worker detection reads its age)", _FT, "gray",
   show="unset")
_k("ZOO_HEARTBEAT_INTERVAL", "float", 1.0,
   "heartbeat stamp cadence (seconds)", _FT, "gray")

# -- observability (docs/observability.md, hand-maintained table) -----------
_k("ZOO_TRACE_DIR", "str", None,
   "trace-span JSONL sink directory", _OBS, show="unset (off)")
_k("ZOO_OBS_FLIGHT_CAP", "int", 512,
   "flight ring capacity (0 = recorder off)", _OBS)
_k("ZOO_OBS_POSTMORTEM_DIR", "str", None,
   "bundle dir + arms the continuous spill", _OBS, show="unset")
_k("ZOO_OBS_SNAPSHOT", "str", None,
   "metrics JSONL flushed on drain/exit", _OBS, show="unset")
_k("ZOO_SLO_TTFT_P99_S", "float", None,
   "p99 time-to-first-token ceiling (s)", _OBS, show="off")
_k("ZOO_SLO_INTER_TOKEN_P99_S", "float", None,
   "p99 inter-token gap ceiling (s)", _OBS, show="off")
_k("ZOO_SLO_ERROR_RATE", "float", None,
   "served error-rate ceiling (0..1)", _OBS, show="off")
_k("ZOO_SLO_SHED_RATE", "float", None,
   "admission shed-rate ceiling (0..1)", _OBS, show="off")
_k("ZOO_SLO_KV_UTIL", "float", None,
   "KV-block pool utilization ceiling (0..1)", _OBS, show="off")
_k("ZOO_SLO_SPEC_ACCEPT_FLOOR", "float", None,
   "speculative accept-rate FLOOR (0..1)", _OBS, show="off")
_k("ZOO_SLO_WINDOW_S", "float", 60.0,
   "rolling evaluation window (s)", _OBS)
_k("ZOO_SLO_INTERVAL_S", "float", 5.0, "evaluation period (s)", _OBS)
_k("ZOO_SLO_FAIL_HEALTHZ", "bool", False,
   "1 = an active breach turns `/healthz` 503", _OBS)

# -- lifecycle (docs/model_lifecycle.md, hand-maintained table) -------------
_k("ZOO_REGISTRY_KEEP", "int", 8,
   "registry retention bound (never evicts aliased/pinned versions)",
   _LC)
_k("ZOO_CKPT_KEEP", "int", 5,
   "checkpoint retention bound (steps + `.corrupt` dirs; newest "
   "verified step protected)", _LC)
_k("ZOO_GATE_SAMPLE", "float", 0.25,
   "fraction of live traffic mirrored to the canary", _LC)
_k("ZOO_GATE_WINDOW", "int", 32,
   "mirrored samples needed for a promotion decision", _LC)
_k("ZOO_GATE_MAX_ERROR_RATE", "float", 0.02,
   "canary error-rate bound", _LC)
_k("ZOO_GATE_MAX_LATENCY_RATIO", "float", 3.0,
   "canary p50 / incumbent p50 bound", _LC)
_k("ZOO_GATE_MAX_LOSS_RATIO", "float", 1.2,
   "canary loss / incumbent loss bound", _LC)

# -- multichip (docs/multichip.md, hand-maintained table) -------------------
_k("ZOO_MESH_DATA", "int", None, "mesh `data` axis size", _MC,
   show="unset")
_k("ZOO_MESH_FSDP", "int", None, "mesh `fsdp` axis size", _MC,
   show="unset")
_k("ZOO_MESH_MODEL", "int", None, "mesh `model` axis size", _MC,
   show="unset")
_k("ZOO_MESH_SEQ", "int", None, "mesh `seq` axis size", _MC,
   show="unset")
_k("ZOO_MESH_EXPERT", "int", None, "mesh `expert` axis size", _MC,
   show="unset")
_k("ZOO_MESH_PIPE", "int", None, "mesh `pipe` axis size", _MC,
   show="unset")
_k("ZOO_FUSED_OPTIM", "bool", False,
   "AdamW takes the fused direct-apply path", _MC)
_k("ZOO_LLM_TP", "int", 1,
   "tensor-parallel ways for `llama:*` serving specs", _MC)
_k("ZOO_PLAN", "str", "auto",
   "default sharding plan for `compile()` when no `plan=` is passed "
   "(`auto`, or a registered plan: `transformer`, `pipeline`, `moe`, "
   "...)", _MC)
_k("ZOO_PIPE_MICROBATCHES", "int", 0,
   "GPipe microbatch count for the `pipeline` plan (`0` = one per "
   "pipeline stage)", _MC, show="0 (pipe size)")
_k("ZOO_MOE_CAPACITY", "float", 1.25,
   "default expert capacity factor for MoE dispatch "
   "(`ops/moe.py`; capacity = factor * tokens / experts)", _MC)

# -- serving misc (docs/serving.md / docs/orca.md prose) --------------------
_k("ZOO_MODEL_SECRET", "str", None,
   "model decryption secret for encrypted artifacts",
   "docs/serving.md", show="unset")
_k("ZOO_MODEL_SALT", "str", None,
   "salt paired with `ZOO_MODEL_SECRET`", "docs/serving.md",
   show="unset")
_k("ZOO_MODEL_ENC_MODE", "str", "cbc",
   "cipher mode for encrypted model artifacts (`cbc`/`gcm`)",
   "docs/serving.md")
_k("ZOO_INT8_MODE", "str", "auto",
   "int8 quantization policy for `InferenceModel` loads: `auto` "
   "microbenches int8 vs float and keeps the winner, `force`, `off`",
   "docs/serving.md")
_k("ZOO_SPARK_STAGING", "str", None,
   "staging directory for Spark-bridge ingestion", "docs/orca.md",
   show="unset")
_k("ZOO_NUM_CORES", "int", None,
   "local-mode core count used when no explicit `cores=` is passed",
   "docs/orca.md", show="unset")

# -- kernels ---------------------------------------------------------------
_k("ZOO_PALLAS_FORCE_INTERPRET", "bool", False,
   "run every Pallas kernel under the interpreter (CPU correctness "
   "tests of TPU kernels)", "docs/parallelism.md")
_KN = "docs/kernels.md"
_k("ZOO_CONV_IMPL", "str", "auto",
   "conv2d backend: `auto` (the XLA reference conv on every platform "
   "— the implicit-GEMM kernel cannot be differentiated), `pallas` "
   "(the kernel, forward only), `reference`",
   _KN)
_k("ZOO_INT8_MATMUL", "str", "auto",
   "int8 GEMM backend: `auto`/`fused` (one-kernel quantize+dot+"
   "dequant), `unfused` (XLA quantize pass + dequant matmul)", _KN)

# -- internal coordination (set by the platform itself, not operators) ------
_k("ZOO_PROCESS_ID", "int", None, internal=True,
   help="worker rank; set by launch_local_cluster for each worker",
   doc="docs/orca.md")
_k("ZOO_NUM_PROCESSES", "int", None, internal=True,
   help="world size; set by launch_local_cluster for each worker",
   doc="docs/orca.md")
_k("ZOO_COORDINATOR_ADDRESS", "str", None, internal=True,
   help="jax coordination-service address; set by "
        "launch_local_cluster", doc="docs/orca.md")
_k("ZOO_ELASTIC_ATTEMPT", "int", 0, internal=True,
   help="relaunch attempt counter run_elastic stamps into worker env")
_k("ZOO_TPU_DISABLE_NATIVE", "bool", False, internal=True,
   help="kill switch for the optional native acceleration module "
        "(debug/bisect aid)")
