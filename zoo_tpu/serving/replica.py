"""One serving replica process (spawned by
:class:`zoo_tpu.serving.ha.ReplicaGroup`).

``python -m zoo_tpu.serving.replica --model m.zoo --port 8980`` loads
the model (``synthetic:*`` specs stay jax-free), starts a
:class:`ServingServer` behind a circuit breaker, the obs door
(``/metrics`` + ``/healthz``) on ``--metrics-port``, the heartbeat
thread the supervisor watches, and a SIGTERM drain handler, then blocks
until drained. Kept OUT of the ``zoo_tpu.serving`` package ``__init__``
so ``python -m`` execution never double-imports the module.
"""

from __future__ import annotations

import argparse
import sys
import time


def serve_replica(ns) -> int:
    import faulthandler
    import signal as _sig

    # live stack dumps on demand: `kill -USR1 <replica pid>` writes
    # every thread's Python stack to the replica log — the tool that
    # localizes a GRAY stall (a camped handler thread, a wedged
    # batcher) while it is happening, which no crash handler can see
    faulthandler.register(_sig.SIGUSR1)
    from zoo_tpu.obs.exporters import MetricsExporter
    from zoo_tpu.obs.flight import flight_recorder, record_event
    from zoo_tpu.obs.slo import SLOWatchdog
    from zoo_tpu.serving.server import ServingServer
    from zoo_tpu.util.resilience import (
        CircuitBreaker,
        start_heartbeat_thread,
    )

    start_heartbeat_thread()  # no-op unless the supervisor set the env
    # before any model load can jit: every seat of a group compiles
    # into (and loads from) the one cache of the checkout
    from zoo_tpu.common.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    # black box first: the recorder opens its spill file (when the
    # supervisor armed $ZOO_OBS_POSTMORTEM_DIR) before the model load —
    # a boot crash leaves remains too. The SIGTERM crash handler is
    # installed AFTER the drain handler below so it chains it: dump the
    # bundle, then drain.
    flight_recorder()
    record_event("replica_boot", model=ns.model, port=ns.port)
    # SLO watchdog: a no-op unless ZOO_SLO_* objectives are armed in
    # the replica env; its verdict rides /healthz (exporters) and its
    # breach flips land in the flight ring
    watchdog = SLOWatchdog().start()
    from zoo_tpu.serving.llm.spec import is_llm_spec
    from zoo_tpu.serving.registry import (
        ModelRegistry,
        is_registry_spec,
        parse_registry_spec,
    )
    model = engine = version = None

    def _mount(inner: str):
        """Load the (possibly registry-nested) spec: an llm spec mounts
        the paged-KV continuous-batching engine behind the same TCP
        door (docs/llm_serving.md; generate is then the only inference
        op — hot-swap reload applies to predict models, an llm version
        change goes through replica restart, which the alias
        resolution covers), anything else the predict path."""
        nonlocal model, engine
        if is_llm_spec(inner):
            from zoo_tpu.serving.llm.spec import build_llm_engine
            engine = build_llm_engine(inner)
        else:
            from zoo_tpu.serving.ha import load_serving_model
            model = load_serving_model(inner, batch_size=ns.batch_size)

    if is_registry_spec(ns.model):
        # the alias is re-resolved HERE, at boot — a replica respawned
        # mid-rolling-update therefore comes up on the currently
        # ALIASED version, never a stale one; the pin keeps registry GC
        # off the version for the duration of the load
        root, ref = parse_registry_spec(ns.model)
        reg = ModelRegistry(root)
        with reg.pin(ref) as pinned:
            version, inner = reg.model_spec(pinned)
            _mount(inner)
    else:
        # "a+b" mounts several specs on ONE door (e.g.
        # "synthetic:double:2+synthllm:slots=2" = predict AND the
        # streaming generate op from the same replica — what the
        # mixed-op chaos storm exercises). Split ONLY when every
        # fragment bears a known spec prefix: a plain model PATH may
        # legally contain '+' (ckpt+lora.zoo) and must load verbatim.
        from zoo_tpu.serving.ha import SYNTHETIC_PREFIX
        parts = ns.model.split("+")
        combinable = len(parts) > 1 and all(
            is_llm_spec(p) or p.startswith(SYNTHETIC_PREFIX)
            for p in parts)
        for part in (parts if combinable else [ns.model]):
            _mount(part)
    server = ServingServer(
        model, host=ns.host, port=ns.port, batch_size=ns.batch_size,
        max_wait_ms=ns.max_wait_ms, llm_engine=engine,
        version=version, model_spec=ns.model,
        breaker=CircuitBreaker(failure_threshold=5,
                               recovery_timeout=5.0)).start()
    exporter = None
    if ns.metrics_port >= 0:
        exporter = MetricsExporter(host=ns.host,
                                   port=ns.metrics_port).start()
    server.install_drain_handler()
    # after the drain handler, so SIGTERM dumps the postmortem bundle
    # and THEN chains into the drain; unhandled exceptions dump too
    from zoo_tpu.obs.flight import install_crash_handlers
    install_crash_handlers()
    print(f"REPLICA READY {server.host}:{server.port}"
          + (f" metrics={exporter.port}" if exporter else ""),
          flush=True)
    try:
        while not server._stop.is_set():
            time.sleep(0.2)
    except KeyboardInterrupt:
        server.drain(timeout=10.0)
    watchdog.stop()
    if exporter is not None:
        exporter.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m zoo_tpu.serving.replica",
        description="one serving replica (spawned by ReplicaGroup)")
    ap.add_argument("--model", required=True,
                    help=".zoo file, SavedModel dir, or synthetic:* spec")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="obs /metrics + /healthz door (-1 disables)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    return serve_replica(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
