"""Cluster Serving launcher CLI (reference: the ``cluster-serving-start``
script + ``config.yaml`` read by ``ClusterServingHelper.scala:292``).

``python -m zoo_tpu.serving.run --model m.zoo [--config config.yaml]``
loads the model into an :class:`InferenceModel`, starts the serving loop
against Redis (external, or the embedded RESP server when nothing is
listening) and the HTTP frontend, then blocks until SIGINT/SIGTERM.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from zoo_tpu.common.knobs import value as knob_value


def _load_config(path):
    """Minimal config.yaml reader (flat ``key: value`` pairs under the
    reference's section names; no yaml dependency)."""
    out = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if ":" in line:
                k, v = line.split(":", 1)
                if v.strip():
                    out[k.strip()] = v.strip().strip("'\"")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m zoo_tpu.serving.run")
    ap.add_argument("--model", required=False,
                    help="serialized zoo model (.zoo) or TF SavedModel dir")
    ap.add_argument("--config", help="reference-style config.yaml "
                                     "(modelPath/redis/.. keys)")
    ap.add_argument("--redis-host", default="localhost")
    ap.add_argument("--redis-port", type=int, default=6379)
    ap.add_argument("--http-port", type=int, default=10020)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--concurrent-num", type=int, default=4)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--redis-mode", default="auto",
                    choices=["auto", "external", "embedded"],
                    help="external: wait for a real Redis (up to "
                         "--redis-wait s, then fail); embedded: always "
                         "boot the in-process RESP server; auto: probe "
                         "briefly, then fall back to embedded")
    ap.add_argument("--redis-wait", type=float, default=60.0)
    ap.add_argument("--tcp-replicas", type=int, default=0,
                    help="HA mode: serve over the TCP door via a "
                         "ReplicaGroup of N supervised replica "
                         "processes instead of the Redis pipeline "
                         "(docs/serving_ha.md); each replica loads the "
                         "model itself")
    ap.add_argument("--tcp-port", type=int, default=0,
                    help="base TCP port for --tcp-replicas (replica i "
                         "serves tcp-port+i; 0 = ephemeral ports, "
                         "printed at startup)")
    ap.add_argument("--tcp-max-restarts", type=int, default=3,
                    help="per-replica respawn budget in HA mode")
    ap.add_argument("--registry", default=None,
                    help="serve from a versioned model registry root "
                         "(docs/model_lifecycle.md): replicas resolve "
                         "--alias at boot and hot-swap via "
                         "ReplicaGroup.rolling_update; shorthand for "
                         "--model registry:<root>:<alias>")
    ap.add_argument("--alias", default="prod",
                    help="registry alias to serve (with --registry; "
                         "default prod)")
    ap.add_argument("--encrypted", action="store_true",
                    help="the model file is encrypted at rest (reference "
                         "trusted serving); key material comes from "
                         "--model-secret/--model-salt or the "
                         "ZOO_MODEL_SECRET/ZOO_MODEL_SALT env")
    ap.add_argument("--model-secret", default=None)
    ap.add_argument("--model-salt", default=None)
    ap.add_argument("--model-enc-mode", default=None,
                    choices=["cbc", "gcm"],
                    help="cipher mode of the encrypted model "
                         "(ZOO_MODEL_ENC_MODE env; default cbc)")
    ns = ap.parse_args(argv)

    # before the model load can jit; spawned replicas inherit the place
    from zoo_tpu.common.compile_cache import ensure_compile_cache
    ensure_compile_cache()

    if ns.config:
        cfg = _load_config(ns.config)
        ns.model = ns.model or cfg.get("modelPath") or cfg.get("path")
        ns.redis_host = cfg.get("redisHost", ns.redis_host)
        ns.redis_port = int(cfg.get("redisPort", ns.redis_port))
        ns.batch_size = int(cfg.get("batchSize", ns.batch_size))
    if ns.registry:
        if ns.model:
            ap.error("--registry and --model are mutually exclusive "
                     "(--registry IS the model source)")
        ns.model = f"registry:{ns.registry}:{ns.alias}"
        if ns.tcp_replicas <= 0:
            ap.error("--registry needs the HA TCP mode "
                     "(--tcp-replicas N): hot-swap reload lives on "
                     "the replica wire")
    if not ns.model:
        ap.error("--model (or a config with modelPath, or --registry) "
                 "is required")

    if ns.tcp_replicas > 0:
        # HA mode: the replicas load the model themselves (one process
        # each, supervised + respawned on a fixed port); this process is
        # only the group supervisor — no Redis, no HTTP frontend
        from zoo_tpu.obs.flight import install_crash_handlers
        from zoo_tpu.serving.ha import ReplicaGroup
        ports = [ns.tcp_port + i for i in range(ns.tcp_replicas)] \
            if ns.tcp_port else None
        group = ReplicaGroup(ns.model, num_replicas=ns.tcp_replicas,
                             ports=ports, batch_size=ns.batch_size,
                             max_restarts=ns.tcp_max_restarts)
        group.start()
        print("serving-ha: endpoints "
              + ",".join(f"{h}:{p}" for h, p in group.endpoints()),
              flush=True)
        stop = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: stop.set())
        # postmortem bundle on a supervisor crash too (chains the stop
        # handlers just installed; no-op without $ZOO_OBS_FLIGHT_CAP)
        install_crash_handlers()
        stop.wait()
        # replicas drain on their own SIGTERM (ProcessMonitor.stop
        # group-kills with SIGTERM first, SIGKILL after a grace)
        group.stop()
        # AFTER the stop: the shutdown SIGTERM is what makes each
        # replica dump its final postmortem bundle — harvesting first
        # would strand those in the flight dirs (docs/observability.md)
        group.harvest_postmortems()
        return 0

    from zoo_tpu.pipeline.inference.inference_model import InferenceModel
    from zoo_tpu.serving.client import InputQueue
    from zoo_tpu.serving.cluster_serving import ClusterServing, FrontEnd
    from zoo_tpu.serving.redis_embedded import EmbeddedRedis

    # Redis resolution (the reference's test mode runs embedded-redis):
    # external Redis may come up after us (compose depends_on orders
    # start, not readiness), so probe with retries before any fallback.
    import socket as _socket
    import time as _time

    def _reachable() -> bool:
        try:
            with _socket.create_connection(
                    (ns.redis_host, ns.redis_port), timeout=1):
                return True
        except OSError:
            return False

    embedded = None
    if ns.redis_mode == "embedded":
        embedded = EmbeddedRedis(host="127.0.0.1",
                                 port=ns.redis_port).start()
        ns.redis_host, ns.redis_port = "127.0.0.1", embedded.port
    else:
        wait = ns.redis_wait if ns.redis_mode == "external" else 3.0
        deadline = _time.time() + wait
        while not _reachable() and _time.time() < deadline:
            _time.sleep(0.5)
        if not _reachable():
            if ns.redis_mode == "external":
                print(f"no Redis at {ns.redis_host}:{ns.redis_port} "
                      f"after {wait:.0f}s", file=sys.stderr)
                return 1
            embedded = EmbeddedRedis(host="127.0.0.1",
                                     port=ns.redis_port).start()
            ns.redis_host, ns.redis_port = "127.0.0.1", embedded.port
    if embedded is not None:
        print(f"embedded RESP server on :{embedded.port}", flush=True)

    im = InferenceModel(supported_concurrent_num=ns.concurrent_num)
    import os
    if os.path.isdir(ns.model):
        im.load_tf(ns.model, batch_size=ns.batch_size)
    elif ns.encrypted or ns.model_secret is not None:
        # encrypted at rest (reference trusted-realtime-ml): decrypted in
        # memory only; key material arrives via flags or env (a KMS hook
        # in production), never in the model file's directory. Plaintext
        # models are NEVER rerouted here by a stray env var — the branch
        # needs the explicit --encrypted/--model-secret opt-in.
        secret = ns.model_secret or knob_value("ZOO_MODEL_SECRET")
        salt = ns.model_salt or knob_value("ZOO_MODEL_SALT")
        if not secret:
            ap.error("--encrypted needs --model-secret or "
                     "ZOO_MODEL_SECRET")
        mode = (ns.model_enc_mode
                or knob_value("ZOO_MODEL_ENC_MODE"))
        if mode not in ("cbc", "gcm"):
            ap.error(f"invalid cipher mode {mode!r} (cbc|gcm)")
        im.load_encrypted(ns.model, secret, salt or "", mode=mode,
                          batch_size=ns.batch_size, quantize=ns.quantize)
    else:
        im.load(ns.model, batch_size=ns.batch_size,
                quantize=ns.quantize)

    serving = ClusterServing(model=im, redis_host=ns.redis_host,
                             redis_port=ns.redis_port,
                             batch_size=ns.batch_size).start()
    fe = FrontEnd(serving, InputQueue(host=ns.redis_host,
                                      port=ns.redis_port),
                  host="0.0.0.0", port=ns.http_port).start()
    print(f"serving: redis {ns.redis_host}:{ns.redis_port}  "
          f"http {fe.host}:{fe.port}", flush=True)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    # graceful drain: close the HTTP front door first (no new work
    # enters), let the engine finish its in-flight batch (stop() joins
    # the worker loop), then flush the final metrics snapshot so the
    # request tallies survive the process (docs/fault_tolerance.md)
    fe.stop()
    serving.stop()
    snap = knob_value("ZOO_OBS_SNAPSHOT")
    if snap:
        try:
            from zoo_tpu.obs.exporters import write_snapshot
            write_snapshot(snap)
        except Exception as e:  # noqa: BLE001 — flush is best-effort
            print(f"metrics snapshot flush failed: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
