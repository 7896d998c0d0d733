"""One-line cluster bootstrap for TPU.

Rebuild of ``init_orca_context`` / ``stop_orca_context``
(reference: ``pyzoo/zoo/orca/common.py:161,271``). The reference's job was to
assemble a SparkContext (local / yarn / k8s / standalone), boot BigDL's JVM
engine, and optionally start a Ray cluster inside the Spark executors
(RayOnSpark, ``pyzoo/zoo/ray/raycontext.py:323``). On TPU there is no JVM and
no Spark: bootstrap means initializing the JAX distributed runtime (for
multi-host pods), picking the device set, and building the global
``jax.sharding.Mesh`` every Estimator will ``pjit`` over.

Supported cluster modes:

- ``"local"``      — whatever ``jax.devices()`` says on this process
                     (a CPU mesh in tests, a single TPU chip on a dev VM).
- ``"tpu"``        — multi-host TPU pod: calls ``jax.distributed.initialize``
                     (TPU env vars are auto-detected by JAX) then meshes all
                     global devices.
- ``"spark-submit"``/``"yarn"``/``"k8s"`` — accepted for API compatibility;
                     they behave like ``"tpu"`` (the scheduler that launched
                     the processes is irrelevant once JAX is initialized).
"""

from __future__ import annotations

import atexit
import logging
import os
from typing import Dict, Optional, Sequence

from zoo_tpu.common.context import (
    RuntimeContext,
    ZooContext,
    _set_runtime_context,
    default_cores,
    get_runtime_context,
)

logger = logging.getLogger("zoo_tpu.orca")


class OrcaContext(ZooContext):
    """Process-global Orca config flags (reference: ``OrcaContextMeta``,
    ``orca/common.py:21-134``). Inherits the knobs from :class:`ZooContext`;
    aliased here so user code reads ``from zoo_tpu.orca import OrcaContext``
    exactly like the reference."""

    # reference ``barrier_mode`` gated Spark barrier-scheduling for the
    # RayOnSpark bootstrap (``raycontext.py:565``); the supervised
    # bootstrap here always gang-launches, so the flag is accepted and
    # inert (kept for reference user code that sets it)
    barrier_mode = True

    @staticmethod
    def get_ray_context():
        """reference ``OrcaContext.get_ray_context`` — the active
        RayContext (a lifecycle shim here; see ``zoo_tpu.ray``)."""
        from zoo_tpu.ray import RayContext
        return RayContext.get(initialize=False)

    @staticmethod
    def get_spark_context():
        raise RuntimeError(
            "no SparkContext exists in the TPU rebuild (no JVM); Spark "
            "DataFrames enter through the gated ingestion "
            "(zoo_tpu.orca.data.spark) and everything else is "
            "XShards/numpy — see docs/migration.md")

    @staticmethod
    def get_spark_session():
        OrcaContext.get_spark_context()


_DIST_INITIALIZED = False


def _maybe_init_distributed(cluster_mode: str, num_nodes: int = 1):  # zoo-lint: config-parse
    """Initialize jax.distributed for multi-host pods. If the launcher (or
    user code) initialized it already, that wins. A failed initialize is
    only tolerable on a single-host dev box — when the caller declared
    ``num_nodes > 1`` it is a hard error, not a debug log (round-1 weak
    point: silently-degraded multi-host)."""
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED or cluster_mode == "local":
        return
    import jax

    if jax.distributed.is_initialized():
        _DIST_INITIALIZED = True
        return
    try:
        coord = os.environ.get("ZOO_COORDINATOR_ADDRESS")
        if coord:  # rendezvous injected by zoo_tpu.orca.bootstrap
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["ZOO_NUM_PROCESSES"]),
                process_id=int(os.environ["ZOO_PROCESS_ID"]))
        else:  # real pod: topology discovered from the TPU metadata
            jax.distributed.initialize()
        _DIST_INITIALIZED = True
    except Exception as e:
        if num_nodes > 1:
            raise RuntimeError(
                f"cluster_mode={cluster_mode!r} with num_nodes={num_nodes} "
                "needs the JAX distributed runtime, but "
                f"jax.distributed.initialize() failed: {e}") from e
        logger.debug("jax.distributed.initialize skipped: %s", e)


def init_orca_context(cluster_mode: str = "local",
                      cores: Optional[int] = None,
                      memory: Optional[str] = None,
                      num_nodes: int = 1,
                      mesh_axes: Optional[Dict[str, int]] = None,
                      axis_names: Optional[Sequence[str]] = None,
                      devices=None,
                      **kwargs) -> RuntimeContext:
    """Create (or return) the global :class:`RuntimeContext`.

    Parameters mirror the reference (``orca/common.py:161``): ``cores`` and
    ``memory`` sized the Spark executors there; here ``cores`` sizes the
    host-side input-pipeline worker pool and ``memory`` is accepted and
    recorded but not enforced (the OS does that). ``num_nodes`` is validated
    against the actual JAX process count on multi-host jobs.

    TPU-specific additions: ``mesh_axes`` (e.g. ``{"data": -1}`` or
    ``{"data": 2, "model": 4}``) chooses the parallelism layout — the
    reference was data-parallel only (SURVEY §2.10), this rebuild makes the
    layout a bootstrap-time choice.
    """
    cluster_mode = cluster_mode.lower()
    if cluster_mode not in ("local", "tpu", "yarn", "k8s", "standalone",
                            "spark-submit", "yarn-client", "yarn-cluster"):
        raise ValueError(f"unsupported cluster_mode: {cluster_mode}")
    if cluster_mode == "local" and num_nodes > 1:
        raise ValueError(
            f"num_nodes={num_nodes} requires a multi-host cluster_mode "
            "(e.g. 'tpu'); cluster_mode='local' is single-host by "
            "definition")

    existing = get_runtime_context(required=False)
    if existing is not None:
        prev = existing.extra.get("_init_args")
        same = prev == (cluster_mode, mesh_axes,
                        tuple(axis_names) if axis_names else None,
                        tuple(devices) if devices is not None else None)
        default_call = (cluster_mode == "local" and not mesh_axes
                        and not axis_names and devices is None)
        if not (same or default_call):
            raise RuntimeError(
                "init_orca_context called twice with different arguments; "
                "call stop_orca_context() first to rebuild")
        logger.warning("init_orca_context called twice; returning existing "
                       "context")
        return existing

    from zoo_tpu.common.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()  # before the first jit

    _maybe_init_distributed(cluster_mode, num_nodes)

    # supervised workers (zoo_tpu.orca.bootstrap with hung-worker
    # detection) hand us a heartbeat file through the env; start beating
    # so the supervisor can tell hung from healthy. No-op otherwise.
    from zoo_tpu.util.resilience import start_heartbeat_thread
    start_heartbeat_thread()

    import jax
    from zoo_tpu.parallel.mesh import (
        build_mesh,
        mesh_axes_from_env,
        publish_mesh_metrics,
    )

    devs = list(devices if devices is not None else jax.devices())
    if mesh_axes is None:
        # deployment-wide layout knobs (docs/multichip.md): ZOO_MESH_DATA /
        # ZOO_MESH_FSDP / ZOO_MESH_MODEL / ... choose the parallelism
        # layout without touching launcher code; an explicit mesh_axes=
        # argument always wins, and env axes that do not fit this
        # context's device list (a single-device reference fit, a bench
        # pinning one chip) fall back to pure DP with a warning instead
        # of crashing the caller
        env_axes = mesh_axes_from_env()
        if env_axes:
            from zoo_tpu.parallel.mesh import DEFAULT_AXES, _factor_shape
            try:
                _factor_shape(len(devs), dict(env_axes),
                              tuple(axis_names or DEFAULT_AXES))
                mesh_axes = dict(env_axes)
            except ValueError as e:
                logger.warning(
                    "ZOO_MESH_* axes %s do not fit the %d device(s) of "
                    "this context (%s); using the data-parallel default",
                    env_axes, len(devs), e)
    mesh = build_mesh(devs, axis_sizes=mesh_axes, axis_names=axis_names)
    publish_mesh_metrics(mesh)

    nproc = jax.process_count()
    if cluster_mode != "local" and num_nodes > 1 and nproc not in (1, num_nodes):
        logger.warning("num_nodes=%d but jax.process_count()=%d",
                       num_nodes, nproc)

    ctx = RuntimeContext(
        cluster_mode=cluster_mode,
        platform=devs[0].platform if devs else "cpu",
        devices=tuple(devs),
        mesh=mesh,
        num_processes=nproc,
        process_index=jax.process_index(),
        cores=cores or default_cores(),
        extra={"memory": memory, "num_nodes": num_nodes,
               "_init_args": (cluster_mode, mesh_axes,
                              tuple(axis_names) if axis_names else None,
                              tuple(devices) if devices is not None else None),
               **kwargs},
    )
    _set_runtime_context(ctx)
    atexit.register(stop_orca_context)
    logger.info("Orca context: mode=%s platform=%s devices=%d mesh=%s "
                "compile_cache=%s", cluster_mode, ctx.platform,
                ctx.num_devices, dict(mesh.shape), cache_dir)
    return ctx


def stop_orca_context():
    """Tear down the global context (reference: ``orca/common.py:271``;
    registered atexit there too). Device buffers owned by Estimators are
    dropped with their Python references; nothing else to kill — there are
    no Ray raylets or JVMs here."""
    if get_runtime_context(required=False) is not None:
        _set_runtime_context(None)
        logger.info("Orca context stopped")
