"""Worker-process bootstrap and supervision.

Rebuild of the reference's RayOnSpark machinery
(``pyzoo/zoo/ray/raycontext.py:323`` ``RayContext._start_cluster``,
``gen_ray_start``:271 with its barrier-mode start; ``ProcessMonitor``
``pyzoo/zoo/ray/process.py:90``; ``JVMGuard``:33 which registers the
raylet pids so the JVM kills orphans). There the cluster fabric to boot
was Ray-on-Spark-executors; on TPU the fabric is the JAX distributed
runtime — one Python worker process per host — so what carries over is
the *supervision* capability:

* :class:`ProcessMonitor` — spawn N workers, watch them, restart on crash
  (bounded), tear the whole group down when any worker fails fatally or
  the parent exits. The JVMGuard orphan-kill maps to ``PR_SET_PDEATHSIG``
  (children get SIGKILLed by the kernel if the supervisor dies) plus
  process-group kills.
* :func:`launch_local_cluster` — the reference's ``local`` RayContext:
  boot an N-process JAX CPU cluster on one machine (coordinator on a free
  localhost port, ranks via ``ZOO_*`` env) for dev/test of multi-host
  code paths.
* CLI: ``python -m zoo_tpu.orca.bootstrap --nproc 4 train.py ...`` —
  the same CPU rig from the command line: supervised workers forced to
  ``JAX_PLATFORMS=cpu`` (it never opens a TPU; on a real pod,
  ``scripts/run_tpu_pod.sh`` starts the one process per host).

``init_orca_context(cluster_mode="tpu")`` picks the rank/coordinator up
from the ``ZOO_COORDINATOR_ADDRESS`` / ``ZOO_NUM_PROCESSES`` /
``ZOO_PROCESS_ID`` environment this module sets.
"""

from __future__ import annotations

import atexit
import ctypes
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

from zoo_tpu.obs.metrics import counter
from zoo_tpu.orca.learn.guard import PREEMPT_EXIT_CODE
from zoo_tpu.util.resilience import (
    HEARTBEAT_FILE_ENV,
    HEARTBEAT_INTERVAL_ENV,
    heartbeat_age,
)

logger = logging.getLogger(__name__)


class WorkersPreempted(RuntimeError):
    """Every worker exited with :data:`PREEMPT_EXIT_CODE` — a
    preemption-triggered coordinated checkpoint, not a crash. The
    supervisor should relaunch at the SAME world size and let the job
    resume from the checkpoint (``run_elastic`` does exactly that;
    resume-don't-retry)."""

_worker_restarts = counter(
    "zoo_worker_restarts_total",
    "Supervised workers respawned after a crash or hang")
_workers_hung = counter(
    "zoo_worker_hung_total",
    "Supervised workers killed for a stale heartbeat")
_worker_quarantines = counter(
    "zoo_worker_quarantine_total",
    "Quarantine-mode transitions performed by supervisors in this "
    "process (quarantined = a worker exhausted its restart budget and "
    "was parked instead of killing the group; probe = a backoff-timed "
    "respawn attempt; readmitted = a probe survived the heal window "
    "and the seat returned to normal supervision)",
    labels=("event",))


def _flight(kind: str, **fields):
    """Flight-recorder event (lazy import — supervision must never fail
    to load because the obs ring could not)."""
    try:
        from zoo_tpu.obs.flight import record_event
        record_event(kind, **fields)
    except Exception:  # noqa: BLE001 — telemetry never fails the op
        pass

_PR_SET_PDEATHSIG = 1


def _child_preexec():
    """Run in the child between fork and exec: new session (own process
    group for clean group-kill) and kernel-level orphan protection."""
    os.setsid()
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(_PR_SET_PDEATHSIG, signal.SIGKILL)
    except Exception:
        pass  # non-Linux: atexit kill still covers the common case


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pick_coordinator_port(retries: int = 16) -> int:
    """A free port for the JAX coordinator, re-probed immediately before
    use. ``free_port`` releases the port when it returns, so another
    process can grab it before worker 0 binds (the classic TOCTOU race);
    re-probing right here and retrying with a fresh candidate shrinks
    that window from "whole launch setup" to microseconds instead of
    failing the entire launch on a stale candidate."""
    last: Optional[OSError] = None
    for _ in range(max(1, retries)):
        port = free_port()
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", port))
            return port
        except OSError as e:  # taken since the probe: try a fresh one
            last = e
            logger.warning("coordinator port %d taken between probe and "
                           "use; retrying with a fresh port", port)
    raise RuntimeError(
        f"could not reserve a coordinator port after {retries} "
        "attempts") from last


class WorkerProcess:
    """One supervised worker (reference: a ray start subprocess tracked by
    ``ProcessInfo``)."""

    def __init__(self, cmd: Sequence[str], env: Dict[str, str],
                 name: str, log_dir: Optional[str] = None,
                 heartbeat_file: Optional[str] = None):
        self.cmd = list(cmd)
        self.env = dict(env)
        self.name = name
        self.log_dir = log_dir
        self.heartbeat_file = heartbeat_file
        if heartbeat_file:
            self.env[HEARTBEAT_FILE_ENV] = heartbeat_file
        self.proc: Optional[subprocess.Popen] = None
        self.restarts = 0
        self._log_fh = None
        self.heartbeat_spawn_mtime: Optional[float] = None
        # quarantine-mode state (docs/fault_tolerance.md): set by a
        # ProcessMonitor(quarantine=True) when this worker exhausts its
        # restart budget — parked, probed on a backoff timer, readmitted
        # after a probe survives the heal window
        self.quarantined = False
        self.quarantine_until = 0.0
        self.quarantine_backoff = 0.0
        self.quarantines = 0
        self.last_spawn_monotonic: Optional[float] = None

    def spawn(self):
        if self._log_fh:  # restart: release the previous run's handle
            self._log_fh.close()
            self._log_fh = None
        if self.heartbeat_file:
            # stamp at spawn so staleness is measured from launch even if
            # the worker never gets far enough to beat on its own; record
            # the stamp so the monitor can tell "never beat yet (still
            # booting — import jax alone can take many seconds)" from
            # "beat, then went silent (hung)"
            from zoo_tpu.util.resilience import touch_heartbeat
            touch_heartbeat(self.heartbeat_file)
            try:
                self.heartbeat_spawn_mtime = \
                    os.stat(self.heartbeat_file).st_mtime
            except OSError:
                self.heartbeat_spawn_mtime = None
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
            self._log_fh = open(
                os.path.join(self.log_dir, f"{self.name}.log"), "ab")
            out = err = self._log_fh
        else:
            out = err = None
        self.proc = subprocess.Popen(
            self.cmd, env=self.env, stdout=out, stderr=err,
            preexec_fn=_child_preexec)
        self.last_spawn_monotonic = time.monotonic()
        return self.proc

    @property
    def returncode(self) -> Optional[int]:
        return self.proc.poll() if self.proc else None

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            try:  # group-kill: the worker may have forked its own helpers
                os.killpg(self.proc.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(self.proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                self.proc.wait()
        if self._log_fh:  # close even for self-exited workers
            self._log_fh.close()
            self._log_fh = None


class ProcessMonitor:
    """Spawn + supervise a set of workers (reference ``ProcessMonitor``
    ``ray/process.py:90``: tracks pids, raises when a member dies, cleans
    the rest up).

    ``max_restarts``: per-worker crash budget. Within budget a crashed
    worker is respawned; past it the whole group is torn down and
    :meth:`wait` raises. Exit code 0 counts as completion, not a crash.

    ``heartbeat_timeout``: optional hung-worker detection. Workers whose
    :class:`WorkerProcess` carries a ``heartbeat_file`` (stamped by
    ``touch_heartbeat`` / the ``init_orca_context`` heartbeat thread) are
    SIGKILLed and charged against the restart budget when the file goes
    stale for longer than this many seconds — a worker stuck in a dead
    collective is a crash the same as one that exited nonzero.

    ``quarantine``: what happens when ONE worker exhausts its restart
    budget. ``False`` (default — training semantics): the whole group
    is torn down and :meth:`wait` raises, because a gang-scheduled job
    cannot run short a rank. ``True`` (serving semantics, what
    :class:`~zoo_tpu.serving.ha.ReplicaGroup` passes): the crash-looping
    worker is QUARANTINED — parked with a flight-ring event instead of
    silently burning the group — while its siblings keep serving; a
    probe respawn is attempted on an exponential-backoff timer
    (``ZOO_QUARANTINE_PROBE_S`` base, ``ZOO_QUARANTINE_PROBE_MAX_S``
    cap), and a probe that stays alive for ``ZOO_QUARANTINE_HEAL_S``
    re-admits the seat with a fresh restart budget.
    """

    def __init__(self, workers: List[WorkerProcess], max_restarts: int = 0,
                 poll_interval: float = 0.2,
                 heartbeat_timeout: Optional[float] = None,
                 heartbeat_boot_grace: float = 120.0,
                 quarantine: bool = False):
        from zoo_tpu.util.resilience import env_float
        self.workers = workers
        self.max_restarts = int(max_restarts)
        self.poll_interval = poll_interval
        self.heartbeat_timeout = heartbeat_timeout
        self.quarantine = bool(quarantine)
        self.quarantine_probe_s = env_float("ZOO_QUARANTINE_PROBE_S",
                                            5.0)
        self.quarantine_probe_max_s = env_float(
            "ZOO_QUARANTINE_PROBE_MAX_S", 60.0)
        self.quarantine_heal_s = env_float("ZOO_QUARANTINE_HEAL_S", 30.0)
        # until a worker has beaten ON ITS OWN at least once it is
        # booting, not hung — a cold `import jax` alone can outlast a
        # tight heartbeat_timeout; the boot window gets the larger bound
        self.heartbeat_boot_grace = max(heartbeat_boot_grace,
                                        heartbeat_timeout or 0.0)
        self._failed: Optional[str] = None
        self._preempted = False
        self._stop = threading.Event()
        self._lock = threading.Lock()  # serializes respawn vs teardown
        self._thread: Optional[threading.Thread] = None
        atexit.register(self.stop)

    def start(self) -> "ProcessMonitor":
        for w in self.workers:
            w.spawn()
            logger.info("spawned %s (pid %d)", w.name, w.proc.pid)
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="zoo-process-monitor")
        self._thread.start()
        return self

    def _crash_reason(self, w: WorkerProcess) -> Optional[str]:
        """A crash description for worker ``w``, or None while healthy.
        Hung workers (stale heartbeat) are killed here so the respawn /
        teardown path treats them exactly like a nonzero exit."""
        rc = w.returncode
        if rc is not None:
            # PREEMPT_EXIT_CODE is a deliberate checkpoint-and-exit
            # (training guardian, docs/fault_tolerance.md): completion,
            # never a crash — no respawn, no restart-budget charge
            return None if rc in (0, PREEMPT_EXIT_CODE) \
                else f"exited rc={rc}"
        if self.heartbeat_timeout and w.heartbeat_file:
            age = heartbeat_age(w.heartbeat_file)
            try:
                mtime = os.stat(w.heartbeat_file).st_mtime
            except OSError:
                mtime = None
            booted = (mtime is not None
                      and w.heartbeat_spawn_mtime is not None
                      and mtime > w.heartbeat_spawn_mtime)
            limit = self.heartbeat_timeout if booted \
                else self.heartbeat_boot_grace
            if age is not None and age > limit:
                logger.warning(
                    "%s heartbeat stale (%.1fs > %.1fs%s); killing the "
                    "hung worker", w.name, age, limit,
                    "" if booted else ", boot grace")
                _workers_hung.inc()
                w.kill()
                return (f"hung (heartbeat stale {age:.1f}s > "
                        f"{limit}s limit)")
        return None

    def _probe_beating(self, w: WorkerProcess) -> bool:
        """Whether a live quarantine probe has proven PROGRESS, not
        just liveness: with heartbeat monitoring armed, the probe must
        have beaten on its own since the spawn and be fresh — a probe
        wedged at boot must never read as healed (it would be
        re-admitted with a fresh budget, hung-killed, re-quarantined,
        and churn forever)."""
        if not (self.heartbeat_timeout and w.heartbeat_file):
            return True  # no heartbeat contract: alive is the bar
        age = heartbeat_age(w.heartbeat_file)
        try:
            mtime = os.stat(w.heartbeat_file).st_mtime
        except OSError:
            return False
        booted = (w.heartbeat_spawn_mtime is not None
                  and mtime > w.heartbeat_spawn_mtime)
        return booted and age is not None and \
            age <= self.heartbeat_timeout

    def _watch_quarantined(self, w: WorkerProcess):
        """One poll of a quarantined seat: probe respawns on the
        backoff timer, re-admission after a probe survives the heal
        window. Never touches the group."""
        now = time.monotonic()
        if w.returncode is None and w.last_spawn_monotonic is not None:
            if now - w.last_spawn_monotonic >= self.quarantine_heal_s:
                if not self._probe_beating(w):
                    # alive past the heal window but HUNG: the probe
                    # failed — kill it; the dead-seat branch below
                    # schedules the next (longer) backoff
                    logger.warning(
                        "%s quarantine probe is alive but not beating "
                        "— hung probe killed, staying quarantined",
                        w.name)
                    w.kill()
                    return
                # the probe held AND made progress: the seat is a real
                # replica again, with a fresh restart budget
                w.quarantined = False
                w.restarts = 0
                w.quarantine_backoff = 0.0
                _worker_quarantines.labels(event="readmitted").inc()
                _flight("replica_unquarantined", worker=w.name,
                        quarantines=w.quarantines)
                logger.warning(
                    "%s survived its quarantine probe for %.0fs; "
                    "re-admitted with a fresh restart budget",
                    w.name, self.quarantine_heal_s)
            return
        if w.returncode is None:
            return  # probe still running inside the heal window
        if now < w.quarantine_until:
            return  # dead, waiting out the backoff
        with self._lock:
            if self._stop.is_set():
                return
            # each failed probe doubles the next wait (capped): a seat
            # with a genuinely broken substrate converges to one cheap
            # respawn a minute instead of a crash loop
            w.quarantine_backoff = min(
                max(self.quarantine_probe_s, 2 * w.quarantine_backoff),
                self.quarantine_probe_max_s)
            w.quarantine_until = now + w.quarantine_backoff
            _worker_quarantines.labels(event="probe").inc()
            _flight("replica_quarantine_probe", worker=w.name,
                    next_backoff_s=w.quarantine_backoff)
            logger.info("%s quarantine probe respawn (next backoff "
                        "%.1fs)", w.name, w.quarantine_backoff)
            w.spawn()

    def _watch(self):
        while not self._stop.is_set():
            for w in self.workers:
                if w.quarantined:
                    self._watch_quarantined(w)
                    continue
                reason = self._crash_reason(w)
                if reason is None:
                    continue
                if w.restarts < self.max_restarts:
                    with self._lock:
                        if self._stop.is_set():
                            return  # teardown won the race: no respawn
                        w.restarts += 1
                        _worker_restarts.inc()
                        logger.warning(
                            "%s %s; restart %d/%d", w.name, reason,
                            w.restarts, self.max_restarts)
                        w.spawn()
                elif self.quarantine:
                    # serving semantics: the seat exhausted its budget
                    # — park it LOUDLY (flight event + counter; the
                    # gauge rides ReplicaGroup.healthz) instead of the
                    # old silent permanent death, and keep probing it
                    # back on a backoff timer while the rest of the
                    # group serves on
                    with self._lock:
                        if self._stop.is_set():
                            return
                        w.quarantined = True
                        w.quarantines += 1
                        # a RE-quarantine (a seat whose earlier probe
                        # "healed" then failed again) continues the
                        # backoff ladder instead of resetting to the
                        # base — only a genuine readmission clears it
                        w.quarantine_backoff = min(
                            max(self.quarantine_probe_s,
                                2 * w.quarantine_backoff),
                            self.quarantine_probe_max_s)
                        w.quarantine_until = (time.monotonic()
                                              + w.quarantine_backoff)
                        _worker_quarantines.labels(
                            event="quarantined").inc()
                        _flight("replica_quarantined", worker=w.name,
                                reason=reason, restarts=w.restarts,
                                probe_backoff_s=w.quarantine_backoff)
                        logger.error(
                            "%s %s with no restart budget left "
                            "(%d/%d) — QUARANTINED; probing back every "
                            "%.1fs (doubling, cap %.0fs)",
                            w.name, reason, w.restarts,
                            self.max_restarts, w.quarantine_backoff,
                            self.quarantine_probe_max_s)
                else:
                    with self._lock:
                        if self._stop.is_set():
                            return  # deliberate stop(), not a crash
                        self._failed = (
                            f"{w.name} {reason} with no restart "
                            f"budget left "
                            f"({w.restarts}/{self.max_restarts})")
                        logger.error("%s — tearing the group down",
                                     self._failed)
                        self._stop.set()
                        for other in self.workers:
                            other.kill()
                    return
            rcs = [w.returncode for w in self.workers]
            if all(rc is not None and rc in (0, PREEMPT_EXIT_CODE)
                   for rc in rcs):
                if PREEMPT_EXIT_CODE in rcs:
                    self._preempted = True
                self._stop.set()
                return
            time.sleep(self.poll_interval)

    def wait(self, timeout: Optional[float] = None):
        """Block until every worker exits 0; raise on fatal failure.
        Raises :class:`WorkersPreempted` when the group completed via a
        coordinated preemption checkpoint (exit :data:`PREEMPT_EXIT_CODE`)
        so the caller relaunches-and-resumes instead of scaling down."""
        deadline = time.time() + timeout if timeout is not None else None
        while True:
            if self._failed:
                raise RuntimeError(self._failed)
            rcs = [w.returncode for w in self.workers]
            if all(rc is not None and rc in (0, PREEMPT_EXIT_CODE)
                   for rc in rcs):
                if PREEMPT_EXIT_CODE in rcs:
                    raise WorkersPreempted(
                        f"{rcs.count(PREEMPT_EXIT_CODE)}/{len(rcs)} "
                        "worker(s) exited via the preemption checkpoint "
                        "protocol; relaunch and resume")
                return
            if self._stop.is_set():
                # the watch thread assigns _failed BEFORE setting _stop;
                # re-check so a failure set between our two reads is not
                # mistaken for a deliberate stop()
                if self._failed:
                    raise RuntimeError(self._failed)
                if self._preempted:
                    raise WorkersPreempted(
                        "workers exited via the preemption checkpoint "
                        "protocol; relaunch and resume")
                return  # deliberate stop(): termination, not failure
            if deadline is not None and time.time() > deadline:
                self.stop()
                raise TimeoutError(
                    f"workers still running after {timeout}s")
            time.sleep(self.poll_interval)

    def alive(self) -> List[str]:
        return [w.name for w in self.workers if w.returncode is None]

    def quarantined(self) -> List[str]:
        """Names of workers currently parked in quarantine — every
        seat accounted for, none silently missing."""
        return [w.name for w in self.workers if w.quarantined]

    def stop(self):
        with self._lock:  # no respawn may interleave with the kills
            self._stop.set()
            for w in self.workers:
                w.kill()


def launch_local_cluster(nproc: int, script: str,
                         args: Sequence[str] = (),
                         local_devices_per_proc: int = 1,
                         max_restarts: int = 0,
                         log_dir: Optional[str] = None,
                         env: Optional[Dict[str, str]] = None,
                         heartbeat_timeout: Optional[float] = None
                         ) -> ProcessMonitor:
    """The CPU test rig for multi-process jobs: boot an ``nproc``-process
    JAX **CPU** cluster running ``script`` on this machine (the
    reference's local RayContext). Every worker is forced to
    ``JAX_PLATFORMS=cpu`` with ``local_devices_per_proc`` virtual
    devices — it never opens a TPU, so it is not a way to run on chips
    (one process drives all chips of a host; serving seats get one chip
    each from :class:`zoo_tpu.serving.ha.ReplicaGroup`). Each worker
    also gets ``ZOO_COORDINATOR_ADDRESS`` / ``ZOO_NUM_PROCESSES`` /
    ``ZOO_PROCESS_ID``, so ``init_orca_context(cluster_mode="tpu")``
    forms the same process mesh it would on a pod.

    ``heartbeat_timeout``: enable hung-worker detection — each worker is
    handed a heartbeat file (``ZOO_HEARTBEAT_FILE``; stamped by the
    ``init_orca_context`` heartbeat thread) and is killed + charged to
    the restart budget when the stamp goes stale for longer than this
    many seconds."""
    import tempfile

    coord = f"127.0.0.1:{_pick_coordinator_port()}"
    hb_dir = None
    if heartbeat_timeout:
        hb_dir = log_dir or tempfile.mkdtemp(prefix="zoo-heartbeat-")
        os.makedirs(hb_dir, exist_ok=True)
    workers = []
    for pid in range(nproc):
        wenv = dict(os.environ)
        wenv.update(env or {})
        wenv.update({
            "ZOO_COORDINATOR_ADDRESS": coord,
            "ZOO_NUM_PROCESSES": str(nproc),
            "ZOO_PROCESS_ID": str(pid),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (wenv.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count="
                          f"{local_devices_per_proc}").strip(),
        })
        # never let a worker inherit the SUPERVISOR's heartbeat file
        # (nested launches: every child stamping the parent's file would
        # mask a hung sibling); each worker gets its own below, or none
        wenv.pop(HEARTBEAT_FILE_ENV, None)
        hb_file = None
        if hb_dir:
            hb_file = os.path.join(hb_dir, f"worker-{pid}.heartbeat")
            # a stale stamp carried over from a previous elastic attempt
            # in the same log_dir must not count as this attempt's beat
            try:
                os.unlink(hb_file)
            except OSError:
                pass
            # beat at a quarter of the timeout: three missed beats of
            # slack before a healthy-but-busy worker reads as hung
            wenv[HEARTBEAT_INTERVAL_ENV] = str(
                max(0.05, heartbeat_timeout / 4.0))
        workers.append(WorkerProcess(
            [sys.executable, script, *args], wenv, f"worker-{pid}",
            log_dir=log_dir, heartbeat_file=hb_file))
    return ProcessMonitor(workers, max_restarts=max_restarts,
                          heartbeat_timeout=heartbeat_timeout).start()


def run_elastic(nproc: int, script: str, args: Sequence[str] = (),
                min_workers: int = 1, max_restarts: int = 0,
                local_devices_per_proc: int = 1,
                log_dir: Optional[str] = None,
                env: Optional[Dict[str, str]] = None,
                wait_timeout: Optional[float] = None,
                heartbeat_timeout: Optional[float] = None,
                max_preempts: int = 100) -> int:
    """Scale-down elastic supervision (SURVEY §5.3; reference:
    ``Topology.scala:1255-1337`` retries within the job from the latest
    snapshot — this is that mechanism lifted to the supervisor, plus the
    re-mesh the reference cannot do).

    Runs ``script`` as an ``nproc``-process cluster. Same-size crashes
    are handled inside :class:`ProcessMonitor` (per-worker
    ``max_restarts``). When a worker exhausts its budget — a PERMANENT
    loss — the whole group is torn down and relaunched as an
    ``nproc-1``-process cluster (fresh coordinator, smaller mesh); the
    training script is expected to resume from its latest checkpoint
    (``est.load_orca_checkpoint()``), which the env var
    ``ZOO_ELASTIC_ATTEMPT`` (> "0") signals. Stops scaling at
    ``min_workers``; returns the world size that completed.

    A group that exits through the training guardian's preemption
    protocol (every worker exited :data:`PREEMPT_EXIT_CODE` after ONE
    coordinated checkpoint) is **resumed at the same world size** —
    preemption is the platform reclaiming a machine, not the job
    failing — bounded by ``max_preempts`` relaunches.
    """
    n, attempt, preempts = int(nproc), 0, 0
    while True:
        wenv = dict(env or {})
        wenv["ZOO_ELASTIC_ATTEMPT"] = str(attempt)
        mon = launch_local_cluster(
            n, script, args, max_restarts=max_restarts,
            local_devices_per_proc=local_devices_per_proc,
            log_dir=log_dir, env=wenv,
            heartbeat_timeout=heartbeat_timeout)
        try:
            mon.wait(timeout=wait_timeout)
            return n
        except WorkersPreempted as e:
            mon.stop()
            preempts += 1
            if preempts > max_preempts:
                raise RuntimeError(
                    f"preempted {preempts} times (> max_preempts="
                    f"{max_preempts}); giving up") from e
            logger.warning(
                "world size %d preempted (%s); relaunching at the same "
                "size, resuming from the preemption checkpoint "
                "(attempt %d)", n, e, attempt + 1)
            attempt += 1
        except RuntimeError as e:
            mon.stop()
            if n - 1 < min_workers:
                raise RuntimeError(
                    f"cannot scale below min_workers={min_workers} "
                    f"(world {n} failed: {e})") from e
            logger.warning(
                "permanent worker loss at world size %d (%s); resuming "
                "from the latest checkpoint on %d workers", n, e, n - 1)
            n -= 1
            attempt += 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m zoo_tpu.orca.bootstrap",
        description="CPU test rig: supervised multi-process launcher "
                    "whose workers are forced to JAX_PLATFORMS=cpu "
                    "with virtual devices (reference: RayContext/"
                    "spark-submit role). It never opens a TPU.")
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=0)
    ap.add_argument("--devices-per-proc", type=int, default=1,
                    help="virtual CPU devices per worker")
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--elastic-min-workers", type=int, default=0,
                    help="enable scale-down elastic mode: on permanent "
                         "worker loss, relaunch the job on a smaller "
                         "mesh (resuming from the latest checkpoint) "
                         "down to this world size")
    ap.add_argument("--heartbeat-timeout", type=float, default=None,
                    help="kill a worker whose heartbeat file goes stale "
                         "for this many seconds (hung-worker detection; "
                         "charged to the restart budget)")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    ns = ap.parse_args(argv)
    try:
        if ns.elastic_min_workers > 0:
            run_elastic(ns.nproc, ns.script, ns.args,
                        min_workers=ns.elastic_min_workers,
                        max_restarts=ns.max_restarts,
                        local_devices_per_proc=ns.devices_per_proc,
                        log_dir=ns.log_dir,
                        heartbeat_timeout=ns.heartbeat_timeout)
            return 0
        mon = launch_local_cluster(
            ns.nproc, ns.script, ns.args,
            local_devices_per_proc=ns.devices_per_proc,
            max_restarts=ns.max_restarts, log_dir=ns.log_dir,
            heartbeat_timeout=ns.heartbeat_timeout)
        mon.wait()
        return 0
    except (RuntimeError, KeyboardInterrupt) as e:
        logger.error("%s", e)
        if "mon" in locals():
            mon.stop()
        return 1


if __name__ == "__main__":
    sys.exit(main())
