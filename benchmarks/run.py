#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), and last the numbers that decided
``correct``, each beside its limit. With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.

It exits non-zero and prints no result unless jax's platform is ``tpu``,
the device kind is in ``peaks.json`` and the chips the cell asks for are
there. ``--rehearse-cpu`` is for the sandbox: toy widths from
``rehearsal/``, says so, prints no device metric and no result that a
driver could take for one (``"rehearsal": true`` comes first in it).
``--control 1`` also reads the control of the comparison (the reference
in the next precision down); the benchmark's own runs leave it out.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")
TRACE_SECONDS = 3.0


def say(msg: str):
    print(msg, flush=True)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def _rehearsal(cell):
    """Toy widths and a toy load for the sandbox, from ``rehearsal/``,
    and, where rounding reads otherwise at toy widths, the limits that
    hold there."""
    from harness import manifest
    for what, name in (("config", cell.spec["config"]),
                       ("traffic", cell.spec["traffic"]),
                       ("limits", cell.name)):
        path = manifest.data_file("rehearsal", f"{what}.{name}.json")
        if what != "limits" or os.path.exists(path):
            with open(path) as f:
                setattr(cell, what,
                        _merge(getattr(cell, what), json.load(f)))


class Tracer:
    """Takes a profiler trace of a part of the window and reduces it."""

    def __init__(self, rec, name: str):
        self.rec = rec
        self.dir = os.path.join(OUT_DIR, "trace-" + name)
        self.t0 = self.t1 = None
        self.trace = None
        self.error = None
        self._thread = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.rec.annotate = True
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.rec.annotate = False

    def during(self, t0: float, t1: float):
        """Trace ``TRACE_SECONDS`` in the middle of [t0, t1) from a
        thread of its own (the window's driver sleeps meanwhile)."""
        def run():
            try:
                mid = t0 + max(0.0, (t1 - t0 - TRACE_SECONDS) / 2)
                time.sleep(max(0.0, mid - time.perf_counter()))
                self.start()
                time.sleep(min(TRACE_SECONDS, max(0.2, (t1 - t0) / 2)))
                self.stop()
            except Exception as e:  # noqa: BLE001 — reported by reduce()
                self.error = e
        self._thread = threading.Thread(target=run, name="bench-tracer")
        self._thread.start()

    def reduce(self):
        from harness import trace as tr
        if self._thread is not None:
            self._thread.join()
        if self.error is not None:
            raise self.error
        try:
            self.trace = tr.read_xplane(tr.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


def _reduced(tracer, args):
    """The run's trace in its plain form, or None where none was asked
    for. The CPU has no device plane: a rehearsal then reads no device
    metric; on the chip a trace without one fails the run."""
    if tracer is None:
        return None
    trace = tracer.reduce()
    if args.rehearse_cpu and not trace.ops:
        say("rehearsal: the CPU's trace has no device plane, so no "
            "metric is read from it")
        tracer.trace = trace = None
    return trace


def _device_section(devs, peak, tracer):
    from harness import device, trace as tr
    out = device.describe(devs, peak)
    if tracer is not None and tracer.trace is not None:
        busy, window = tr.busy_and_window(tracer.trace)
        out["busy_s"], out["window_s"] = busy, window
    return out


def _breakdown(tracer):
    from harness import trace as tr
    say("modules: " + json.dumps(tr.module_census(tracer.trace)))
    return {"device_ops": tr.top_ops(tracer.trace),
            "idle_gaps": tr.idle_gaps(tracer.trace)}


def _judge(numbers: dict, limits: dict):
    """Each number compared beside its limit; ``correct`` only if every
    limit is there and kept."""
    compared, ok = {}, True
    for name, lim in limits.items():
        if name not in numbers:
            ok = False
            compared[name] = {"value": None, "limit": lim["limit"]}
            continue
        v = numbers[name]
        compared[name] = {"value": v, "limit": lim["limit"]}
        ok = ok and v == v and v <= lim["limit"]
    if not limits:
        ok = False
    extra = {k: v for k, v in numbers.items() if k not in limits}
    return ok, compared, extra


# ------------------------------------------------------------------ serving

def run_serve(cell, args, devs, peaks, rec):
    from harness import device, manifest, readers, serve_cell as sc
    ref_mod = manifest.reference_of(cell.config)
    tracer = Tracer(rec, cell.name) if args.trace else None
    sys_ = sc.ServedDecoder(cell.config, args.seed, rec, ref_mod)
    hook = tracer.during if tracer else (lambda t0, t1: None)
    win = sc.LOOPS[cell.traffic["loop"]](
        sys_, cell, args.seed, args.seconds, hook, T_START)
    peak = device.memory_peak_bytes(devs)
    say(f"peak_bytes_in_use={peak}")
    stats = sys_.engine.stats()
    say("engine: " + json.dumps({k: stats.get(k) for k in (
        "decode_attention_impl", "prefill_attention_impl",
        "kv_cache_dtype", "weight_dtype", "weight_bytes", "prefill_chunk",
        "overlap", "compiles", "decode_steps", "generated_tokens")}))
    sys_.close()
    cen = sc.census(rec, win["t0"], win["t1"], len(win["finished"]))
    say("census: " + json.dumps(cen))
    say("gc: " + json.dumps(sys_.gc_watch.said(win["t0"], win["t1"])))
    metrics = {"setup_s": win["setup_s"], **win["end_to_end"]}
    trace = _reduced(tracer, args)
    checked = sc.pick_checked(win["finished"], args.seed,
                              int(cell.traffic["checked_requests"]))
    t_ref = time.perf_counter()
    numbers = sc.compare(cell, args.seed, checked, ref_mod,
                         bool(args.control)) if checked else {}
    say(f"reference_s={time.perf_counter() - t_ref:.3f} over "
        f"{len(checked)} requests")
    numbers["requests_failed"] = float(win["failed"])
    if win["errors"]:
        say("errors: " + json.dumps(win["errors"]))
    ctx = readers.Context(
        rec=rec, t0=win["t0"], t1=win["t1"], cfg=cell.config,
        traffic=cell.traffic, chips=cell.chips, peaks=peaks,
        facts=win["facts"], counters=win["counters"],
        trace=trace,
        traced=(tracer.t0, tracer.t1) if tracer else None,
        traced_census=sc.census(rec, tracer.t0, tracer.t1, 0)
        if tracer else None)
    return dict(metrics=metrics, ctx=ctx, numbers=numbers, peak=peak,
                tracer=tracer, attempted=win["attempted"],
                failed=win["failed"])


# ----------------------------------------------------------------- training

def run_train(cell, args, devs, peaks, rec):
    from harness import device, manifest, readers, train_cell as tc
    ref_mod = manifest.reference_of(cell.config)
    tracer = Tracer(rec, cell.name) if args.trace else None
    job = tc.TrainedClassifier(cell, args.seed, devs, rec, ref_mod)
    first = job.first_call()                  # compiles; is compared
    # the second call is given the first one's outputs and may compile
    # once more for their layout; the third is warm and says how long
    # an epoch takes
    job.fit(1)
    t = time.perf_counter()
    job.fit(1)
    epoch_s = time.perf_counter() - t
    epochs = max(1, int(round(args.seconds / epoch_s)))
    setup_s = time.perf_counter() - T_START
    t0 = time.perf_counter()
    if tracer and epochs >= 3:
        job.fit(epochs // 2)
        tracer.start()
        job.fit(1)
        tracer.stop()
        job.fit(epochs - epochs // 2 - 1)
    else:
        if tracer:
            tracer.start()
        job.fit(epochs)
        if tracer:
            tracer.stop()
    t1 = time.perf_counter()
    peak = device.memory_peak_bytes(devs)
    say(f"peak_bytes_in_use={peak}")
    samples = epochs * job.rows
    say("census: " + json.dumps({
        "epochs": epochs, "steps": epochs * job.steps, "samples": samples,
        "window_s": t1 - t0, "warm_epoch_s": epoch_s}))
    host_data = job.host_data
    job.close()
    trace = _reduced(tracer, args)
    t_ref = time.perf_counter()
    ref = tc.reference_first_call(cell, args.seed, devs, ref_mod,
                                  host_data)
    numbers = tc.compare_first_call(first, ref)
    say(f"reference_s={time.perf_counter() - t_ref:.3f} over "
        f"{len(ref['step_losses'])} + {cell.traffic['followed_steps']} "
        f"steps; loss program {first['step_losses']} {first['loss']:.6f} "
        f"reference {ref['step_losses']} {ref['loss']:.6f}")
    if args.control:
        low = tc.reference_first_call(cell, args.seed, devs, ref_mod,
                                      host_data, lower=True)
        for k, v in tc.compare_first_call(low, ref).items():
            numbers["control_" + k] = v
        for share, tag in ((0.5, "half_batch"),
                           (1.0 / len(devs), "no_exchange")):
            if share >= 1.0:
                continue
            bad = tc.reference_first_call(cell, args.seed, devs, ref_mod,
                                          host_data, rows=share)
            for k, v in tc.compare_first_call(bad, ref).items():
                numbers[f"fault_{tag}_{k}"] = v
    metrics = {"setup_s": setup_s,
               "train_samples_per_s_per_chip":
                   samples / (t1 - t0) / len(devs)}
    traced_epochs = 1 if (tracer and epochs >= 3) else epochs
    ctx = readers.Context(
        rec=rec, t0=t0, t1=t1, cfg=cell.config, traffic=cell.traffic,
        chips=cell.chips, peaks=peaks, facts={}, counters=None,
        trace=trace, traced=(tracer.t0, tracer.t1) if tracer else None,
        traced_census={"epochs": traced_epochs,
                       "samples": traced_epochs * job.rows}
        if tracer else None)
    return dict(metrics=metrics, ctx=ctx, numbers=numbers, peak=peak,
                tracer=tracer, attempted=epochs * job.steps, failed=0)


DRIVERS = {"serve_decoder": run_serve, "train_classifier": run_train}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--override", default=None, help="a builder's sweep: "
                    'JSON {"config": {...}, "traffic": {...}} laid over '
                    "the cell's files; the result says so first")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(1, ROOT)
    from harness import device, manifest, readers
    from harness.spans import Recorder

    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}")
        _rehearsal(cell)
        say("REHEARSAL on the CPU at toy widths: no number below is a "
            "device metric")
    else:
        # the program's one place for the persistent compile cache:
        # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR is set
        from zoo_tpu.common.compile_cache import ensure_compile_cache
        say(f"compile cache: {ensure_compile_cache()}")
        import jax
        # every program goes into the cache, also one that compiled in
        # under a second, so a cell's later runs find them all there
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devs, peaks = device.claim(cell.chips, args.rehearse_cpu)
    except device.NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    over = json.loads(args.override) if args.override else None
    if over:
        cell.config = _merge(cell.config, over.get("config", {}))
        cell.traffic = _merge(cell.traffic, over.get("traffic", {}))
        say(f"OVERRIDE {args.override}: not the cell as committed")
    rec = Recorder()
    out = DRIVERS[cell.config["kind"]](cell, args, devs, peaks, rec)

    tracer = out["tracer"]
    if args.trace:
        metrics = readers.read_all(out["ctx"], cell.per_layer())
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out["metrics"].items()
                   if k in units and v is not None}
    ok, compared, extra = _judge(out["numbers"], cell.limits)
    result = {}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    if over:
        result["override"] = over
    result.update({
        "correct": bool(ok), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics,
        "device": _device_section(devs, out["peak"], tracer)})
    if tracer is not None and tracer.trace is not None:
        result["breakdown"] = _breakdown(tracer)
    if extra:
        result["also_read"] = extra
    result["compared"] = compared
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct = {ok}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (schedulers, pools) must not hold
    # the exit; everything the run started has been stopped above
    os._exit(code)
