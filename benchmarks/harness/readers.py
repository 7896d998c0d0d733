"""The few generic readers that per-layer metrics are made of.

A metric's file under ``metrics/`` names one reader and its parameters.
A reader gets the run's ``Context`` and the parameters and returns one
number, or ``None`` where it finds nothing to read: the harness then
leaves the metric out of the line. None of them returns 0 for a share
that it could not measure.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Callable, Dict, Optional

from . import manifest, trace as tr
from .manifest import resolve
from .spans import Recorder


@dataclasses.dataclass
class Context:
    rec: Recorder
    t0: float                     # the measured window, perf_counter
    t1: float
    cfg: dict
    traffic: dict
    chips: int
    peaks: Optional[dict]         # None in a CPU rehearsal
    facts: Dict[str, float]       # numbers the cell's driver took itself
    counters: Optional[Dict[str, float]]  # the program's counters, end
    #                               minus start; None where none were read
    trace: Optional[tr.Trace] = None
    traced: Optional[tuple] = None        # (t0, t1) of the traced part
    traced_census: Optional[dict] = None  # counts within the traced part


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile; None of nothing."""
    if not values:
        return None
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, int(round(q / 100.0 * len(xs) + 0.5)) - 1))
    return xs[k]


def _span_percentile(ctx: Context, p: dict):
    v = percentile(ctx.rec.spans_in(p["span"], ctx.t0, ctx.t1), p["q"])
    return None if v is None else v * p.get("scale", 1.0)


def _sample_stat(ctx: Context, p: dict):
    xs = ctx.rec.samples_in(p["sample"], ctx.t0, ctx.t1)
    if not xs:
        return None
    v = {"mean": statistics.fmean, "max": max, "min": min,
         "p50": lambda a: percentile(a, 50),
         "p99": lambda a: percentile(a, 99)}[p["stat"]](xs)
    if "divide_by_fact" in p:
        den = ctx.facts.get(p["divide_by_fact"])
        if not den:
            return None
        v = v / den
    return v * p.get("scale", 1.0)


def _count_sum(ctx: Context, p: dict):
    if p["count"] not in ctx.rec.counts:
        return None
    return ctx.rec.count_in(p["count"], ctx.t0, ctx.t1) * p.get("scale", 1.0)


def _counter_delta(ctx: Context, p: dict):
    """Growth of one of the program's own counters over the window,
    summed over its label sets. A family that has never been touched
    has grown by 0."""
    if ctx.counters is None:
        return None
    return ctx.counters.get(p["counter"], 0.0)


def _fact(ctx: Context, p: dict):
    v = ctx.facts.get(p["fact"])
    return None if v is None else v * p.get("scale", 1.0)


def _idle_share(ctx: Context, p: dict):
    return None if ctx.trace is None else tr.idle_share(ctx.trace)


def _collective_exposed(ctx: Context, p: dict):
    if ctx.trace is None:
        return None
    try:
        return tr.collective_exposed_share(ctx.trace)
    except LookupError:
        return None


def _module_count_per(ctx: Context, p: dict):
    """Executables run on a device in the traced part, per unit of work
    done there (``per`` names a count of the traced census)."""
    if ctx.trace is None or not ctx.traced_census:
        return None
    # on the device that ran most of them: the host hands its small
    # executables to the first chip alone
    rx = re.compile(p.get("module", "."))
    n = max((sum(1 for name, _, _ in evs if rx.search(name))
             for evs in ctx.trace.modules.values()), default=0)
    den = ctx.traced_census.get(p["per"])
    return n / den if den else None


def _formula_share(ctx: Context, p: dict):
    """A formula's operations or bytes for the traced work, over the
    device time of the executables matching ``module`` there, as a
    share of one of the chip's peaks, in percent."""
    if ctx.trace is None or ctx.peaks is None or not ctx.traced_census:
        return None
    busy, n = tr.matched_time(ctx.trace.modules, p["module"])
    if not n or busy <= 0:
        return None
    census = dict(ctx.traced_census)
    if "ticks_from_trace" in p:
        # the trace, not the host's clock, says how many ticks it holds:
        # scale the host's per-tick means to that many
        host_ticks = census.get(p["ticks_from_trace"])
        if not host_ticks:
            return None
        census = {k: v * n / host_ticks for k, v in census.items()}
    work = manifest.formula(p["formula"], ctx.cfg)(ctx.cfg, ctx.traffic,
                                                   census)
    if work <= 0:
        return None
    return 100.0 * (work / ctx.chips) / busy / ctx.peaks[p["peak"]]


def _scope_share(ctx: Context, p: dict):
    """Device time of the operations traced under a scope matching
    ``scope`` (each operation's own time: a ``while`` does not count
    its body twice) inside the executables matching ``module``, over
    the device time of those executables, in percent."""
    if ctx.trace is None:
        return None
    return tr.scope_share(ctx.trace, p["scope"], p["module"])


READERS: Dict[str, Callable[[Context, dict], Optional[float]]] = {
    "span_percentile": _span_percentile,
    "sample_stat": _sample_stat,
    "count_sum": _count_sum,
    "counter_delta": _counter_delta,
    "fact": _fact,
    "trace_idle_share": _idle_share,
    "trace_collective_exposed": _collective_exposed,
    "module_count_per": _module_count_per,
    "formula_share": _formula_share,
    "scope_share": _scope_share,
}


def read_all(ctx: Context, metrics) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = resolve(m["reader"], READERS)(ctx, m.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
