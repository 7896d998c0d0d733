"""Readers of the spans the program records of itself.

``zoo_tpu.obs.tracing`` keeps the last 65,536 finished spans of the
process in a ring, ``(name, t0, dur_s, thread_id, attrs)`` with ``t0`` on
``time.perf_counter`` — the clock the harness's window is on. A metric
file names one of these readers as ``"reader": "program_spans:<name>"``.

Every reader returns ``None`` where it finds nothing to read: a program
without the ring (the parent of the PR that brought it), a ring that has
wrapped past the start of what is asked for, or no span of the name. It
never returns a number computed from a part of the window.
"""

from __future__ import annotations

import json
import re
import statistics
from typing import Dict, List, Optional, Tuple

from harness import manifest, trace as tr
from harness.readers import percentile

Span = Tuple[str, float, float, int, Optional[dict]]

PAIRS_AGREE_S = 50e-6


def ring(since: Optional[float]) -> Optional[List[Span]]:
    """Every span the ring holds, or None where the program has no ring
    or the ring no longer reaches back to ``since`` (``None``: to the
    start of the process). The ring is ordered by END time, so what it
    has dropped ended before its oldest span did: a span that started at
    or after ``since`` is still there if the oldest one ended by then."""
    try:
        from zoo_tpu.obs import tracing
        state, spans = tracing.ring_state(), tracing.recent_spans()
    except (ImportError, AttributeError):
        return None
    if state["written"] > state["capacity"]:
        if since is None or not spans \
                or spans[0][1] + spans[0][2] > since:
            return None
    return spans


def _in_window(ctx, name: str) -> Optional[List[Span]]:
    spans = ring(ctx.t0)
    if spans is None:
        return None
    return [s for s in spans if s[0] == name and ctx.t0 <= s[1] < ctx.t1]


def ring_percentile(ctx, p: dict) -> Optional[float]:
    """Percentile ``q`` of the durations of the spans ``span`` that
    started in the window, times ``scale``."""
    spans = _in_window(ctx, p["span"])
    if not spans:
        return None
    return percentile([s[2] for s in spans], p["q"]) * p.get("scale", 1.0)


def ring_share(ctx, p: dict) -> Optional[float]:
    """Time under the spans ``span`` that started in the window, over
    the window, in percent."""
    spans = _in_window(ctx, p["span"])
    if not spans:
        return None
    return 100.0 * sum(s[2] for s in spans) / (ctx.t1 - ctx.t0)


def ring_count(ctx, p: dict) -> Optional[float]:
    """How many spans ``span`` started in the window. 0 is a reading:
    it is given only where the ring holds such a span from some other
    time, which shows that the program records them."""
    spans = ring(ctx.t0)
    if spans is None or not any(s[0] == p["span"] for s in spans):
        return None
    return float(sum(1 for s in spans
                     if s[0] == p["span"] and ctx.t0 <= s[1] < ctx.t1))


def ring_sum_before(ctx, p: dict) -> Optional[float]:
    """Seconds under the spans ``span`` that started before the window:
    what set-up spent there."""
    spans = ring(None)
    if spans is None:
        return None
    durs = [s[2] for s in spans if s[0] == p["span"] and s[1] < ctx.t0]
    return sum(durs) * p.get("scale", 1.0) if durs else None


# --------------------------------------------------- the ring beside a trace

def clock_offset(rec_spans: Dict[str, List[Tuple[float, float]]],
                 host: List[tr.Event], t0: float, t1: float
                 ) -> Optional[float]:
    """Seconds to add to a ``perf_counter`` time to get the profiler's.

    The harness's own spans exist on both clocks: ``rec_spans[name]`` as
    (start, duration) on ``perf_counter`` and ``host`` as the
    annotations of the same names in the trace. Of one name, the trace
    holds those that began while the session ran, the recorder's list
    those that began in [t0, t1): a few at either end exist on one side
    only. They are dropped by sliding the shorter list along the longer
    one to where the durations agree best; the offset is the median
    difference of the starts of the pairs. None with no pair, or where
    the middle half of the pairs disagrees by more than 50 us."""
    diffs: List[float] = []
    for name, spans in rec_spans.items():
        a = sorted((s, d) for s, d in spans if t0 <= s < t1)
        b = sorted((s, d) for n, s, d in host if n == name)
        if not a or not b:
            continue
        short, long_ = (a, b) if len(a) <= len(b) else (b, a)
        best = min(range(len(long_) - len(short) + 1), key=lambda k: sum(
            abs(long_[k + i][1] - short[i][1]) for i in range(len(short))))
        sign = 1.0 if short is a else -1.0
        diffs += [sign * (long_[best + i][0] - short[i][0])
                  for i in range(len(short))]
    if not diffs:
        return None
    if len(diffs) >= 4:
        q = statistics.quantiles(diffs, n=4)
        if q[2] - q[0] > PAIRS_AGREE_S:
            return None
    return statistics.median(diffs)


def idle_by_span(trace: tr.Trace, spans: List[Tuple[str, float, float]]
                 ) -> Tuple[Dict[str, float], float]:
    """The first device's idle time by the program's span that covers
    it, as ``harness.trace.idle_gaps`` does it for the harness's spans:
    each gap between operations goes to the shortest span over its
    middle, gaps under 20 us are kept apart. ``spans`` are on the
    trace's clock. Returns (seconds by name, the window's seconds)."""
    t0, t1 = tr.window_of(trace)
    dev = sorted(trace.ops)[0]
    gaps = tr.subtract([(t0, t1)], tr.clip(tr.union(trace.ops[dev]), t0, t1))
    shortest_first = sorted(spans, key=lambda ev: ev[2])
    sums: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < tr.SMALL_GAP_S:
            key = "between_ops_under_20us"
        else:
            mid = 0.5 * (s + e)
            key = next((name for name, hs, hd in shortest_first
                        if hs <= mid < hs + hd), "unattributed")
        sums[key] = sums.get(key, 0.0) + (e - s)
    return sums, t1 - t0


def idle_unattributed(ctx, p: dict) -> Optional[float]:
    """Share of the traced window in which the first device idles under
    none of the program's spans matching ``spans`` (the phases of its
    threads; a request's life covers everything and says nothing), in
    percent. Prints the whole table as ``idle_by_program_span: {...}``,
    seconds by span."""
    if ctx.trace is None or not ctx.traced:
        return None
    spans = ring(ctx.traced[0])
    if spans is None:
        return None
    shift = clock_offset(ctx.rec.spans, ctx.trace.host, *ctx.traced)
    if shift is None:
        return None
    rx = re.compile(p["spans"])
    mine = [(name, t0 + shift, dur) for name, t0, dur, _, _ in spans
            if rx.search(name)]
    if not mine:
        return None
    sums, window = idle_by_span(ctx.trace, mine)
    print("idle_by_program_span: " + json.dumps(
        dict(sorted(sums.items(), key=lambda kv: -kv[1]))), flush=True)
    return 100.0 * sums.get("unattributed", 0.0) / window


def op_formula_share(ctx, p: dict) -> Optional[float]:
    """The bytes or operations ``work`` counts for the traced work (a
    function of the configuration's formulas module, or
    ``module:function``) over the
    device time of the OPERATIONS matching ``op`` (a kernel by its
    name), as a share of one of the chip's peaks, in percent. The trace
    says how many ticks it holds (executables matching ``module``); the
    host's census is scaled to that many, as ``formula_share`` does."""
    if ctx.trace is None or ctx.peaks is None or not ctx.traced_census:
        return None
    busy, n_ops = tr.matched_time(ctx.trace.ops, p["op"])
    _, n_ticks = tr.matched_time(ctx.trace.modules, p["module"])
    host_ticks = ctx.traced_census.get(p["ticks_from_trace"])
    if not n_ops or busy <= 0 or not n_ticks or not host_ticks:
        return None
    census = {k: v * n_ticks / host_ticks
              for k, v in ctx.traced_census.items()}
    work = manifest.formula(p["work"], ctx.cfg)(ctx.cfg, ctx.traffic,
                                                census)
    if work <= 0:
        return None
    return 100.0 * (work / ctx.chips) / busy / ctx.peaks[p["peak"]]
