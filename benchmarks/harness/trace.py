"""From a profiler trace to numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote
into a plain form (``Trace``): for every device the operations and the
executables ("modules") that ran on it, and the host's spans, each as
(name, start_s, duration_s) on the profiler's clock. Everything else
here works on that plain form, so it can be checked on a small recorded
trace with no profiler (``tests/test_trace.py``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s

SMALL_GAP_S = 20e-6
DEVICE_PLANE = "/device:TPU:"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]           # device -> operations
    modules: Dict[str, List[Event]]       # device -> executables run
    host: List[Event]                     # host spans (annotations)
    # device -> for every operation of ``ops``, in its order, the scope
    # it was traced under as the program's ``jax.named_scope``s give it
    # (``jit(_decode_fn)/.../zoo.mlp/dot_general``), "" for none; empty
    # where the profile carries no scope at all
    scopes: Dict[str, List[str]] = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "Trace":
        fix = lambda evs: [(n, float(s), float(t)) for n, s, t in evs]
        return Trace({k: fix(v) for k, v in d["ops"].items()},
                     {k: fix(v) for k, v in d["modules"].items()},
                     fix(d["host"]),
                     {k: list(v) for k, v in d.get("scopes", {}).items()})


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _xplane_pb2():
    """tsl's generated classes for the profile's format
    (``tsl/profiler/protobuf/xplane.proto``). They ship inside the
    tensorflow package; the one file is loaded by its path, because
    importing the package round it takes nine seconds and the file
    needs nothing of it."""
    import importlib.util
    if "_xplane_pb2" not in sys.modules:
        root = importlib.util.find_spec(
            "tensorflow").submodule_search_locations[0]
        spec = importlib.util.spec_from_file_location(
            "_xplane_pb2", os.path.join(
                root, "tsl", "profiler", "protobuf", "xplane_pb2.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["_xplane_pb2"] = mod
    return sys.modules["_xplane_pb2"]


def read_xplane(path: str, host_prefix: str = "bench:") -> Trace:
    """Device planes are those named ``/device:TPU:<n>``; their line
    "XLA Ops" holds the operations and "XLA Modules" the executables.
    Host spans are the events whose name starts with ``host_prefix`` on
    any line of the host planes. An operation's scope is the stat
    ``tf_op`` of its event's metadata (one entry per distinct operation
    of a plane), which ``jax.profiler.ProfileData`` does not show."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    scopes: Dict[str, List[str]] = {}
    host: List[Event] = []
    for plane in space.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE)
        meta = plane.event_metadata
        for line in plane.lines:
            t_line = line.timestamp_ns * 1e-9
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                dst = ops if line.name == "XLA Ops" else modules
                dst.setdefault(plane.name, []).extend(
                    (meta[ev.metadata_id].name,
                     t_line + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12)
                    for ev in line.events)
                if dst is ops:
                    by_id = _scopes_by_metadata(plane)
                    scopes.setdefault(plane.name, []).extend(
                        by_id.get(ev.metadata_id, "") for ev in line.events)
            elif not is_dev:
                host.extend(
                    (meta[ev.metadata_id].name[len(host_prefix):],
                     t_line + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12)
                    for ev in line.events
                    if meta[ev.metadata_id].name.startswith(host_prefix))
    if not any(s for line in scopes.values() for s in line):
        scopes = {}
    return Trace(ops, modules, host, scopes)


SCOPE_STAT = "tf_op"


def _scopes_by_metadata(plane) -> Dict[int, str]:
    """Metadata id -> the scope that operation was traced under, for
    the operations of one plane that carry one. A stat holds its text
    itself or points at the stat name that does."""
    names = {k: m.name for k, m in plane.stat_metadata.items()}
    out = {}
    for key, m in plane.event_metadata.items():
        for st in m.stats:
            if names.get(st.metadata_id) == SCOPE_STAT:
                text = st.str_value or names.get(st.ref_value, "")
                if text:
                    out[key] = text
    return out


# ------------------------------------------------------------- intervals

def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of some events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of intervals ``a`` that no interval of ``b`` covers
    (both merged and sorted)."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(trace: Trace) -> Tuple[float, float]:
    """The traced window: from the first device operation's start to
    the last one's end."""
    starts = [s for evs in trace.ops.values() for _, s, _ in evs]
    ends = [s + d for evs in trace.ops.values() for _, s, d in evs]
    if not starts:
        raise ValueError("no operation ran on a device in this trace")
    return min(starts), max(ends)


# --------------------------------------------------------------- numbers

def busy_by_device(trace: Trace, t0: float, t1: float) -> Dict[str, float]:
    return {dev: total(clip(union(evs), t0, t1))
            for dev, evs in trace.ops.items()}


def busy_and_window(trace: Trace) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    t0, t1 = window_of(trace)
    busy = busy_by_device(trace, t0, t1)
    return sum(busy.values()) / len(busy), t1 - t0


def idle_share(trace: Trace) -> float:
    """1 - busy/window on the fullest device, in percent."""
    t0, t1 = window_of(trace)
    busy = busy_by_device(trace, t0, t1)
    return 100.0 * (1.0 - max(busy.values()) / (t1 - t0))


def clean(name: str, cap: int = 64) -> str:
    """An operation's name as one token: ``%fusion.3 = bf16[8,128]{..}``
    becomes ``fusion.3_bf16_8_128_``."""
    m = re.match(r"%?([\w.\-]+)(?:\s*=\s*([\w\[\],]+))?", name.strip())
    if m:
        name = m.group(1) + ("_" + m.group(2) if m.group(2) else "")
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:cap]


def own_seconds(events: Sequence[Event]) -> List[float]:
    """For every event, in the order given, its seconds less those of
    the events that lie inside it: a ``while`` or a ``conditional``
    holds its body's operations, and only what it spends itself is its
    own."""
    own = [d for _, _, d in events]
    open_: List[Tuple[int, float]] = []        # (index, end)
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][1], -events[i][2])):
        _, s, d = events[i]
        while open_ and open_[-1][1] <= s:
            open_.pop()
        if open_ and s + d > open_[-1][1] + 1e-12:
            continue                  # overlaps its neighbour, not inside
        if open_:
            own[open_[-1][0]] -= d
        open_.append((i, s + d))
    return [max(t, 0.0) for t in own]


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, seconds of its own) of every event."""
    return [(ev[0], t) for ev, t in zip(events, own_seconds(events))]


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """The events that hold no other event: a ``while`` that holds a
    whole epoch is running for as long as its body is, and says nothing
    about what runs beside a collective."""
    out: List[Event] = []
    open_: List[list] = []            # [event, end, holds another]
    for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while open_ and open_[-1][1] <= ev[1]:
            top = open_.pop()
            if not top[2]:
                out.append(top[0])
        if open_ and ev[1] + ev[2] <= open_[-1][1] + 1e-12:
            open_[-1][2] = True
        open_.append([ev, ev[1] + ev[2], False])
    out.extend(ev for ev, _, holds in open_ if not holds)
    return out


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time of their own, summed
    by name and averaged over the devices."""
    sums: Dict[str, float] = {}
    for evs in trace.ops.values():
        for name, t in self_times(evs):
            sums[name] = sums.get(name, 0.0) + t
    k = max(1, len(trace.ops))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[clean(name), t / k] for name, t in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first device by what the host was doing: each
    gap between operations goes to the shortest host span that covers
    its middle; gaps under 20 us are kept apart."""
    t0, t1 = window_of(trace)
    dev = sorted(trace.ops)[0]
    busy = clip(union(trace.ops[dev]), t0, t1)
    gaps = subtract([(t0, t1)], busy)
    sums: Dict[str, float] = {}
    host = sorted(trace.host, key=lambda ev: ev[2])     # shortest first
    for s, e in gaps:
        if e - s < SMALL_GAP_S:
            key = "between_ops_under_20us"
        else:
            mid = 0.5 * (s + e)
            key = next((name for name, hs, hd in host
                        if hs <= mid < hs + hd), "unattributed")
        sums[key] = sums.get(key, 0.0) + (e - s)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[clean(k), v] for k, v in ranked]


def module_census(trace: Trace, n: int = 12) -> Dict[str, List]:
    """[count, seconds] of the executables that ran on the first
    device, by name, the longest first."""
    sums: Dict[str, List] = {}
    for name, _, d in trace.modules.get(sorted(trace.ops)[0], ()):
        c = sums.setdefault(clean(name), [0, 0.0])
        c[0] += 1
        c[1] += d
    return dict(sorted(sums.items(), key=lambda kv: -kv[1][1])[:n])


def matched_time(events_by_dev: Dict[str, List[Event]], pattern: str
                 ) -> Tuple[float, int]:
    """(device seconds, count) of the events whose name matches
    ``pattern``, on the device that spent most on them."""
    rx = re.compile(pattern)
    best = (0.0, 0)
    for evs in events_by_dev.values():
        hit = [d for name, _, d in evs if rx.search(name)]
        if sum(hit) > best[0]:
            best = (sum(hit), len(hit))
    return best


def scope_share(trace: Trace, scope: str, module: str) -> Optional[float]:
    """Own seconds of the operations whose scope matches ``scope`` and
    that began inside an executable matching ``module``, over the
    seconds of those executables, in percent, on the device that spent
    most in them. None where the trace names no scope, no such
    executable ran, or no operation under the scope did."""
    if not trace.scopes:
        return None
    rx_scope, rx_mod = re.compile(scope), re.compile(module)
    best = None
    for dev, evs in trace.ops.items():
        runs = sorted((s, s + d) for name, s, d in trace.modules.get(dev, ())
                      if rx_mod.search(name))
        den = sum(e - s for s, e in runs)
        if den <= 0 or (best and den <= best[1]):
            continue
        starts = [s for s, _ in runs]
        num = 0.0
        for (_, s, _), own, under in zip(evs, own_seconds(evs),
                                         trace.scopes.get(dev, ())):
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < runs[k][1] and rx_scope.search(under):
                num += own
        best = (num, den)
    if best is None or best[0] <= 0:
        return None
    return 100.0 * best[0] / best[1]


def collective_exposed_share(trace: Trace) -> float:
    """Time in which a collective runs and no other operation does,
    over the window, on the worst device, in percent."""
    t0, t1 = window_of(trace)
    worst = 0.0
    found = False
    for evs in trace.ops.values():
        evs = leaf_events(evs)
        coll = [ev for ev in evs if COLLECTIVE_RE.search(ev[0])]
        if not coll:
            continue
        found = True
        rest = [ev for ev in evs if not COLLECTIVE_RE.search(ev[0])]
        exposed = total(clip(subtract(union(coll), union(rest)), t0, t1))
        worst = max(worst, exposed / (t1 - t0))
    if not found:
        raise LookupError("no collective in this trace")
    return 100.0 * worst
