"""One general load generator for served systems, driven by a traffic
file's parameters.

It knows two loops. ``closed``: a fixed number of clients, each sending
its next request when the last token of the previous one has arrived.
``open``: requests sent on a schedule, whatever the system does.

The seed never changes the amount of work. Lengths come from the
traffic file's distributions as a fixed stratified set (the i-th of n
values is the quantile (i+0.5)/n), and so do the gaps between arrivals.
Their order is drawn from the file's ``schedule_seed``, so every run
sends the same schedule and the seed draws the token ids alone; a file
without that key lets the seed draw the order too (the same set of
sizes and gaps, in another order).
"""

from __future__ import annotations

import dataclasses
import math
import queue
import statistics
import threading
import time
from typing import Callable, Iterable, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def stratified(dist: dict, n: int) -> np.ndarray:
    """n whole numbers that stand for ``dist``: its quantiles at
    (i+0.5)/n, clipped to [min, max]."""
    if dist["dist"] == "const":
        return np.full((n,), int(dist["value"]), np.int64)
    if dist["dist"] == "lognormal":
        mu = math.log(dist["median"])
        q = [math.exp(mu + dist["sigma"] * _NORMAL.inv_cdf((i + 0.5) / n))
             for i in range(n)]
        return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n gaps standing for a Poisson process of ``rate`` a second: the
    exponential's quantiles at (i+0.5)/n, scaled to mean exactly
    1/rate."""
    q = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return q / q.mean() / rate


@dataclasses.dataclass
class Request:
    index: int
    client: int
    due: float                    # seconds after the loop's start
    prompt: np.ndarray
    max_new: int
    # filled in as it runs
    sent: Optional[float] = None  # perf_counter
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    cut: bool = False             # stopped by the harness at the close
    trace_id: Optional[str] = None

    @property
    def finished(self) -> bool:
        return self.error is None and not self.cut \
            and len(self.tokens) == self.max_new


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, size=(int(n),), dtype=np.int64) \
        .astype(np.int32)


def open_schedule(traffic: dict, seed: int, seconds: float, vocab: int
                  ) -> List[Request]:
    """Every request due in [0, seconds) of an open loop. The order of
    the gaps and of the sizes is drawn from the traffic file's
    ``schedule_seed`` where it has one (every run then sends the same
    schedule and ``seed`` draws the token ids alone), else from
    ``seed``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(traffic["schedule_seed"]) \
        if "schedule_seed" in traffic else rng
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    gaps = exponential_gaps(traffic["rate_rps"], n)
    prompts = stratified(traffic["prompt_tokens"], n)
    outs = stratified(traffic["output_tokens"], n)
    order.shuffle(gaps)
    order.shuffle(prompts)
    order.shuffle(outs)
    due = np.cumsum(gaps) - gaps[0]      # the first is due at 0
    return [Request(i, i, float(due[i]), token_ids(rng, prompts[i], vocab),
                    int(outs[i])) for i in range(n)]


def closed_lengths(traffic: dict) -> tuple:
    """(prompt tokens, new tokens) of every request of a closed loop:
    constants of the traffic file."""
    return (int(stratified(traffic["prompt_tokens"], 1)[0]),
            int(stratified(traffic["output_tokens"], 1)[0]))


Send = Callable[[Request], Iterable[int]]


def _drive(req: Request, send: Send, stop: Optional[threading.Event],
           on_token: Optional[Callable[[], None]] = None):
    """Send one request and record when each token came back."""
    req.sent = time.perf_counter()
    try:
        stream = send(req)
        try:
            for tok in stream:
                req.token_times.append(time.perf_counter())
                req.tokens.append(int(tok))
                if on_token is not None:
                    on_token()
                if stop is not None and stop.is_set():
                    req.cut = len(req.tokens) < req.max_new
                    break
        finally:
            close = getattr(stream, "close", None)
            if close is not None:
                close()
    except Exception as e:  # noqa: BLE001 — a failed request is counted
        req.error = f"{type(e).__name__}: {e}"


class ClosedLoop:
    """``clients`` threads; each sends its next request when the last
    token of the previous one arrived, until ``stop``. Every request is
    full size. The clients start one after another: client i sends its
    first request once client 0 has received ``start_every_tokens * i``
    tokens, so in the steady state one request ends, and one prompt
    arrives, every ``start_every_tokens`` decode steps, whatever the
    system's speed."""

    def __init__(self, traffic: dict, seed: int, vocab: int, send: Send):
        self.traffic, self.vocab, self.send = traffic, vocab, send
        self.clients = int(traffic["clients"])
        self.every = int(traffic.get("start_every_tokens", 0))
        self.stop = threading.Event()
        self.requests: List[Request] = []
        self._lock = threading.Lock()
        self._lead = 0                  # tokens client 0 has received
        self._lead_moved = threading.Condition()
        self._rngs = [np.random.default_rng([seed, c])
                      for c in range(self.clients)]
        self._threads = [threading.Thread(target=self._run, args=(c,),
                                          daemon=True,
                                          name=f"bench-client-{c}")
                         for c in range(self.clients)]
        self.t_start: Optional[float] = None

    def _lead_token(self):
        with self._lead_moved:
            self._lead += 1
            self._lead_moved.notify_all()

    def _wait_turn(self, c: int):
        with self._lead_moved:
            while self._lead < self.every * c and not self.stop.is_set():
                self._lead_moved.wait(0.1)

    def _run(self, c: int):
        self._wait_turn(c)
        # client 0 counts its tokens only until the last client has
        # started: nobody waits for them after that
        counted = self._lead_token if c == 0 and self.every else None
        p, n = closed_lengths(self.traffic)
        k = 0
        while not self.stop.is_set():
            req = Request(k, c, 0.0, token_ids(self._rngs[c], p, self.vocab),
                          n)
            with self._lock:
                self.requests.append(req)
            _drive(req, self.send, self.stop, counted)
            if req.error is not None:
                return
            if counted and self._lead >= self.every * self.clients:
                counted = None
            k += 1

    def start(self):
        self.t_start = time.perf_counter()
        for t in self._threads:
            t.start()
        return self

    def snapshot(self) -> List[Request]:
        with self._lock:
            return list(self.requests)

    def all_started(self) -> bool:
        """Every client has had a token back (or a client has failed,
        which ends the wait)."""
        reqs = self.snapshot()
        if any(r.error for r in reqs):
            return True
        return len({r.client for r in reqs if r.tokens}) == self.clients

    def close(self, timeout: float = 60.0):
        self.stop.set()
        with self._lead_moved:
            self._lead_moved.notify_all()
        for t in self._threads:
            t.join(timeout)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"clients did not stop: {alive}")


class OpenLoop:
    """Requests sent when they are due by a pool of waiting threads;
    one dispatcher thread keeps the schedule."""

    def __init__(self, schedule: List[Request], send: Send,
                 workers: int = 192):
        self.schedule, self.send = schedule, send
        self._q: "queue.Queue" = queue.Queue()
        self._workers = [threading.Thread(target=self._work, daemon=True,
                                          name=f"bench-sender-{i}")
                         for i in range(workers)]
        self._disp = threading.Thread(target=self._dispatch, daemon=True,
                                      name="bench-dispatch")
        self.t_start: Optional[float] = None

    def _work(self):
        while True:
            req = self._q.get()
            if req is None:
                return
            _drive(req, self.send, None)

    def _dispatch(self):
        for req in self.schedule:
            delay = self.t_start + req.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self._q.put(req)

    def start(self):
        for w in self._workers:
            w.start()
        self.t_start = time.perf_counter()
        self._disp.start()
        return self

    def close(self, drain_s: float):
        """Wait for the schedule's end and then for every answer, up to
        ``drain_s`` past the last due time; what has not come by then
        never came."""
        self._disp.join()
        for _ in self._workers:
            self._q.put(None)
        deadline = time.perf_counter() + drain_s
        for w in self._workers:
            w.join(max(0.0, deadline - time.perf_counter()))
        for req in self.schedule:
            if not req.finished and req.error is None:
                req.error = "no answer within the drain time"
