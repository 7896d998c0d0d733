"""The seed may choose token ids and weights and nothing else: two
seeds give the closed loop the same lengths, the same order of starts
and the same order of events, and the open loop the same schedule (or, where
the traffic file leaves the order to the seed, the same set of sizes
and gaps)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from harness import traffic as tg

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


class TickingServer:
    """Stands where the engine does: a clock of its own ticks, and every
    stream's next token comes at the next tick, all lanes in step, so
    the order of events is a function of the schedule alone."""

    def __init__(self):
        self.tick, self.moved = 0, threading.Condition()
        self.stop = threading.Event()
        self.starts = {}                  # (client, index) -> tick
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self.stop.wait(0.002):
            with self.moved:
                self.tick += 1
                self.moved.notify_all()

    def send(self, req):
        with self.moved:
            self.starts[(req.client, req.index)] = self.tick
        for k in range(req.max_new):
            with self.moved:
                seen = self.tick
                while self.tick == seen and not self.stop.is_set():
                    self.moved.wait(1.0)
            yield int(req.prompt[k % len(req.prompt)])


def _closed_run(seed, rounds=3):
    tr = dict(_traffic("decode-closed"))
    tr.update(clients=8, start_every_tokens=2,
              prompt_tokens={"dist": "const", "value": 12},
              output_tokens={"dist": "const", "value": 16})
    srv = TickingServer()
    loop = tg.ClosedLoop(tr, seed, 1000, srv.send).start()
    while min((sum(1 for r in loop.snapshot() if r.client == c
                   and r.finished) for c in range(tr["clients"])),
              default=0) < rounds:
        time.sleep(0.005)
    assert loop.all_started()
    loop.close()
    srv.stop.set()
    return tr, loop.snapshot(), srv.starts


def test_closed_loop_two_seeds_same_schedule_other_tokens():
    tr, a, starts = _closed_run(1)
    _, b, _ = _closed_run(2_147_483_999)

    def schedule(reqs):
        return sorted((r.client, r.index, len(r.prompt), r.max_new)
                      for r in reqs if r.index < 3)

    assert schedule(a) == schedule(b)
    # every request is full size ...
    assert {r.max_new for r in a} == {16}
    assert {len(r.prompt) for r in a + b} == {12}
    # ... and client i starts once client 0 has 2 * i tokens, so the
    # clients' first requests begin two ticks apart (a tick's slack for
    # the thread that wakes) and no two of them together
    first = [starts[(c, 0)] - starts[(0, 0)] for c in range(8)]
    assert all(2 * c <= first[c] <= 2 * c + 1 for c in range(8)), first
    ids_a = {(r.client, r.index): r.prompt.tolist() for r in a}
    ids_b = {(r.client, r.index): r.prompt.tolist() for r in b}
    shared = sorted(set(ids_a) & set(ids_b))
    assert shared and all(ids_a[k] != ids_b[k] for k in shared)
    # the same seed gives the same ids again
    _, a2, _ = _closed_run(1)
    ids_a2 = {(r.client, r.index): r.prompt.tolist() for r in a2}
    assert all(ids_a[k] == ids_a2[k] for k in set(ids_a) & set(ids_a2))


def test_the_cells_own_file_fixes_every_size():
    tr = _traffic("decode-closed")
    assert tr["clients"] == 32 and tr["start_every_tokens"] == 8
    assert tg.closed_lengths(tr) == (1024, 256)
    # the last client starts inside client 0's first request, so no
    # request is ever shorter than a full one
    assert tr["start_every_tokens"] * (tr["clients"] - 1) \
        < tr["output_tokens"]["value"]


def test_open_loop_two_seeds_same_schedule_other_tokens():
    tr = _traffic("chat-open")
    a = tg.open_schedule(tr, 3, 45.0, 32768)
    b = tg.open_schedule(tr, 2_147_483_650, 45.0, 32768)
    assert len(a) == len(b) == round(tr["rate_rps"] * 45.0)
    # the file fixes the order: due times and both lengths are the same
    # request for request, and only the token ids differ
    key = lambda r: (r.due, len(r.prompt), r.max_new)
    assert list(map(key, a)) == list(map(key, b))
    assert all(x.prompt.tolist() != y.prompt.tolist()
               for x, y in zip(a, b) if len(x.prompt) >= 8)
    _check_sets(tr, a)


def test_open_loop_without_a_schedule_seed_the_seed_draws_the_order():
    tr = {k: v for k, v in _traffic("chat-open").items()
          if k != "schedule_seed"}
    a = tg.open_schedule(tr, 3, 45.0, 32768)
    b = tg.open_schedule(tr, 2_147_483_650, 45.0, 32768)
    for what in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(what, a)) == sorted(map(what, b))
        assert list(map(what, a)) != list(map(what, b))
    _check_sets(tr, a)
    _check_sets(tr, b)


def _check_sets(tr, rs):
    """The stratified sets of gaps and sizes that the file stands for."""
    # the same stratified set of gaps, less the one after the last request
    full = np.sort(tg.exponential_gaps(tr["rate_rps"], len(rs)))
    gaps = np.sort(np.diff([r.due for r in rs]))
    assert any(np.isclose(np.delete(full, i), gaps).all()
               for i in range(len(full)))
    assert rs[0].due == 0.0 and rs[-1].due < 45.0
    p = [len(r.prompt) for r in rs]
    assert min(p) >= 16 and max(p) <= 2048
    assert np.median(p) == pytest.approx(256, rel=0.05)
    o = [r.max_new for r in rs]
    assert min(o) >= 8 and max(o) <= 384
    assert np.median(o) == pytest.approx(96, rel=0.05)
