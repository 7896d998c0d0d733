"""Step an ``LLMEngine`` by hand: the one deterministic schedule the
white-box tests use.

``tick(eng)``, engine NOT started, runs one pass of the scheduler, wakes
what has landed the way the scheduler thread's loop does after every
pass, and then runs the landing of what the pass put on ``eng._rbq``
on the calling thread: the same ``LLMEngine._pass``,
``LLMEngine._wake_landed`` and ``LLMEngine._land`` the scheduler thread
and the readback thread run, with never more than one tick in flight. A landing's tokens are in ``h.tokens`` when
``tick`` returns; their readers are woken by the next ``tick`` (after
its dispatch) or by ``eng.stop()``. A stream stepped this way emits
what the running engine emits; which streams share a pass is the
test's choice, not the threads'.
"""

import numpy as np


class HostStepped:
    """``decode_step`` / ``read_tokens`` over ``self.decode``: the fake
    'device' is synchronous, so a batch is just the array."""

    def decode_step(self, prev, host_tokens, use_host, block_tables,
                    positions, sampling):
        prev = np.zeros_like(host_tokens) if prev is None else \
            np.asarray(prev)
        toks = np.where(np.asarray(use_host), host_tokens, prev)
        return self.decode(toks, block_tables, positions, sampling)

    def read_tokens(self, batch):
        return np.asarray(batch)


def tick(eng) -> bool:
    """One pass, the wake-up that follows every pass, and the pass's
    landing; False when the pass dispatched nothing (no decodable
    lane)."""
    assert eng._thread is None, "tick() steps an engine that is not started"
    dispatched = False
    try:
        dispatched = eng._pass()
    finally:
        eng._wake_landed(dispatched)
    if not dispatched:
        return False
    eng._land(eng._rbq.get_nowait())
    return True
