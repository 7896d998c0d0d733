"""Step an ``LLMEngine`` by hand: the one deterministic schedule the
white-box tests use.

``tick(eng)``, engine NOT started, runs one pass of the scheduler and
then that pass's landing on the calling thread: the same
``LLMEngine._pass`` and ``LLMEngine._land`` the scheduler thread and
the readback thread run, with never more than one tick in flight. A
stream stepped this way emits what the running engine emits; which
streams share a pass is the test's choice, not the threads'.
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
    """One pass and its landing; False when the pass dispatched
    nothing (no decodable lane)."""
    assert eng._thread is None, "tick() steps an engine that is not started"
    item = eng._pass()
    if item is None:
        return False
    eng._land(item)
    return True
