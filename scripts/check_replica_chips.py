#!/usr/bin/env python
"""One process per chip, on real chips: N ``llama:`` seats of one
:class:`ReplicaGroup`, each holding one chip of the host.

Run it on a multi-chip TPU host through the chip tool::

    chiprun --chips 4 -- python scripts/check_replica_chips.py

The supervisor (this process) never imports jax, so it holds no chip.
Each seat is handed one chip through its environment
(``zoo_tpu.serving.ha.seat_chip_envs``). The check: every seat comes up
at the same time, reports platform ``tpu`` with ONE device from its own
``llm_stats``, was started with a distinct ``TPU_VISIBLE_CHIPS``, and
streams tokens through ``HAServingClient``; one seat more than the host
has chips is refused by count. It is not a leg of ``chip_smoke.py``
(the smoke is one process) and it fails off a TPU host.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the smoke's serving width; depth cut to keep four cold boots short
SPEC = ("llama:vocab=32000,hidden=768,n_block=4,n_head=12,n_kv_head=4,"
        "intermediate=2048:slots=4,block=16,blocks=128,tables=16,"
        "buckets=32/128")
MAX_NEW = 16


def check() -> int:
    import numpy as np

    from zoo_tpu.common.compile_cache import ensure_compile_cache
    from zoo_tpu.serving.ha import ReplicaGroup, _host_chips
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.tcp_client import _Connection

    def llm_stats(host, port):
        conn = _Connection(host, port)
        try:
            return conn.rpc({"op": "llm_stats"})["stats"]
        finally:
            conn.close()

    chips = _host_chips(dict(os.environ))
    print(f"host chips visible to the supervisor: {chips}", flush=True)
    assert len(chips) >= 2, "needs a multi-chip TPU host"
    n = len(chips)
    cache_dir = ensure_compile_cache()   # seats inherit the place
    try:
        ReplicaGroup(SPEC, num_replicas=n + 1)
    except ValueError as e:
        print(f"refused {n + 1} seats: {e}", flush=True)
    else:
        raise AssertionError(f"{n + 1} jax seats on {n} chips not refused")

    log_dir = os.path.join("chiprun_out", "replica_chips")
    group = ReplicaGroup(SPEC, num_replicas=n, log_dir=log_dir,
                         max_restarts=0)
    group.start(timeout=300)
    try:
        seats = []
        for i, (host, port) in enumerate(group.endpoints()):
            st = llm_stats(host, port)
            pid = group._monitor.workers[i].proc.pid
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = dict(kv.split(b"=", 1) for kv in
                           f.read().split(b"\0") if b"=" in kv)
            seats.append({
                "seat": i, "pid": pid,
                "chip": env[b"TPU_VISIBLE_CHIPS"].decode(),
                "device": st["device"],
                "decode_impl": st["decode_attention_impl"]})
            print(f"seat {json.dumps(seats[-1])}", flush=True)
        assert len({s["chip"] for s in seats}) == n, seats

        rs = np.random.RandomState(0)
        # every seat serves on its own chip: one stream sent to each
        for host, port in group.endpoints():
            conn = _Connection(host, port)
            try:
                frames = list(conn.stream({
                    "op": "generate", "max_new_tokens": MAX_NEW,
                    "prompt": rs.randint(1, 32000, (24,))
                    .astype(np.int32)}))
            finally:
                conn.close()
            assert not any(f.get("error") for f in frames), frames
            assert sum(len(f.get("tokens", ())) for f in frames) \
                == MAX_NEW, frames
        # and the group serves through the HA client
        client = HAServingClient(group.endpoints(), hedge=False,
                                 deadline_ms=600_000)
        try:
            streams = [list(client.generate(
                rs.randint(1, 32000, (20 + 7 * j,)), MAX_NEW))
                for j in range(2 * n)]
        finally:
            client.close()
        assert all(len(s) == MAX_NEW for s in streams), \
            [len(s) for s in streams]
        served = [llm_stats(host, port)["generated_tokens"]
                  for host, port in group.endpoints()]
        assert all(g >= MAX_NEW for g in served), \
            f"a seat served nothing: {served}"
        assert sum(served) == MAX_NEW * 3 * n, served
        assert group.restarts() == 0
        assert all(s["device"]["platform"] == "tpu"
                   and len(s["device"]["ids"]) == 1 for s in seats), seats
        assert all(s["decode_impl"] == "flash" for s in seats), seats
    finally:
        group.stop()
    assert "jax" not in sys.modules, "the supervisor imported jax"
    print(f"ok: {n} llama seats, one chip each "
          f"({[s['chip'] for s in seats]}), {len(streams)} streams x "
          f"{MAX_NEW} tokens, tokens per seat {served}, supervisor "
          f"jax-free, compile_cache={cache_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(check())
