"""One process per chip: ``ReplicaGroup``'s seat -> chip environment.

Checked on synthetic seats: the group is built for a jax spec (so the
assignment happens), then every seat's command is swapped for a tiny
jax-free python that reports the chip it was handed. No jax, no chip.
"""

import os
import signal
import sys
import time

import pytest

from zoo_tpu.serving.ha import ReplicaGroup, seat_chip_envs

TPU_HOST = {"JAX_PLATFORMS": "", "TPU_VISIBLE_CHIPS": "4,5,6,7"}


def test_each_jax_seat_gets_one_distinct_chip():
    envs = seat_chip_envs("llama:tiny", 4, dict(TPU_HOST))
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["4", "5", "6", "7"]
    for e in envs:
        # a one-chip process topology, so libtpu does not wait for peers
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_more_jax_seats_than_chips_is_refused_by_count():
    with pytest.raises(ValueError, match=r"5 replica seats.*4 chip"):
        seat_chip_envs("llama:tiny", 5, dict(TPU_HOST))
    with pytest.raises(ValueError, match=r"5 replica seats.*4 chip"):
        ReplicaGroup("llama:tiny", num_replicas=5, env=dict(TPU_HOST))


def test_seats_that_open_no_tpu_are_left_alone():
    # jax-free specs never touch a chip
    assert seat_chip_envs("synthetic:double+synthllm:slots=2", 9,
                          dict(TPU_HOST)) == [{}] * 9
    # the CPU rig holds jax to the host
    cpu = dict(TPU_HOST, JAX_PLATFORMS="cpu")
    assert seat_chip_envs("llama:tiny", 9, cpu) == [{}] * 9


_SEAT = (
    "import os, time\n"
    "with open(os.environ['SEAT_REPORT'], 'a') as f:\n"
    "    f.write(os.environ['TPU_VISIBLE_CHIPS'] + ' '\n"
    "            + os.environ['ZOO_LLM_ROLE'] + '\\n')\n"
    "time.sleep(120)\n")


def _lines(path):
    try:
        with open(path) as f:
            return f.read().split("\n")[:-1]
    except FileNotFoundError:
        return []


def _wait(cond, what, timeout=30.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, what
        time.sleep(0.05)


def test_a_respawned_seat_keeps_its_chip(tmp_path):
    group = ReplicaGroup("llama:tiny", num_replicas=3, max_restarts=2,
                         env=dict(TPU_HOST),
                         roles=["prefill", "decode", "decode"])
    reports = [str(tmp_path / f"seat-{i}") for i in range(3)]
    for w, report in zip(group._monitor.workers, reports):
        w.cmd = [sys.executable, "-c", _SEAT]
        w.env["SEAT_REPORT"] = report
    group._monitor.start()
    try:
        _wait(lambda: all(len(_lines(r)) == 1 for r in reports),
              "seats did not come up")
        assert [_lines(r)[0] for r in reports] == \
            ["4 prefill", "5 decode", "6 decode"]
        os.kill(group._monitor.workers[1].proc.pid, signal.SIGKILL)
        _wait(lambda: len(_lines(reports[1])) == 2,
              "killed seat was not respawned")
        # same chip (and, like before, same role) after the respawn
        assert _lines(reports[1]) == ["5 decode", "5 decode"]
        assert group.restarts() == 1
    finally:
        group._monitor.stop()
