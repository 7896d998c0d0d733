"""The readers of the program's own counters (``program_counters.py``)
on a recorded ``Context``, and the four metric files that read through
them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import program_counters as pc  # noqa: E402
from harness import manifest, readers  # noqa: E402
from harness.spans import Recorder  # noqa: E402

CPU = "zoo_llm_host_cpu_seconds_total"
OFF = "zoo_llm_host_offcpu_seconds_total"
TICKS = "zoo_llm_decode_steps_total"
# the window's growth of each family, as serve_cell.py hands it over
GROWN = {CPU: 0.5, OFF: 2.0, TICKS: 250.0, "zoo_llm_preempt_total": 0.0}


def _ctx(counters):
    return readers.Context(rec=Recorder(), t0=100.0, t1=110.0, cfg={},
                           traffic={}, chips=1, peaks=None, facts={},
                           counters=counters)


@pytest.mark.parametrize("counter,want", [(CPU, 2.0), (OFF, 8.0)])
def test_a_counter_per_tick_in_milliseconds(counter, want):
    p = {"counter": counter, "per": TICKS, "scale": 1000}
    assert pc.counter_per(_ctx(GROWN), p) == pytest.approx(want)
    assert pc.counter_per(_ctx(GROWN), {"counter": counter,
                                        "per": TICKS}) == \
        pytest.approx(want / 1000)


@pytest.mark.parametrize("counters", [
    None,                                           # no counters read
    {k: v for k, v in GROWN.items() if k != CPU},   # the parent's program
    {**GROWN, TICKS: 0.0},                          # no tick in the window
    {CPU: 0.5},                                     # ticks never counted
], ids=["no_counters", "absent_family", "zero_ticks", "absent_per"])
def test_nothing_to_read_is_none(counters):
    p = {"counter": CPU, "per": TICKS, "scale": 1000}
    assert pc.counter_per(_ctx(counters), p) is None


def test_the_four_metric_files_read_through_it():
    """Each serving cell lists its pair of the four, and reads nothing
    from a program without the families."""
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {"mistral-7b.chat-open": ".open"}
    for cell_name in ("mistral-7b.decode-closed", "mistral-7b.chat-open",
                      "glm-4.7-flash.longctx-closed",
                      "minicpm-sala.ctx32k-closed"):
        side = want.get(cell_name, ".closed")
        cell = manifest.Cell(bench, cell_name)
        mine = [m for m in cell.per_layer()
                if m["name"].startswith("host_")]
        assert sorted(m["name"] for m in mine) == [
            "host_cpu_ms_per_tick" + side, "host_offcpu_ms_per_tick" + side]
        got = readers.read_all(_ctx(GROWN), mine)
        assert got == {
            "host_cpu_ms_per_tick" + side: {"value": 2.0, "unit": "ms"},
            "host_offcpu_ms_per_tick" + side: {"value": 8.0, "unit": "ms"}}
        parent = {k: v for k, v in GROWN.items() if k not in (CPU, OFF)}
        assert readers.read_all(_ctx(parent), mine) == {}
