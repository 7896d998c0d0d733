"""The reduction from a trace to numbers, on a small recorded trace
whose answers can be worked out by hand (``data/small_trace.json``:
two devices, 40 ms, one collective, one 6 ms gap under a host span and
one of 5 us)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace as tr  # noqa: E402


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return tr.Trace.from_json(json.load(f))


def test_union_merges_overlaps_and_keeps_gaps():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0),
           ("d", 3.2, 0.1), ("zero", 9.0, 0.0)]
    assert tr.union(evs) == [(0.0, 1.5), (3.0, 4.0)]
    assert tr.total(tr.clip(tr.union(evs), 1.0, 3.5)) == pytest.approx(1.0)
    assert tr.subtract([(0.0, 5.0)], tr.union(evs)) == [(1.5, 3.0),
                                                        (4.0, 5.0)]


def test_window_busy_and_idle(small):
    t0, t1 = tr.window_of(small)
    assert (t0, t1) == pytest.approx((10.0, 10.04))
    busy = tr.busy_by_device(small, t0, t1)
    # device 0: 10.000-10.014, 10.020-10.030, 10.030005-10.040
    assert busy["/device:TPU:0"] == pytest.approx(0.033995)
    assert busy["/device:TPU:1"] == pytest.approx(0.030)
    b, w = tr.busy_and_window(small)
    assert w == pytest.approx(0.04)
    assert b == pytest.approx((0.033995 + 0.030) / 2)
    # the fullest device decides the idle share
    assert tr.idle_share(small) == pytest.approx(
        100 * (1 - 0.033995 / 0.04))


def test_gaps_go_to_the_shortest_host_span_over_them(small):
    gaps = dict((k, v) for k, v in tr.idle_gaps(small))
    assert gaps["engine.build_tick"] == pytest.approx(0.006)
    assert gaps["between_ops_under_20us"] == pytest.approx(5e-6)
    assert "fit.call" not in gaps and "unattributed" not in gaps


def test_ops_are_matched_and_named(small):
    top = tr.top_ops(small, 2)
    assert top[0][0] == "fusion.1_bf16_8_128_"
    # 20 ms on each device, averaged over the devices
    assert top[0][1] == pytest.approx(0.020)
    assert tr.clean("%copy.3 = s8[2,16]{1,0} copy(...)") == "copy.3_s8_2_16_"
    secs, n = tr.matched_time(small.modules, "epoch_fn")
    assert (secs, n) == (pytest.approx(0.040), 2)
    assert tr.matched_time(small.modules, "no_such") == (0.0, 0)


def test_leaves_are_the_events_that_hold_no_other(small):
    names = [tr.clean(ev[0]) for ev in
             tr.leaf_events(small.ops["/device:TPU:1"])]
    assert len(names) == 3 and not any(n.startswith("while") for n in names)
    assert len(tr.leaf_events(small.ops["/device:TPU:0"])) == 4


def test_an_operation_keeps_only_its_own_time():
    evs = [("while.1", 0.0, 10.0), ("fusion.a", 1.0, 3.0),
           ("cond.2", 5.0, 4.0), ("fusion.b", 5.5, 1.0),
           ("fusion.a", 7.0, 1.5), ("copy.c", 12.0, 1.0)]
    own = {}
    for name, t in tr.self_times(evs):
        own[name] = own.get(name, 0.0) + t
    assert own == pytest.approx({"while.1": 3.0, "fusion.a": 4.5,
                                 "cond.2": 1.5, "fusion.b": 1.0,
                                 "copy.c": 1.0})
    top = tr.top_ops(tr.Trace({"d": evs}, {}, []), 2)
    assert [name for name, _ in top] == ["fusion.a", "while.1"]


def test_module_census_counts_the_first_devices_executables(small):
    assert tr.module_census(small) == {
        "jit_epoch_fn": [2, pytest.approx(0.040)]}


def test_collective_exposed_is_the_worst_device(small):
    # device 0: all-reduce 10.008-10.014, covered until 10.010: 4 ms
    # device 1: all-reduce 10.010-10.020, nothing runs beside it but the
    # while that holds all three operations: 10 ms
    assert tr.collective_exposed_share(small) == pytest.approx(25.0)
    no_coll = tr.Trace({"d": [("fusion", 0.0, 1.0)]}, {}, [])
    with pytest.raises(LookupError):
        tr.collective_exposed_share(no_coll)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        tr.window_of(tr.Trace({}, {}, []))


def test_dispatches_are_counted_on_the_device_that_ran_most(small):
    from harness import readers
    ctx = readers.Context(rec=None, t0=0.0, t1=1.0, cfg={}, traffic={},
                          chips=2, peaks=None, facts={}, counters={},
                          trace=small, traced=(0.0, 1.0),
                          traced_census={"epochs": 1})
    count = readers.READERS["module_count_per"]
    # device 0 ran the epoch twice, device 1 once beside another one
    assert count(ctx, {"module": "epoch_fn", "per": "epochs"}) == 2
    assert count(ctx, {"module": "jit_other", "per": "epochs"}) == 1
    assert count(ctx, {"per": "no_such_count"}) is None
