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


def test_scope_share_is_own_time_under_a_scope_inside_an_executable(small):
    # device 0 spent most in ``epoch_fn`` (two runs, 40 ms): fusion.1
    # twice under zoo.mlp, copy.3 under zoo.kv_append, the all-reduce
    # under no scope
    assert tr.scope_share(small, r"zoo\.mlp", "epoch_fn") \
        == pytest.approx(50.0)
    assert tr.scope_share(small, r"zoo\.kv_append", "epoch_fn") \
        == pytest.approx(100 * 0.009995 / 0.040)
    # ``jit_other`` ran on device 1 alone, 10 ms: the one fusion that
    # began inside it; the while that holds it began before
    assert tr.scope_share(small, r"zoo\.mlp", "jit_other") \
        == pytest.approx(100.0)
    # nothing to read is nothing, never 0
    assert tr.scope_share(small, r"zoo\.paged_attend", "epoch_fn") is None
    assert tr.scope_share(small, r"zoo\.mlp", "no_such_module") is None
    bare = tr.Trace(small.ops, small.modules, small.host)
    assert tr.scope_share(bare, r"zoo\.mlp", "epoch_fn") is None
    # a while under the scope counts what it spends itself, once
    nested = tr.Trace(
        {"d": [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 5.0, 2.0)]},
        {"d": [("jit_decode_fn(1)", 0.0, 10.0)]}, [],
        {"d": ["jit(f)/zoo.mlp/while", "jit(f)/zoo.mlp/dot",
               "jit(f)/zoo.paged_attend/call"]})
    assert tr.scope_share(nested, r"zoo\.mlp", "decode_fn") \
        == pytest.approx(80.0)
    assert tr.scope_share(nested, r"zoo\.paged_attend", "decode_fn") \
        == pytest.approx(20.0)
    from harness import readers
    ctx = readers.Context(rec=None, t0=0.0, t1=1.0, cfg={}, traffic={},
                          chips=1, peaks=None, facts={}, counters={},
                          trace=nested)
    assert readers.READERS["scope_share"](
        ctx, {"scope": r"zoo\.mlp", "module": "decode_fn"}) \
        == pytest.approx(80.0)


def _space():
    """A profile of two planes, built with the format's own classes:
    on the device an operation of the same name in two executables,
    traced under two scopes (two metadata entries, one name)."""
    space = tr._xplane_pb2().XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    for i, name in ((1, "tf_op"), (2, "hlo_category"), (300, "fusion")):
        dev.stat_metadata[i].name = name
    for key, name, scope in (
            (7, "%fusion.1 = bf16[8] fusion(...)",
             "jit(_decode_fn)/zoo.mlp/dot_general:"),
            (8, "%fusion.1 = bf16[8] fusion(...)",
             "jit(_prefill_chunk_fn)/zoo.attn_proj/dot_general:"),
            (9, "%copy.8 = bf16[8] copy(...)", None)):
        m = dev.event_metadata[key]
        m.id, m.name = key, name
        m.stats.add(metadata_id=2, ref_value=300)
        m.stats.add(metadata_id=300, uint64_value=12345)
        if scope:
            m.stats.add(metadata_id=1, str_value=scope)
    dev.event_metadata[20].name = "jit__decode_fn(1)"
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=2_000_000_000)
    for key, off_ps, dur_ps in ((7, 0, 3_000_000), (9, 4_000_000, 500_000),
                                (8, 6_000_000, 1_000_000)):
        ops.events.add(metadata_id=key, offset_ps=off_ps, duration_ps=dur_ps)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=2_000_000_000)
    mods.events.add(metadata_id=20, offset_ps=0, duration_ps=5_000_000)
    dev.lines.add(name="Steps").events.add(metadata_id=20)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "tf_op"
    host.event_metadata[7].name = "bench:tick"
    host.event_metadata[7].stats.add(metadata_id=1, str_value="not a device")
    host.event_metadata[8].name = "zoo:llm.tick.decode"
    line = host.lines.add(name="python", timestamp_ns=1_000_000_000)
    line.events.add(metadata_id=7, offset_ps=5_000_000, duration_ps=250_000)
    line.events.add(metadata_id=8, offset_ps=6_000_000, duration_ps=250_000)
    return space


def test_read_xplane_keeps_every_operations_own_scope(tmp_path):
    """Read with the format's own classes: operations and executables
    of the device planes, ``bench:`` spans of the host's, and for every
    operation the scope of its own metadata entry, so that one name in
    two executables keeps two scopes."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space().SerializeToString())
    t = tr.read_xplane(str(path))
    dev = "/device:TPU:0"
    assert [n for n, _, _ in t.ops[dev]] == [
        "%fusion.1 = bf16[8] fusion(...)", "%copy.8 = bf16[8] copy(...)",
        "%fusion.1 = bf16[8] fusion(...)"]
    assert t.ops[dev][2][1:] == pytest.approx((2.000006, 1e-6))
    assert t.scopes == {dev: [
        "jit(_decode_fn)/zoo.mlp/dot_general:", "",
        "jit(_prefill_chunk_fn)/zoo.attn_proj/dot_general:"]}
    assert t.modules == {dev: [("jit__decode_fn(1)", pytest.approx(2.0),
                                pytest.approx(5e-6))]}
    assert t.host == [("tick", pytest.approx(1.000005),
                       pytest.approx(2.5e-7))]
    # the second fusion.1 began outside ``_decode_fn`` and is another
    # scope's: neither its name nor the first one's scope counts it
    assert tr.scope_share(t, r"zoo\.mlp", "_decode_fn") \
        == pytest.approx(60.0)
    assert tr.scope_share(t, r"zoo\.attn_proj", "_decode_fn") is None
    assert tr.Trace.from_json(json.loads(json.dumps(t.to_json()))).scopes \
        == t.scopes


def test_read_xplane_reads_what_the_profiler_wrote(tmp_path):
    """A real profile of this installation's ``jax.profiler`` (the CPU
    has no device plane): every ``bench:`` annotation comes back once,
    with the times ``ProfileData`` gives."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench:step{i}"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    t = tr.read_xplane(path)
    assert sorted(n for n, _, _ in t.host) == ["step0", "step1", "step2"]
    theirs = {ev.name: (ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("bench:")}
    for name, start, dur in t.host:
        assert (start, dur) == pytest.approx(theirs["bench:" + name])
    assert t.ops == {} and t.scopes == {}


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
