"""``--rehearse-cpu`` of every cell end to end, as the driver starts a
run: a process of its own from the root of the checkout. And the other
side of it: without a chip the benchmark prints no result."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest

ROOT = os.path.dirname(manifest.HERE)
BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(*extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *extra], cwd=ROOT, env=e,
        capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_of_a_cell(workload, trace):
    p = _run("--workload", workload, "--seed", str(2**31 + 77),
             "--seconds", "3", "--trace", str(trace), "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    result = json.loads(lines[-1])
    cell = manifest.Cell(BENCH, workload)
    assert list(result)[0] == "rehearsal" and list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cell.chips
    assert "busy_s" not in result["device"] and "breakdown" not in result
    want = cell.per_layer() if trace else cell.end_to_end()
    names = {m["name"] for m in want}
    assert set(result["metrics"]) <= names
    if not trace:
        assert set(result["metrics"]) == names
    for m in want:
        # a share of a peak or of a roofline is a device metric: none
        # of them is printed from a CPU run
        if m["unit"] == "%" and m["source"] == "device_trace":
            assert m["name"] not in result["metrics"]
    # the numbers compared come last on standard error too
    tail = p.stderr.strip().splitlines()[-(len(result["compared"]) + 1):]
    assert tail[-1] == "correct = True"
    assert all(ln.startswith("compared ") for ln in tail[:-1])
    if cell.config["kind"] == "serve_decoder":
        census = json.loads(next(ln for ln in lines
                                 if ln.startswith("census: "))[8:])
        assert census["decode_ticks"] > 0 and census["prefill_ticks"] > 0
        assert census["attended_positions"] > census["decode_tokens"] > 0
        if trace:
            closed = cell.traffic["loop"] == "closed"
            key = "compiles_in_window." + ("closed" if closed else "open")
            assert result["metrics"][key]["value"] == 0


def test_without_a_chip_there_is_no_result():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "platform is 'cpu'" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    p = _run("--workload", "no.such-cell", "--seed", "1", "--seconds", "1",
             "--trace", "0", "--rehearse-cpu")
    assert p.returncode != 0 and "no workload" in p.stderr
