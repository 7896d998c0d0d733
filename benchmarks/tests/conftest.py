"""The benchmark's own tests run on the CPU, on four virtual devices
(the four-chip cell rehearses on them). Both have to be said before jax
is imported anywhere."""

import copy
import json
import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def _data(name):
    with open(os.path.join(BENCH, "tests", "data", name)) as f:
        return json.load(f)


@pytest.fixture
def training_cells(monkeypatch):
    """Lays the four-chip training cell that was measured and left out
    of BENCHMARK.json (PERF.md says why) over the manifest, so that the
    path across chips stays tested on four virtual devices:
    ``data/fit-4chip.cell.json``. Returns its name."""
    from harness import manifest
    bench = manifest.load_benchmark()
    extra = _data("fit-4chip.cell.json")
    name = extra["workload"]["name"]
    bench["workloads"].append(extra["workload"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in extra["reports"]:
            m["workloads"].append(name)
    bench["per_layer"] += extra["per_layer"]
    monkeypatch.setattr(manifest, "load_benchmark",
                        lambda: copy.deepcopy(bench))
    return name


@pytest.fixture
def second_architectures(monkeypatch, tmp_path):
    """Lays ``data/second-architectures.json`` over the manifest: a
    second served decoder and a second trained classifier whose every
    file (configuration, adapter, reference, formulas, rehearsal sizes,
    limits) is under ``tests/data/``, as a later PR would bring them
    under ``benchmarks/``. Each of their cells is listed under every
    metric that the accepted cell it ``reports_as`` is listed under.
    Returns the new cells' names."""
    from harness import manifest
    data = os.path.join(BENCH, "tests", "data")
    extra = _data("second-architectures.json")
    bench = manifest.load_benchmark()
    bench["configs"] += extra["configs"]
    bench["workloads"] += extra["workloads"]
    for cell, twin in extra["reports_as"].items():
        for m in bench["end_to_end"] + bench["per_layer"]:
            if twin in m.get("workloads", ()):
                m["workloads"].append(cell)
    monkeypatch.setattr(manifest, "load_benchmark",
                        lambda: copy.deepcopy(bench))
    # the data files of ``benchmarks/`` with those of ``tests/data/``
    # laid over them, as one directory of links
    for sub in ("traffic", "metrics", "limits", "rehearsal"):
        os.makedirs(tmp_path / sub)
        for root in (BENCH, data):
            if not os.path.isdir(os.path.join(root, sub)):
                continue
            for name in os.listdir(os.path.join(root, sub)):
                link = tmp_path / sub / name
                if not link.exists():
                    os.symlink(os.path.join(root, sub, name), link)
    monkeypatch.setattr(manifest, "HERE", str(tmp_path))
    # ``adapters`` and ``reference`` are packages without an __init__,
    # so a second directory of the same name on the path adds to them
    monkeypatch.syspath_prepend(data)
    return [w["name"] for w in extra["workloads"]]
