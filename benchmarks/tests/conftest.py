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


@pytest.fixture
def four_chip_cell(monkeypatch):
    """Lays the four-chip training cell of ``data/fit-4chip.cell.json``
    over the manifest (it is measured and left out of BENCHMARK.json:
    PERF.md says why), so that the path across chips stays tested on
    four virtual devices. Returns its name."""
    from harness import manifest
    with open(os.path.join(BENCH, "tests", "data",
                           "fit-4chip.cell.json")) as f:
        extra = json.load(f)
    name = extra["workload"]["name"]
    bench = manifest.load_benchmark()
    bench["workloads"].append(extra["workload"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in extra["reports"]:
            m["workloads"].append(name)
    bench["per_layer"] += extra["per_layer"]

    class Cell(manifest.Cell):
        def __init__(self, b, n):
            super().__init__(b, n)
            if n == name:
                self.limits = extra["limits"]

    monkeypatch.setattr(manifest, "load_benchmark",
                        lambda: copy.deepcopy(bench))
    monkeypatch.setattr(manifest, "Cell", Cell)
    return name
