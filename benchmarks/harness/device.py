"""The device a run is on: found, checked against the table of peaks,
and described in the result line."""

from __future__ import annotations

import json
import os
from typing import List

from .manifest import HERE


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark can stand on."""


def peaks_table() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)["devices"]


def claim(chips: int, rehearse_cpu: bool):
    """The first ``chips`` devices and their peaks. Fails, printing
    nothing that looks like a result, unless the platform is ``tpu``
    and the kind is in ``peaks.json`` (``rehearse_cpu`` lifts both
    checks and returns no peaks)."""
    import jax
    devs: List = jax.devices()
    if rehearse_cpu:
        if len(devs) < chips:
            raise NoChip(f"rehearsal wants {chips} virtual devices, "
                         f"jax has {len(devs)}")
        return devs[:chips], None
    if devs[0].platform != "tpu":
        raise NoChip(f"platform is {devs[0].platform!r}, not 'tpu'")
    table = peaks_table()
    if devs[0].device_kind not in table:
        raise NoChip(f"device_kind {devs[0].device_kind!r} is not in "
                     "benchmarks/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax has "
                     f"{len(devs)}")
    return devs[:chips], table[devs[0].device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the
    backend reports none, as the CPU does)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def describe(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
