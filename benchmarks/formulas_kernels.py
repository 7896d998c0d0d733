"""Bytes and operations of single kernels, from shapes alone (the whole
step's are in ``formulas.py``). A metric file names one as
``"work": "formulas_kernels:<function>"``."""

from __future__ import annotations

import formulas


def paged_decode_bytes(cfg: dict, traffic: dict, census: dict) -> float:
    """Least bytes the paged decode kernel had to read in the traced
    ticks: the K and V rows of every attended position in every layer
    at the cache's stated type, and for int8 their f32 scales. The
    queries it reads and the partial outputs it writes are left out
    (under 1% at a thousand positions a slot), and so is what the
    kernel reads beyond the last attended row of a block."""
    return formulas.kv_bytes_per_token(cfg) * census["attended_positions"]
