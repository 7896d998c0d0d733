"""Readers of the counters the program keeps of itself.

``ctx.counters`` holds the growth of each of the program's counter
families over the measured window, summed over their label sets
(``harness/serve_cell.py::ServedDecoder.counters``). A metric file names
one of these readers as ``"reader": "program_counters:<name>"``.

Every reader returns ``None`` where it finds nothing to read: a program
without the counter (the parent of the PR that brought it), or nothing
to divide by.
"""

from __future__ import annotations

from typing import Optional


def counter_per(ctx, p: dict) -> Optional[float]:
    """The growth of ``counter`` over the window per unit of growth of
    ``per``, times ``scale``."""
    if ctx.counters is None or p["counter"] not in ctx.counters:
        return None
    den = ctx.counters.get(p["per"], 0.0)
    if not den:
        return None
    return ctx.counters[p["counter"]] / den * p.get("scale", 1.0)
