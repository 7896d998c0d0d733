"""The benchmark's harness: manifest, load generation, spans, trace
reduction, readers and the comparison that decides ``correct``.

Everything here is driven by the data files beside it (``configs/``,
``traffic/``, ``metrics/``, ``limits/``, ``peaks.json``) and by
``BENCHMARK.json`` at the root of the checkout; nothing here names a
cell, a configuration or a metric.
"""
