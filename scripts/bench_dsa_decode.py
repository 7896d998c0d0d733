#!/usr/bin/env python
"""Microbenchmark of ``zoo_dsa_decode`` alone, on the chip.

A decode tick's worth of the kernel (``--layers`` layers under one
``lax.scan`` over the stacked ``kv`` leaf, as the serving step runs it)
at the sizes of ``keye-vl-2.0.doc32k-closed``: 16 slots, 32 query heads
over 4 K/V heads of 128 (a token's K and V one ``(8, 128)`` bf16 tile),
4 layers of 8,704 pages of 64, 2,048 selected tokens a slot scattered
over the whole pool, every one live. Timed from a profiler trace: the
device time of the operations named ``zoo_dsa_decode``, a call, the
nanoseconds a copied token costs, and the share of the byte floor that
is (2,048 B a token over 819 GB/s, what ``dsa_decode_kernel_roofline``
reads).

    python scripts/bench_dsa_decode.py --steps 128,256 \
        --parent <another checkout>      # on a TPU host

``--steps`` sets ``tokens_per_step`` (0: the module's own),
``--parent`` times other checkouts' kernels beside it (a comma-separated
list). The plain-XLA twin ``dsa_decode_reference`` is timed as a
yardstick (its wall time a call: it is many operations, not one
kernel), and every form's result is held against it
(``gap_to_reference``) and against the first kernel's
(``gap_to_first_kernel``: 0 where the products and their order are the
same). Exits non-zero off the TPU: a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench_paged_decode import load_kernel, measure  # noqa: E402
from benchmarks.harness.device import peaks_table  # noqa: E402
from zoo_tpu.ops.pallas import dsa_decode as dd  # noqa: E402

# slots, query heads, K/V heads, head width, page, (layers, pages), tokens
FULL = (16, 32, 4, 128, 64, (4, 8704), 2048)
# --rehearse-cpu: the same code at a size the interpreter can run
TINY = (4, 8, 4, 128, 16, (2, 64), 256)


def operands(size, seed):
    S, H, G, D, bs, (n_layer, n_blocks), K = size
    rs = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    kv = jax.random.normal(key, (n_layer, n_blocks, bs) + dd.kv_row_shape(G, D),
                           jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, H, D), jnp.float32)
    rows = jnp.asarray(rs.randint(0, n_blocks * bs, (S, K)), jnp.int32)
    n_live = jnp.full((S,), K, jnp.int32)
    return q, kv, rows, n_live


def decode_call(kernel, layers, **kw):
    @jax.jit
    def call(q, kv, rows, n_live):
        def layer(acc, i):
            out = kernel(q, kv, rows, n_live, layer=i % kv.shape[0], **kw)
            return acc + out, None
        return jax.lax.scan(layer, jnp.zeros_like(q), jnp.arange(layers))[0]
    return call


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", default="0")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/bench_dsa_decode.jsonl")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes under the interpreter; times are nan")
    a = ap.parse_args()
    size = TINY if a.rehearse_cpu else FULL
    if jax.default_backend() != "tpu" and not a.rehearse_cpu:
        sys.exit("bench_dsa_decode: no TPU; a CPU time is no device number")
    ints = lambda s: [int(x) for x in s.split(",")]
    forms = [("reference", None, dd.dsa_decode_reference)]
    forms += [(path, None,
               load_kernel(path, "dsa_decode", "dsa_paged_decode"))
              for path in (a.parent or "").split(",") if path]
    forms += [("kernel", t, dd.dsa_paged_decode) for t in ints(a.steps)]
    args = operands(size, a.seed)
    S, _, G, D, _, _, K = size
    copies = S * K * a.layers
    kind = jax.devices()[0].device_kind
    peaks = peaks_table()
    peak = peaks[next(iter(peaks)) if a.rehearse_cpu else kind][
        "hbm_bytes_per_s"]
    floor = copies * 2 * G * D * 2 / peak
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    want = first = None
    with open(a.out, "a") as sink:
        for form, steps, kernel in forms:
            kw = {}
            if form != "reference":
                kw["interpret"] = a.rehearse_cpu
            if steps:
                kw["tokens_per_step"] = steps
            try:
                busy, wall, out = measure(decode_call(kernel, a.layers, **kw),
                                          args, a.calls, "zoo_dsa_decode")
                want = out if want is None else want
                first = out if first is None and form != "reference" else first
                gap = float(np.abs(out - want).max())
                same = float(np.abs(out - first).max()) if form != "reference" \
                    else float("nan")
            except Exception as e:   # one form failing is a row
                busy = wall = gap = same = float("nan")
                print(f"# {form} steps={steps}: "
                      f"{type(e).__name__}: {str(e)[:300]}", flush=True)
            row = {"form": form, "tokens_per_step": steps,
                   "kernel_ms": busy * 1e3, "wall_ms": wall * 1e3,
                   "ns_per_copy": busy * 1e9 / copies,
                   "floor_ms": floor * 1e3,
                   "floor_share_pct": 100 * floor / busy,
                   "gap_to_reference": gap, "gap_to_first_kernel": same,
                   "device": kind}
            print(json.dumps(row), flush=True)
            sink.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
