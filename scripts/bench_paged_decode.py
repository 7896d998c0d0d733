#!/usr/bin/env python
"""Microbenchmark of ``zoo_paged_decode`` alone, on the chip.

A decode call's worth of the kernel (``--layers`` layers under one
``lax.scan`` over a stacked int8 cache, as the serving step runs it) at
the Mistral cells' shapes, timed from a profiler trace: the device time
of the operations named ``zoo_paged_decode``, a call, and the share of
the byte floor that is (the K / V rows and scales of the attended
positions over 819 GB/s, what ``paged_decode_kernel_roofline`` reads).
Three regimes: the closed cell's (160 entries, positions 1,024-1,280,
32 live slots), the open cell's (7 live slots, the rest at position 0)
and a long table (640 entries, positions 8k-10k).

    chiprun -- python scripts/bench_paged_decode.py \
        --entries 4,8,16 --splits 1,2,4 --forms a,b \
        --parent .archive_check/parent

``--entries`` overrides the module's ``MAX_ENTRIES`` (N, the table
entries a step attends; 0 leaves the module's own choice), ``--forms b``
swaps the block-diagonal product for the per-head form kept here for
the comparison, ``--parent`` times another checkout's kernel beside it.
Exits non-zero off the TPU: a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import trace as tr  # noqa: E402
from benchmarks.harness.device import peaks_table  # noqa: E402
from zoo_tpu.ops.pallas import paged_decode as pd  # noqa: E402

S, H, N_KV, D, BLOCK = 32, 32, 8, 128, 16
# table width, positions from / to, live slots, (layers held, blocks)
REGIMES = {"closed": (160, 1024, 1280, S, (8, 5120)),
           "open": (160, 1024, 1280, 7, (8, 5120)),
           "long": (640, 8192, 10240, S, (2, 20480))}
# --rehearse-cpu: the same code at a size the interpreter can run
TINY = {"closed": (12, 64, 96, S, (2, 400)),
        "open": (12, 64, 96, 2, (2, 400)),
        "long": (40, 400, 600, S, (2, 1400))}


def attend_per_head(q, pos, firsts, keys, k_scales, values, *, scale, group,
                    m_scr, l_scr, a_scr):
    """Form (b): head ``h``'s rows of the step's N blocks as one
    ``(N * block, D)`` key tile, scores ``(group, N * block)``; the
    head's stretch of every entry's scale row is gathered across lanes.
    Same signature as ``paged_decode._attend`` (the entries of a step
    are consecutive, so column ``c`` is cache index ``firsts[0] + c``)."""
    n_kv, block, _ = keys[0].shape
    f32 = jnp.float32
    wide = (lambda x: x.astype(f32)) if k_scales is not None else (lambda x: x)
    col = jax.lax.broadcasted_iota(jnp.int32, (group, len(keys) * block), 1)
    live = firsts[0] + col <= pos
    vals = None
    for h in range(n_kv):
        rows = slice(h * group, (h + 1) * group)
        lanes = slice(h * block, (h + 1) * block)
        k = jnp.concatenate([wide(k_[h]) for k_ in keys], axis=0)
        s_ = jax.lax.dot_general(q[rows], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * scale
        if k_scales is not None:
            s_ = s_ * jnp.concatenate([r[:, lanes] for r in k_scales], axis=1)
        s_ = jnp.where(live, s_, -jnp.inf)
        m_prev = m_scr[rows, :][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[rows, :][:, :1] + jnp.sum(p, axis=-1,
                                                       keepdims=True)
        if vals is None:
            vals, v_scales = values()
        if v_scales is not None:
            p = p * jnp.concatenate([r[:, lanes] for r in v_scales], axis=1)
        v = jnp.concatenate([wide(v_[h]) for v_ in vals], axis=0)
        a_scr[rows, :] = a_scr[rows, :] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_scr[rows, :] = jnp.broadcast_to(m_new, (group, m_scr.shape[1]))
        l_scr[rows, :] = jnp.broadcast_to(l_new, (group, l_scr.shape[1]))


def load_kernel(checkout, module="paged_decode", fn="paged_flash_decode"):
    """Function ``fn`` of another checkout's ``ops/pallas/<module>.py``."""
    path = os.path.join(checkout, "zoo_tpu", "ops", "pallas", module + ".py")
    spec = importlib.util.spec_from_file_location(
        module + "_" + checkout.strip("/").replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, fn)


def regime(table, name, seed):
    """(cache operands, tables, positions, attended positions)."""
    width, lo, hi, live_slots, (n_layer, n_blocks) = table[name]
    rs = np.random.RandomState(seed)
    # distinct blocks for every live entry where the pool allows
    pos = np.zeros(S, np.int32)
    pos[:live_slots] = rs.randint(lo, hi, live_slots)
    bt = np.zeros((S, width), np.int32)
    pool = rs.permutation(np.arange(1, n_blocks))
    used = 0
    for s in range(live_slots):
        n = pos[s] // BLOCK + 1
        bt[s, :n] = pool[np.arange(used, used + n) % len(pool)]
        used += n
    key = jax.random.PRNGKey(seed)
    shape = (n_layer, n_blocks, N_KV, BLOCK, D)
    kc = jax.random.randint(key, shape, -127, 128, jnp.int8)
    vc = jax.random.randint(jax.random.fold_in(key, 1), shape, -127, 128,
                            jnp.int8)
    ks = jax.random.uniform(jax.random.fold_in(key, 2),
                            (n_layer, n_blocks, 1, N_KV * BLOCK),
                            jnp.float32, 0.001, 0.02)
    vs = ks * 0.5
    q = jax.random.normal(jax.random.fold_in(key, 3), (S, H, D), jnp.float32)
    attended = int((pos[:live_slots] + 1).sum())
    return (q, kc, vc, ks, vs), jnp.asarray(bt), jnp.asarray(pos), attended


def decode_call(kernel, layers, splits):
    @jax.jit
    def call(q, kc, vc, ks, vs, bt, pos):
        def layer(acc, i):
            out = kernel(q, kc, vc, bt, pos, layer=i % kc.shape[0],
                         k_scale=ks, v_scale=vs, num_splits=splits)
            return acc + out, None
        return jax.lax.scan(layer, jnp.zeros_like(q), jnp.arange(layers))[0]
    return call


def measure(call, args, calls, pattern="zoo_paged_decode"):
    """(device seconds a call of the operations ``pattern`` names, wall
    seconds a call, result); both nan off the TPU, the first nan where
    no operation matched."""
    jax.block_until_ready(call(*args))
    result = np.asarray(call(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = call(*args)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / calls
        jax.profiler.stop_trace()
        trace = tr.read_xplane(tr.find_xplane(d))
    busy, n_ops = tr.matched_time(trace.ops, pattern)
    if jax.default_backend() != "tpu":
        busy = wall = float("nan")
    elif not n_ops:
        busy = float("nan")
    return busy / calls, wall, result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--regimes", default="closed,open,long")
    ap.add_argument("--entries", default="0")
    ap.add_argument("--splits", default="0")
    ap.add_argument("--forms", default="a")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/bench_paged_decode.jsonl")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes under the interpreter; times are nan")
    a = ap.parse_args()
    table = TINY if a.rehearse_cpu else REGIMES
    if jax.default_backend() != "tpu" and not a.rehearse_cpu:
        sys.exit("bench_paged_decode: no TPU; a CPU time is no device number")
    ints = lambda s: [int(x) for x in s.split(",")]
    kernels = [("parent", None, load_kernel(a.parent))] if a.parent else []
    kernels += [(form, n, pd.paged_flash_decode)
                for form in a.forms.split(",") for n in ints(a.entries)]
    own = pd._attend, pd.MAX_ENTRIES
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    row_bytes = 2 * N_KV * (D + 4)      # int8 K and V rows, f32 scales
    kind = jax.devices()[0].device_kind
    # a device that is not in the table is an error (the rehearsal
    # prints no time, so any row of it will do there)
    peaks = peaks_table()
    peak = peaks[next(iter(peaks)) if a.rehearse_cpu else kind][
        "hbm_bytes_per_s"]
    with open(a.out, "a") as sink:
        for name in a.regimes.split(","):
            ops, bt, pos, attended = regime(table, name, a.seed)
            live = table[name][3]
            floor = attended * row_bytes * a.layers / peak
            first = None        # the regime's first result: the others'
            for form, n, kernel in kernels:
                pd._attend = attend_per_head if form == "b" else own[0]
                pd.MAX_ENTRIES = n or own[1]
                for splits in ints(a.splits) if form != "parent" else [0]:
                    call = decode_call(kernel, a.layers, splits or None)
                    try:
                        busy, wall, out = measure(call, (*ops, bt, pos),
                                                  a.calls)
                        first = out if first is None else first
                        # live slots only: an empty slot's row is garbage
                        gap = float(np.abs(out - first)[:live].max())
                    except Exception as e:   # one form failing is a row
                        busy = wall = gap = float("nan")
                        print(f"# {name} {form} N={n} splits={splits}: "
                              f"{type(e).__name__}: {str(e)[:300]}",
                              flush=True)
                    row = {"regime": name, "form": form, "entries": n,
                           "splits": splits, "kernel_ms": busy * 1e3,
                           "wall_ms": wall * 1e3, "floor_ms": floor * 1e3,
                           "floor_share_pct": 100 * floor / busy,
                           "gap_to_first": gap,
                           "device": kind}
                    print(json.dumps(row), flush=True)
                    sink.write(json.dumps(row) + "\n")
    pd._attend, pd.MAX_ENTRIES = own


if __name__ == "__main__":
    main()
