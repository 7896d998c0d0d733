#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that zoo_tpu still starts on the chip.

One process drives the system's two main paths through the entry points
a user would call, at the full width of models the repo supports, and
checks what comes out by the repo's own means:

* ``train``   — BERT-base (12 blocks, hidden 768, 12 heads, intermediate
  3072, vocab 30522, seq 128, batch 64, ``mixed_bfloat16``,
  ``remat="dots"``) through ``init_orca_context`` →
  ``Estimator.from_keras`` → ``fit`` from host numpy (the superbatch +
  staging-pool path) and from device-resident arrays (the whole-epoch
  executable), then ``predict`` and a checkpoint save + restore.
* ``serve``   — a 12-block hidden-768 GQA decoder built by
  ``build_llm_engine``, mounted on an in-process ``ServingServer`` and
  driven over TCP by ``HAServingClient.generate``: bucket prefill,
  chunked prefill, speculative verify, a prefix-cache hit, f32 and int8
  KV. The engines must have landed on the Pallas kernels by themselves.
* ``kernels`` — every Pallas kernel of ``zoo_tpu.ops.pallas``, compiled
  by Mosaic (``interpret=False``) at one real shape and compared with
  its ``jax.numpy`` reference under a tolerance stated per dtype.
* ``multichip`` — when four devices are visible: the same BERT-base
  ``fit`` under ``data=2 x fsdp=2`` and the serving spec with ``tp=2``.

It fails (non-zero exit, no result line) unless jax's platform is
``tpu``; any leg that raises, any short or failed stream, any kernel
outside its tolerance fails the run. Weights are random, from a seed;
no rate, MFU or peak is printed — this is a bring-up check, not a
benchmark. The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-cpu`` is the sandbox rehearsal: the same legs at toy
widths with the kernels interpreted. It says so in its output and in
its result line, and it proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Sequence

LEGS = ("train", "serve", "kernels", "multichip")
# the driver gives the run 1200 s; die with every thread's stack a
# little before that instead of being killed without a trace
DEADLINE_S = 1150


# --------------------------------------------------------------------- sizes

@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every shape the smoke runs at. ``FULL`` is the chip run: published
    widths everywhere (depth is already the models' own). ``TOY`` is the
    CPU rehearsal."""
    # train: BERT through the Keras facade
    bert_blocks: int
    bert_hidden: int
    bert_heads: int
    bert_vocab: int
    bert_seq: int
    bert_batch: int
    bert_steps: int            # steps per epoch
    # serve: the llama: spec (architecture, then engine geometry)
    llm_arch: str
    llm_engine: str
    llm_spec_k: int
    llm_buckets: str
    llm_chunk: int
    short_prompt: int
    bucket_prompt: int         # lands in the largest bucket
    long_prompt: int           # only the chunk executable can take it
    max_new: int
    # kernels
    flash_seqs: Sequence[int]
    mm_rows: int
    mm_shapes: Sequence[Sequence[int]]      # (K, N)
    conv_batch: int
    conv_stages: Sequence[Sequence[int]]    # (hw, c_in of the 1x1, c_mid)
    optim_shape: Sequence[int]
    bottleneck: Sequence[int]               # (batch, hw, c_in, c_mid)


FULL = Sizes(
    bert_blocks=12, bert_hidden=768, bert_heads=12, bert_vocab=30522,
    bert_seq=128, bert_batch=64, bert_steps=16,
    llm_arch=("vocab=32000,hidden=768,n_block=12,n_head=12,n_kv_head=4,"
              "intermediate=2048"),
    llm_engine="slots=8,block=16,blocks=512,tables=64,prefix_cache=1",
    llm_spec_k=4,
    llm_buckets="32/128/512", llm_chunk=64,
    short_prompt=20, bucket_prompt=400, long_prompt=700, max_new=24,
    flash_seqs=(512, 4096),
    mm_rows=512, mm_shapes=((768, 3072), (3072, 768), (8192, 768)),
    conv_batch=8,
    conv_stages=((56, 256, 64), (28, 512, 128), (14, 1024, 256),
                 (7, 2048, 512)),
    optim_shape=(768, 3072),
    bottleneck=(8, 56, 256, 64),
)

TOY = Sizes(
    bert_blocks=1, bert_hidden=32, bert_heads=2, bert_vocab=200,
    bert_seq=16, bert_batch=8, bert_steps=4,
    llm_arch=("vocab=256,hidden=64,n_block=1,n_head=4,n_kv_head=2,"
              "intermediate=128"),
    llm_engine="slots=4,block=8,blocks=64,tables=12,prefix_cache=1",
    llm_spec_k=3,
    llm_buckets="8/32", llm_chunk=8,
    short_prompt=5, bucket_prompt=26, long_prompt=44, max_new=6,
    flash_seqs=(32,),
    mm_rows=16, mm_shapes=((128, 128),),
    conv_batch=1, conv_stages=((8, 16, 8),),
    optim_shape=(8, 128),
    bottleneck=(2, 8, 16, 8),
)


def say(msg: str):
    print(msg, flush=True)


def _ints(spec: str) -> dict:
    """``"a=1,b=2"`` -> ``{"a": 1, "b": 2}`` (the llama: spec halves)."""
    return {k: int(v) for k, v in
            (kv.split("=") for kv in spec.split(","))}


# --------------------------------------------------------------------- train

def _bert_model(sz: Sizes):
    """BERT-base as the repo trains it (the benchmark's
    ``bert-base.fit-resident`` cell builds the same stack)."""
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import BERT, Dense, Lambda
    from zoo_tpu.pipeline.api.keras.optimizers import AdamWeightDecay

    hidden = sz.bert_hidden
    m = Sequential()
    m.add(BERT(vocab=sz.bert_vocab, hidden_size=hidden,
               n_block=sz.bert_blocks, n_head=sz.bert_heads,
               seq_len=sz.bert_seq, intermediate_size=4 * hidden,
               hidden_p_drop=0.0, attn_p_drop=0.0, remat="dots",
               max_position_len=max(sz.bert_seq, 512),
               input_shape=(sz.bert_seq,)))
    m.add(Lambda(lambda h: h[:, 0], output_shape=(hidden,)))
    m.add(Dense(2))
    m.compile(optimizer=AdamWeightDecay(lr=1e-4),
              loss="sparse_categorical_crossentropy_from_logits",
              dtype_policy="mixed_bfloat16")
    return m


def _bert_data(sz: Sizes, n: int, seed: int = 0):
    """Random token ids whose FIRST token tells the label, so a few
    dozen steps are enough for the loss to fall."""
    import numpy as np
    rs = np.random.RandomState(seed)
    ids = rs.randint(3, sz.bert_vocab, (n, sz.bert_seq)).astype(np.int32)
    y = rs.randint(0, 2, n).astype(np.int32)
    ids[:, 0] = 1 + y
    return ids, y


def _leaves(tree):
    import jax
    return [a for a in jax.tree_util.tree_leaves(tree)
            if hasattr(a, "devices")]


def _guard_interventions() -> dict:
    """Steps the training guard skipped and rollbacks it made, from the
    metrics registry. A skipped step adds 0 to the loss ``fit`` reports,
    so "every loss finite" only means something when these are 0."""
    from zoo_tpu.obs.metrics import get_registry
    names = ("zoo_guard_nonfinite_steps_total",
             "zoo_guard_rollbacks_total")
    counters = get_registry().snapshot()["counters"]
    return {n: sum(c["value"] for c in counters if c["name"] == n)
            for n in names}


def leg_train(sz: Sizes, platform: str) -> str:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from zoo_tpu.orca import init_orca_context, stop_orca_context
    from zoo_tpu.orca.learn.keras import Estimator
    from zoo_tpu.orca.learn.trigger import MaxEpoch

    n = sz.bert_batch * sz.bert_steps
    ids, y = _bert_data(sz, n)
    model_dir = tempfile.mkdtemp(prefix="zoo-chip-smoke-")
    # one chip is the unit the published batch is sized for
    init_orca_context("local", devices=jax.devices()[:1])
    try:
        est = Estimator.from_keras(_bert_model(sz), model_dir=model_dir)
        t0 = time.perf_counter()
        # host numpy: the superbatch + staging-buffer path
        host = est.fit({"x": ids, "y": y}, epochs=2,
                       batch_size=sz.bert_batch, shuffle=True,
                       checkpoint_trigger=MaxEpoch(3),
                       max_failure_retries=0)["loss"]
        t_host = time.perf_counter() - t0
        # device-resident: the whole-epoch executable; the third epoch
        # is the one the trigger checkpoints
        t0 = time.perf_counter()
        dev = est.fit({"x": jnp.asarray(ids), "y": jnp.asarray(y)},
                      epochs=1, batch_size=sz.bert_batch, shuffle=True,
                      checkpoint_trigger=MaxEpoch(3),
                      max_failure_retries=0)["loss"]
        t_dev = time.perf_counter() - t0
        losses = host + dev
        assert len(losses) == 3 and all(np.isfinite(losses)), losses
        assert not any(_guard_interventions().values()), \
            _guard_interventions()
        # the host-fed epochs staged through preallocated buffers, not
        # the plain-slicing fallback the pool's probe can fall to
        from zoo_tpu.orca.data.ingest import StagingBufferPool
        assert StagingBufferPool.maybe_create([ids, y], rows=n) \
            is not None, "the staging-buffer probe refused this backend"
        assert host[1] < host[0], \
            f"second-epoch loss {host[1]} not below first {host[0]}"
        model = est.get_model()
        on = {d.platform for a in _leaves(model.params)
              for d in a.devices()}
        assert on == {platform}, f"params live on {on}, not {platform}"

        preds = est.predict(ids[:2 * sz.bert_batch],
                            batch_size=sz.bert_batch)
        assert preds.shape == (2 * sz.bert_batch, 2), preds.shape
        assert np.isfinite(preds).all()

        # the newest checkpoint is the one the trigger wrote after the
        # third epoch; the only older one holds the initial weights
        saved = jax.device_get(model.params)
        model.params = None
        est.load_orca_checkpoint()
        restored = jax.device_get(est.get_model().params)
        la = jax.tree_util.tree_leaves(saved)
        lb = jax.tree_util.tree_leaves(restored)
        assert len(la) == len(lb) and all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(la, lb)), "restored params differ from saved"
        again = est.predict(ids[:2 * sz.bert_batch],
                            batch_size=sz.bert_batch)
        assert np.array_equal(preds, again), \
            "predictions changed across checkpoint restore"
        n_params = sum(int(np.prod(a.shape)) for a in la)
    finally:
        stop_orca_context()
        shutil.rmtree(model_dir, ignore_errors=True)
    return (f"params={n_params} losses={[f'{v:.3g}' for v in losses]} "
            f"guard_skipped_steps=0 "
            f"host_2_epochs_s={t_host:.1f} (compile included) "
            f"device_epoch_s={t_dev:.1f} (compile included) "
            f"predict={preds.shape} ckpt=save+restore-equal")


# --------------------------------------------------------------------- serve

def _llm_spec(sz: Sizes, *extra: str) -> str:
    return f"llama:{sz.llm_arch}:" + ",".join((sz.llm_engine,) + extra)


def _prompts(sz: Sizes):
    """name -> token ids. ``motif`` is a tiled n-gram, the shape the
    prompt-lookup drafter hits; ``long`` is sent twice (the second time
    is the prefix-cache hit)."""
    import numpy as np
    vocab = _ints(sz.llm_arch)["vocab"]
    rs = np.random.RandomState(7)
    motif = rs.randint(1, vocab, (6,))
    return {
        "short": rs.randint(1, vocab, (sz.short_prompt,)),
        "bucket": rs.randint(1, vocab, (sz.bucket_prompt,)),
        "long": rs.randint(1, vocab, (sz.long_prompt,)),
        "motif": np.tile(motif, sz.bucket_prompt // 6 + 1)
        [:sz.bucket_prompt],
    }


def _serve(spec: str, plan: Sequence[str], prompts, max_new: int,
           expect_impl: str, platform: str, n_devices: int = 1,
           **overrides):
    """Build the engine from ``spec`` the way a replica does, mount it
    on a ServingServer, drive ``plan`` (prompt names, in order) over
    TCP, and check the engine's own accounting. An engine with a draft
    budget sends every tick through the verify executable and one
    without through the decode executable, so which of the two must
    have compiled exactly once follows from the spec. Returns
    (streams, stats)."""
    from zoo_tpu.serving.ha_client import HAServingClient
    from zoo_tpu.serving.llm.spec import build_llm_engine
    from zoo_tpu.serving.server import ServingServer
    from zoo_tpu.serving.tcp_client import _Connection

    engine = build_llm_engine(spec, **overrides)
    server = ServingServer(None, host="127.0.0.1", port=0,
                           llm_engine=engine).start()
    client = HAServingClient([(server.host, server.port)], hedge=False,
                             deadline_ms=600_000)
    try:
        streams = []
        for name in plan:
            toks = list(client.generate(prompts[name], max_new))
            assert len(toks) == max_new, \
                f"{spec}: stream {name!r} delivered {len(toks)} of " \
                f"{max_new} tokens"
            streams.append(toks)
        conn = _Connection(server.host, server.port)
        try:
            stats = conn.rpc({"op": "llm_stats"})["stats"]
        finally:
            conn.close()
    finally:
        client.close()
        server.stop()
    assert stats["decode_attention_impl"] == expect_impl, stats
    assert stats["prefill_attention_impl"] == expect_impl, stats
    dev = stats["device"]
    assert dev["platform"] == platform and len(dev["ids"]) == n_devices, \
        f"{spec}: engine lives on {dev}"
    compiles = stats["compiles"]
    # -1 means jit's private cache counter moved: that fails here too
    assert min(compiles.values()) >= 0, compiles
    ticked, idle = ("verify", "decode") if stats["spec_k"] \
        else ("decode", "verify")
    assert compiles[ticked] == 1 and compiles[idle] == 0, compiles
    assert stats["blocks_used"] == 0, \
        f"{stats['blocks_used']} KV blocks leaked after drain"
    assert stats["generated_tokens"] >= max_new * len(plan), stats
    return streams, stats


def leg_serve(sz: Sizes, platform: str, rehearsal: bool) -> str:
    prompts = _prompts(sz)
    # on the chip the engines must pick the kernels themselves; the CPU
    # rehearsal asks for them (interpreted) to walk the same code
    pick = dict(decode_impl="flash", prefill_impl="flash") \
        if rehearsal else {}
    bucketed = _llm_spec(sz, f"buckets={sz.llm_buckets}", "chunk=0",
                         f"spec_k={sz.llm_spec_k}")
    chunked = _llm_spec(sz, f"buckets={sz.llm_buckets}",
                        f"chunk={sz.llm_chunk}", "spec_k=0", "kv=int8")

    # f32 KV, bucketed prefill, speculation on: the bucket executables
    # (the largest one runs the training flash kernel on a TPU), the
    # verify executable, and the suffix chunk executable on the prefix
    # hit
    plan_a = ("short", "bucket", "motif", "bucket")
    streams, st = _serve(bucketed, plan_a, prompts, sz.max_new, "flash",
                         platform, **pick)
    assert st["kv_cache_dtype"] == "f32" and st["prefill_chunk"] == 0, st
    # the dot weights are held as the device's dot reads them: bf16 on
    # the chip, as built on the CPU
    assert st["weight_dtype"] == ("float32" if rehearsal
                                  else "bfloat16"), st["weight_dtype"]
    assert st["spec_proposed_tokens"] > 0, \
        "the drafter proposed nothing on a tiled motif"
    assert st["prefix_hit_tokens"] > 0, "repeated prompt missed the cache"
    assert st["compiles"]["prefill"] >= 2, st["compiles"]
    # printed, not asserted: the hit feeds its suffix through another
    # executable than the cold prompt did, and with random weights the
    # largest logit can flip on rounding
    hit_same = streams[1] == streams[3]

    # int8 KV, chunked prefill, no speculation: the ONE chunk
    # executable takes every prompt, including one no bucket could,
    # and every tick is the plain decode executable
    plan_b = ("short", "long", "motif", "long")
    streams_q, stq = _serve(chunked, plan_b, prompts, sz.max_new, "flash",
                            platform, **pick)
    assert stq["kv_cache_dtype"] == "int8", stq
    assert stq["prefill_chunk"] == sz.llm_chunk, stq
    assert stq["compiles"]["prefill_chunk"] == 1, stq["compiles"]
    assert stq["prefix_hit_tokens"] > 0, "repeated prompt missed the cache"

    # the same weights through the explicit dense-gather path: printed,
    # not asserted — with random weights the largest logit flips on
    # rounding, and the kernels' numerics are the kernels leg's to check
    dense, _ = _serve(bucketed, plan_a, prompts, sz.max_new, "dense",
                      platform, decode_impl="dense", prefill_impl="dense")
    same = sum(a == b for s, d in zip(streams, dense)
               for a, b in zip(s, d))
    total = sz.max_new * len(plan_a)
    return (f"decode_impl=flash prefill_impl=flash decode_compiles=1 "
            f"(int8 engine) verify_compiles=1 (f32 engine) "
            f"failed_streams=0 leaked_blocks=0 "
            f"kv=f32+int8 weights={st['weight_dtype']} "
            f"streams={len(plan_a) + len(plan_b)} "
            f"prefill_compiles={st['compiles']['prefill']} "
            f"spec_accepted={st['spec_accepted_tokens']}"
            f"/{st['spec_proposed_tokens']} "
            f"prefix_hit_tokens={st['prefix_hit_tokens']}"
            f"+{stq['prefix_hit_tokens']} "
            f"prefix_hit_stream_equal_to_cold={hit_same} "
            f"tokens_agreeing_with_dense={same}/{total}")


# ------------------------------------------------------------------- kernels

@dataclasses.dataclass
class KernelCase:
    """One kernel at one shape: ``fn`` is the Pallas path, ``ref`` the
    plain jax.numpy one, both over the arrays ``make()`` returns.
    ``tol`` bounds max|fn - ref| / max(1, max|ref|) per output."""
    name: str
    make: Callable[[], list]
    fn: Callable
    ref: Callable
    tol: float


# Tolerances, on max|fn - ref| / max(1, max|ref|), by what the kernel
# rounds. Each is about three times the largest error its class showed
# on the v5e (PR 21's chip run, in parentheses), and far under what a
# dropped term, a wrong mask or a missing scale produces (O(0.1-1)).
#
# f32 in and out (3.1e-3). The MXU multiplies f32 operands in bf16
# passes at default precision — inside a Mosaic kernel as in every XLA
# f32 dot of the model around it — so f32 operands buy range, not
# products more exact than bf16's; the reference runs at "highest".
TOL_F32 = 1e-2
TOL_INT8_KV = 1e-2       # int8 rows widened to f32, then as TOL_F32 (2.8e-3)
TOL_BF16 = 1e-2          # bf16 operands or outputs: 2^-8 per rounding (3.2e-3)
TOL_BF16_GRAD = 2e-2     # chained bf16 matmuls, fwd + bwd (4.8e-3)
TOL_INT8_REQUANT = 5e-3  # a borderline activation may round the other way (0)
TOL_EXACT = 1e-5         # integer accumulation or elementwise f32 only (0)


def _dense_paged_ref(q, kc, vc, bt, pos, ks=None, vs=None):
    """Gather-then-attend reference for both paged kernels. ``q`` is
    (S, C, H, D) with per-row positions ``pos`` (S, C); a block's
    scales are one head-major row, (num_blocks, 1, n_kv * block)."""
    import jax
    import jax.numpy as jnp
    S, C, H, D = q.shape
    _, n_kv, bs, _ = kc.shape
    W = bt.shape[1]

    def gather(cache, scale):
        g = cache[bt].astype(jnp.float32)       # (S, W, n_kv, bs, D)
        if scale is not None:
            g = g * scale[bt].reshape(S, W, n_kv, bs, 1)
        return g.transpose(0, 1, 3, 2, 4).reshape(S, W * bs, n_kv, D)

    keys, vals = gather(kc, ks), gather(vc, vs)
    qg = q.astype(jnp.float32).reshape(S, C, n_kv, H // n_kv, D)
    s = jnp.einsum("sckgd,stkd->sckgt", qg, keys) / jnp.sqrt(float(D))
    live = jnp.arange(W * bs)[None, None, :] <= pos[:, :, None]
    s = jnp.where(live[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sckgt,stkd->sckgd", p, vals).reshape(S, C, H, D)


def _paged_cases(sz: Sizes, interpret) -> List[KernelCase]:
    import numpy as np

    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import paged_flash_decode, paged_flash_prefill
    from zoo_tpu.util.quantize import absmax_scale, narrow_int8

    arch, eng = _ints(sz.llm_arch), _ints(sz.llm_engine)
    H, n_kv = arch["n_head"], arch["n_kv_head"]
    D = arch["hidden"] // H
    S, bs = eng["slots"], eng["block"]
    nb, W = eng["blocks"], eng["tables"]
    T = sz.llm_spec_k + 1
    ctx = W * bs

    def make(n_seq, C, kv, starts, layers=(), heads=(H, n_kv, D)):
        """``layers=(n,)`` stacks n layers' caches (and scale planes)
        and appends the layer to attend as the last argument; ``heads``
        = (query heads, kv heads, head_dim) other than the spec's."""
        def _make():
            H, n_kv, D = heads
            rs = np.random.RandomState(C)
            q = rs.randn(n_seq, C, H, D).astype(np.float32)
            kc = rs.randn(*layers, nb, n_kv, bs, D).astype(np.float32)
            vc = rs.randn(*layers, nb, n_kv, bs, D).astype(np.float32)
            bt = np.stack([rs.permutation(np.arange(1, nb))[:W]
                           for _ in range(n_seq)]).astype(np.int32)
            pos = np.minimum(np.asarray(starts[:n_seq])[:, None]
                             + np.arange(C)[None, :],
                             ctx - 1).astype(np.int32)
            out = [q, kc, vc, bt, pos]
            if kv == "bf16":
                out[1] = jnp.asarray(kc, jnp.bfloat16)
                out[2] = jnp.asarray(vc, jnp.bfloat16)
            elif kv == "int8":
                ks = np.asarray(absmax_scale(kc, axis=-1), np.float32)
                vs = np.asarray(absmax_scale(vc, axis=-1), np.float32)
                out[1] = narrow_int8(kc, ks[..., None])
                out[2] = narrow_int8(vc, vs[..., None])
                # a block's scales: one head-major row
                out += [ks.reshape(*layers, nb, 1, n_kv * bs),
                        vs.reshape(*layers, nb, 1, n_kv * bs)]
            if layers:
                out.append(np.int32(layers[0] - 1))
            return out
        return _make

    def scales(sc):
        return dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}

    def decode(q, kc, vc, bt, pos, *sc, layer=None):
        return paged_flash_decode(q[:, 0], kc, vc, bt, pos[:, 0],
                                  layer=layer, interpret=interpret,
                                  **scales(sc))[:, None]

    def prefill(q, kc, vc, bt, pos, *sc, layer=None):
        return paged_flash_prefill(q, kc, vc, bt, pos, layer=layer,
                                   interpret=interpret, **scales(sc))

    # the serving step's form: the whole stacked cache and a traced layer
    def at_layer(kernel):
        return lambda q, kc, vc, bt, pos, *sc: kernel(
            q, kc, vc, bt, pos, *sc[:-1], layer=sc[-1])

    def stacked_ref(q, kc, vc, bt, pos, *sc):
        return _dense_paged_ref(q, kc[sc[-1]], vc[sc[-1]], bt, pos,
                                *(s[sc[-1]] for s in sc[:-1]))

    # slot positions: the first token, both sides of a block edge, mid
    # table, the last column
    spread = [0, bs - 1, bs, ctx // 3, ctx // 2, ctx - 2 * T, ctx - T - 1,
              ctx - 1] * (S // 8 + 1)
    cases = []
    for kv in ("f32", "bf16", "int8"):
        tol = {"f32": TOL_F32, "bf16": TOL_BF16, "int8": TOL_INT8_KV}[kv]
        cases += [
            KernelCase(f"paged_flash_decode[{kv}]",
                       make(S, 1, kv, spread), decode, _dense_paged_ref,
                       tol),
            # Mistral's heads (32 on 8 of 128): rows and scale rows of
            # whole 128-lane tiles, so the kernel copies several table
            # entries a step out of HBM itself (the spec's 64-wide heads
            # go an entry a step through the BlockSpec pipeline)
            KernelCase(f"paged_flash_decode[32x8x128,{kv}]",
                       make(S, 1, kv, spread, heads=(32, 8, 128)), decode,
                       _dense_paged_ref, tol),
            KernelCase(f"paged_flash_prefill[chunk,{kv}]",
                       make(1, sz.llm_chunk, kv, [ctx // 2]), prefill,
                       _dense_paged_ref, tol),
            KernelCase(f"paged_flash_prefill[verify,{kv}]",
                       make(S, T, kv, spread), prefill, _dense_paged_ref,
                       tol),
        ]
    cases += [
        KernelCase("paged_flash_decode[stacked,int8]",
                   make(S, 1, "int8", spread, layers=(3,)),
                   at_layer(decode), stacked_ref, TOL_INT8_KV),
        KernelCase("paged_flash_prefill[stacked,int8]",
                   make(1, sz.llm_chunk, "int8", [ctx // 2], layers=(3,)),
                   at_layer(prefill), stacked_ref, TOL_INT8_KV),
    ]
    return cases


def _flash_cases(sz: Sizes, interpret) -> List[KernelCase]:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import flash_attention

    arch = _ints(sz.llm_arch)
    H, n_kv = arch["n_head"], arch["n_kv_head"]
    D = arch["hidden"] // H

    def make(T):
        def _make():
            rs = np.random.RandomState(T)
            return [jnp.asarray(rs.randn(*shape) * 0.5, jnp.bfloat16)
                    for shape in ((1, H, T, D), (1, n_kv, T, D),
                                  (1, n_kv, T, D), (1, H, T, D))]
        return _make

    def dense(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k = jnp.repeat(k, H // n_kv, axis=1)
        v = jnp.repeat(v, H // n_kv, axis=1)
        T = q.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(D))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def fwd_bwd(attend):
        def run(q, k, v, g):
            def loss(q, k, v):
                return jnp.sum(attend(q, k, v).astype(jnp.float32)
                               * g.astype(jnp.float32))
            return (attend(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)
        return run

    kernel = fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    return [KernelCase(f"flash_attention[fwd+bwd,S={T}]", make(T), kernel,
                       fwd_bwd(dense), TOL_BF16_GRAD)
            for T in sz.flash_seqs]


def _matmul_cases(sz: Sizes, interpret) -> List[KernelCase]:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import (
        fused_quantized_matmul,
        quantize_int8,
        quantized_matmul,
    )
    M = sz.mm_rows

    def int_dot(xq, wq):
        return jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)

    cases = []
    for K, N in sz.mm_shapes:
        def make_q(K=K, N=N):
            rs = np.random.RandomState(K)
            return [rs.randint(-127, 128, (M, K)).astype(np.int8),
                    rs.randint(-127, 128, (K, N)).astype(np.int8),
                    rs.uniform(0.5, 1.5, (M, 1)).astype(np.float32) / K,
                    rs.uniform(0.5, 1.5, (N,)).astype(np.float32) / 127]

        def make_f(K=K, N=N):
            rs = np.random.RandomState(K + 1)
            return [rs.randn(M, K).astype(np.float32),
                    rs.randint(-127, 128, (K, N)).astype(np.int8),
                    rs.uniform(0.5, 1.5, (N,)).astype(np.float32) / 127]

        def fused_ref(x, wq, ws):
            xq, xs = quantize_int8(x, axis=-1)
            return int_dot(xq, wq) * xs * ws[None, :]

        cases += [
            KernelCase(
                f"quantized_matmul[K={K},N={N}]", make_q,
                lambda xq, wq, xs, ws: quantized_matmul(
                    xq, wq, xs, ws, interpret=interpret),
                lambda xq, wq, xs, ws: int_dot(xq, wq) * xs * ws[None, :],
                TOL_EXACT),
            KernelCase(
                f"fused_quantized_matmul[K={K},N={N}]", make_f,
                lambda x, wq, ws: fused_quantized_matmul(
                    x, wq, ws, interpret=interpret),
                fused_ref, TOL_INT8_REQUANT),
        ]
    return cases


def _conv_cases(sz: Sizes, interpret) -> List[KernelCase]:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import conv2d, conv2d_int8, fused_bottleneck
    from zoo_tpu.ops.pallas.fused_block import _xla_block
    nb = sz.conv_batch

    def conv_ref(x, w):
        return jax.lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    cases = []
    for hw, c_in, c_mid in sz.conv_stages:
        for k, ci, co in ((1, c_in, c_mid), (3, c_mid, c_mid)):
            def make(k=k, ci=ci, co=co, hw=hw):
                rs = np.random.RandomState(hw + k)
                return [jnp.asarray(rs.randn(nb, hw, hw, ci), jnp.bfloat16),
                        jnp.asarray(rs.randn(k, k, ci, co) / (k * ci ** .5),
                                    jnp.bfloat16)]

            def make_q(k=k, ci=ci, co=co, hw=hw):
                rs = np.random.RandomState(hw + k + 1)
                return [rs.randint(-127, 128, (nb, hw, hw, ci))
                        .astype(np.float32),
                        rs.randint(-127, 128, (k, k, ci, co))
                        .astype(np.int8),
                        rs.uniform(0.5, 1.5, (nb, 1, 1, 1))
                        .astype(np.float32) / (127 * k * k * ci),
                        rs.uniform(0.5, 1.5, (co,)).astype(np.float32)]

            tag = f"{k}x{k},{hw}x{hw},{ci}->{co}"
            cases += [
                KernelCase(
                    f"conv2d[{tag}]", make,
                    lambda x, w: conv2d(x, w, impl="pallas",
                                        interpret=interpret),
                    conv_ref, TOL_BF16),
                KernelCase(
                    f"conv2d_int8[{tag}]", make_q,
                    lambda x, w, xs, ws: conv2d_int8(
                        x, w, xs, ws, impl="pallas", interpret=interpret),
                    lambda x, w, xs, ws: conv2d_int8(
                        x, w, xs, ws, impl="reference"),
                    TOL_EXACT),
            ]

    b, hw, c_in, c_mid = sz.bottleneck

    def make_block():
        rs = np.random.RandomState(3)
        return [jnp.asarray(rs.randn(b, hw, hw, c_in), jnp.bfloat16),
                jnp.asarray(rs.randn(c_in, c_mid) / c_in ** .5,
                            jnp.bfloat16),
                jnp.asarray(rs.randn(3, 3, c_mid, c_mid)
                            / (3 * c_mid ** .5), jnp.bfloat16),
                jnp.asarray(rs.randn(c_mid, c_in) / c_mid ** .5,
                            jnp.bfloat16)]

    cases.append(KernelCase(
        f"fused_bottleneck[{hw}x{hw},{c_in}->{c_mid}]", make_block,
        lambda x, w1, w2, w3: fused_bottleneck(x, w1, w2, w3, interpret),
        lambda *a: _xla_block(*(t.astype(jnp.float32) for t in a)),
        TOL_BF16_GRAD))
    return cases


def _optim_cases(sz: Sizes, interpret) -> List[KernelCase]:
    import numpy as np

    from zoo_tpu.ops.pallas import fused_apply_adam, fused_apply_sgd
    from zoo_tpu.ops.pallas.fused_optim import reference_apply_adam

    def make(n):
        def _make():
            rs = np.random.RandomState(n)
            arrs = [rs.randn(*sz.optim_shape).astype(np.float32)
                    for _ in range(n)]
            arrs[-1] = np.abs(arrs[-1])     # Adam's second moment
            return arrs
        return _make

    def sgd_ref(p, g, buf):
        g = g + 0.01 * p
        buf = 0.9 * buf + g
        return p - 0.1 * buf, buf

    return [
        KernelCase("fused_apply_sgd", make(3),
                   lambda p, g, buf: fused_apply_sgd(
                       p, g, buf, 0.1, momentum=0.9, weight_decay=0.01,
                       interpret=interpret),
                   sgd_ref, TOL_EXACT),
        KernelCase("fused_apply_adam", make(4),
                   lambda p, g, m, v: fused_apply_adam(
                       p, g, m, v, 3, 1e-3, weight_decay=0.01,
                       interpret=interpret),
                   lambda p, g, m, v: reference_apply_adam(
                       p, g, m, v, 3, 1e-3, weight_decay=0.01),
                   TOL_EXACT),
    ]


def _sparse_state_cases(sz: Sizes, interpret) -> List[KernelCase]:
    """The block-sparse decode kernel and the Lightning state's step at
    the widths of ``minicpm-sala-d12`` (16 query heads a K/V head of
    128, pages of 64; 32 heads of 128 x 128 float32 state), every slot
    another number of live entries, one none."""
    import numpy as np

    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import lightning_decode, sparse_paged_decode
    from zoo_tpu.ops.pallas.lightning import (
        lightning_decode_reference,
        write_state,
    )
    from zoo_tpu.ops.pallas.sparse_decode import sparse_decode_reference

    S, G, Hg, D, bs, nb, E, L = 8, 2, 16, 128, 64, 48, 20, 2

    def sparse(kv):
        def _make():
            rs = np.random.RandomState(E)
            q = rs.randn(S, G, Hg, D).astype(np.float32)
            kc = rs.randn(L, nb, G, bs, D).astype(np.float32)
            vc = rs.randn(L, nb, G, bs, D).astype(np.float32)
            n_live = rs.randint(0, E + 1, (S, G)).astype(np.int32)
            n_live[0], n_live[1, 0] = E, 0
            lens = np.where(np.arange(E) < n_live[..., None], bs, 0)
            own = rs.randint(0, np.maximum(n_live, 1))
            np.put_along_axis(
                lens, own[..., None],
                np.where(n_live > 0, rs.randint(1, bs + 1, (S, G)),
                         0)[..., None], axis=-1)
            tables = np.where(lens > 0, rs.randint(1, nb, (S, G, E)), 0)
            if kv == "bf16":
                kc, vc = (jnp.asarray(x, jnp.bfloat16) for x in (kc, vc))
            return [q, kc, vc, tables.astype(np.int32),
                    lens.astype(np.int32), n_live, np.int32(1)]
        return _make

    def sparse_fn(q, kc, vc, tables, lens, n_live, layer):
        return sparse_paged_decode(q, kc, vc, tables, lens, n_live,
                                   layer=layer, interpret=interpret)

    def sparse_ref(q, kc, vc, tables, lens, n_live, layer):
        return sparse_decode_reference(
            q, kc.astype(jnp.float32), vc.astype(jnp.float32), tables,
            lens, layer=layer)

    H = 32

    def state():
        rs = np.random.RandomState(H)
        q, k, v = (rs.randn(S, H, D).astype(np.float32) for _ in range(3))
        live = np.arange(S) % 3 != 1
        return [rs.randn(L, S, H, D, D).astype(np.float32), np.int32(1),
                q, k, v, np.exp(-np.linspace(0.004, 0.9, H)).astype(
                    np.float32), live]

    def step_fn(leaf, layer, q, k, v, decay, live):
        return lightning_decode(leaf, layer, q, k, v, decay, live,
                                interpret=interpret)

    def write_fn(leaf, layer, q, k, v, decay, live):
        return write_state(leaf, layer, 5, leaf[0, 2] * 2.0,
                           interpret=interpret)

    return [
        KernelCase("sparse_paged_decode[f32]", sparse("f32"), sparse_fn,
                   sparse_ref, TOL_F32),
        KernelCase("sparse_paged_decode[bf16]", sparse("bf16"), sparse_fn,
                   sparse_ref, TOL_BF16),
        # elementwise float32: the state is exact, the output row is a
        # sum of 128 float32 products
        KernelCase("lightning_decode[32x128]", state, step_fn,
                   lightning_decode_reference, TOL_EXACT),
        KernelCase("lightning_decode[write_state]", state, write_fn,
                   lambda leaf, layer, *_: leaf.at[layer, 5].set(
                       leaf[0, 2] * 2.0), TOL_EXACT),
    ]


def kernel_cases(sz: Sizes, interpret) -> List[KernelCase]:
    """Every Pallas kernel ``zoo_tpu.ops.pallas`` exports, at the
    smoke's shapes. ``tests/test_tpu_lowering.py`` compiles the same
    list for a v5e without a chip."""
    return (_flash_cases(sz, interpret) + _paged_cases(sz, interpret)
            + _sparse_state_cases(sz, interpret)
            + _matmul_cases(sz, interpret) + _conv_cases(sz, interpret)
            + _optim_cases(sz, interpret))


def leg_kernels(sz: Sizes, rehearsal: bool) -> str:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from zoo_tpu.ops.pallas import on_tpu

    if not rehearsal:
        assert on_tpu(), "on_tpu() is false on the chip"
    interpret = rehearsal       # explicit either way: never ``None``
    compile_total, worst, over = 0.0, ("", 0.0), []
    cases = kernel_cases(sz, interpret)
    for case in cases:
        args = [jnp.asarray(a) for a in case.make()]
        t0 = time.perf_counter()
        compiled = jax.jit(case.fn).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        compile_total += t_compile
        outs = jax.tree_util.tree_leaves(compiled(*args))
        # the reference is the accurate side: full f32 matmuls
        with jax.default_matmul_precision("highest"):
            refs = jax.tree_util.tree_leaves(jax.jit(case.ref)(*args))
        assert len(outs) == len(refs), (case.name, len(outs), len(refs))
        err = 0.0
        for o, r in zip(outs, refs):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            assert o.shape == r.shape, (case.name, o.shape, r.shape)
            assert np.isfinite(o).all(), f"{case.name}: non-finite output"
            err = max(err, float(np.max(np.abs(o - r))
                                 / max(1.0, np.max(np.abs(r)))))
        say(f"  kernel {case.name}: compile_s={t_compile:.2f} "
            f"err={err:.2e} tol={case.tol:.0e}"
            + ("" if err <= case.tol else "  OVER TOLERANCE"))
        if err > case.tol:
            over.append(case.name)
        if err / case.tol > worst[1]:
            worst = (case.name, err / case.tol)
        del args, outs, refs, compiled
    assert not over, f"kernels over their tolerance: {over}"
    return (f"kernels={len(cases)} interpret={interpret} "
            f"compile_total_s={compile_total:.1f} (set-up time) "
            f"closest_to_tolerance={worst[0]} at {worst[1]:.2f} of it")


# ----------------------------------------------------------------- multichip

def _tree_bytes_frac(placed) -> float:
    """Per-device bytes over replicated bytes of a placed pytree."""
    import numpy as np
    local = total = 0
    for leaf in _leaves(placed):
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        local += leaf.addressable_shards[0].data.nbytes
    return local / max(total, 1)


def leg_multichip(sz: Sizes, platform: str, rehearsal: bool) -> str:
    import numpy as np

    import jax

    from zoo_tpu.analysis.hlo import assert_collectives
    from zoo_tpu.orca import init_orca_context, stop_orca_context
    from zoo_tpu.orca.learn.keras import Estimator

    devices = jax.devices()[:4]
    bs = sz.bert_batch
    ids, y = _bert_data(sz, bs * 4, seed=1)

    def first_step_loss(devs, mesh_axes):
        """A fresh model (same seed), one step on the same batch."""
        init_orca_context("local", devices=devs, mesh_axes=mesh_axes)
        est = Estimator.from_keras(_bert_model(sz))
        loss = est.fit({"x": ids[:bs], "y": y[:bs]}, epochs=1,
                       batch_size=bs, shuffle=False)["loss"][0]
        return est, float(loss)

    try:
        _, one = first_step_loss(devices[:1], None)
    finally:
        stop_orca_context()
    gc.collect()
    try:
        est, four = first_step_loss(devices, {"data": 2, "fsdp": 2})
        # bf16 matmuls reduced in another order over four shards
        tol = 2e-2
        assert abs(four - one) <= tol * max(1.0, abs(one)), \
            f"first-step loss {four} on the mesh vs {one} on one chip"
        more = est.fit({"x": ids, "y": y}, epochs=2, batch_size=bs,
                       shuffle=True)["loss"]
        assert all(np.isfinite(more)), more
        assert not any(_guard_interventions().values()), \
            _guard_interventions()
        model = est.get_model()
        leaves = _leaves(model.params)
        spans = {len(a.sharding.device_set) for a in leaves}
        assert spans == {4}, \
            f"param leaves span {spans} devices, want 4 each"
        assert {d for a in leaves for d in a.devices()} == set(devices)
        frac = _tree_bytes_frac(model.params)
        assert frac <= 0.5 + 0.05, \
            f"per-device param bytes {frac:.3f} of replicated (fsdp=2)"
        counts = assert_collectives(
            model.lower_train_hlo(ids, y, batch_size=bs),
            require=["all-gather"],
            require_any=["reduce-scatter", "all-to-all", "all-reduce"],
            label="data=2 x fsdp=2 BERT train step")
        del est, model, leaves
    finally:
        stop_orca_context()
    gc.collect()

    pick = dict(decode_impl="flash", prefill_impl="flash") \
        if rehearsal else {}
    spec = _llm_spec(sz, f"buckets={sz.llm_buckets}", "chunk=0",
                     "spec_k=0", "tp=2")
    _, st = _serve(spec, ("short", "motif", "bucket"),
                   _prompts(sz), sz.max_new, "flash", platform,
                   n_devices=2, **pick)
    assert st["tp"] == 2, st
    return (f"mesh=data2xfsdp2 first_step_loss one_chip={one:.4f} "
            f"four_chips={four:.4f} (tol {tol}) param_devices=4 "
            f"param_bytes_frac={frac:.3f} collectives={counts} "
            f"tp2_serving: tokens delivered, decode_compiles=1, "
            f"cache on devices {st['device']['ids']}")


# ---------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox rehearsal: toy widths, kernels "
                         "interpreted, any platform; proves nothing "
                         "about the chip")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of "
                         f"{','.join(LEGS)} (default: all; multichip "
                         "runs only when four devices are visible)")
    ns = ap.parse_args(argv)
    legs = [name for name in ns.legs.split(",") if name]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error(f"unknown leg(s) {unknown}")
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    # the platform, before anything else
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not ns.rehearse_cpu:
        print(f"chip_smoke: jax found no TPU (platform="
              f"{device['platform']}, device_kind={device['kind']}); "
              "this check only means something on the chip. "
              "--rehearse-cpu runs the toy rehearsal.", file=sys.stderr)
        return 2

    from zoo_tpu.common.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}")
    say(f"jax={jax.__version__} libtpu={libtpu_version} "
        f"compile_cache={cache_dir}")
    if ns.rehearse_cpu:
        say("REHEARSAL: toy widths, Pallas kernels interpreted — this "
            "run says nothing about the chip")
    sz = TOY if ns.rehearse_cpu else FULL
    platform = device["platform"]

    for name in legs:
        if name == "multichip" and device["count"] < 4:
            say(f"leg multichip: not run ({device['count']} device(s) "
                "visible, needs 4)")
            continue
        t0 = time.perf_counter()
        if name == "train":
            line = leg_train(sz, platform)
        elif name == "serve":
            line = leg_serve(sz, platform, ns.rehearse_cpu)
        elif name == "kernels":
            line = leg_kernels(sz, ns.rehearse_cpu)
        else:
            line = leg_multichip(sz, platform, ns.rehearse_cpu)
        say(f"leg {name}: ok in {time.perf_counter() - t0:.1f}s — {line}")
        gc.collect()

    result = {"ok": True, "device": device}
    if ns.rehearse_cpu:
        result["rehearsal"] = True
    if legs != list(LEGS):
        result["legs"] = legs
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
