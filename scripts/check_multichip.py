#!/usr/bin/env python
"""Multichip smoke on the CPU rig: an 8-device VIRTUAL CPU mesh.

It checks layouts, parity and collectives; it holds no chip and times
nothing (the same layouts on real chips are ``chip_smoke.py``'s
``multichip`` leg). The three acceptance properties of GSPMD sharded
training & serving —

* **sharded fit == single-device fit**: the same model/seed/data trained
  on a ``data x fsdp`` mesh produces the same loss curve as one device
  (tolerance 1e-5; on XLA CPU it is bit-exact), with params/opt-state
  ACTUALLY sharded — per-device param bytes ~ 1/n_devices — and the
  compiled step passing the HLO lint (weight all-gather + grad
  reduction present, no full-parameter all-gather into a replicated
  output, ``zoo_tpu.parallel.hlo_check``);
* **resharding-on-restore**: a checkpoint saved from the 8-device mesh
  restores onto a 4-device mesh and a single device bit-exactly
  (``CheckpointManager.restore(sharding=mesh)`` — the ``run_elastic``
  re-mesh path);
* **sharded paged decode == unsharded decode**: ``llama:...:tp=2``
  spans one set of weights + one paged KV cache over 2 devices and
  streams token-identical output to the single-device engine, with
  ``decode compiles == 1`` and zero leaked KV blocks.

Run directly (``python scripts/check_multichip.py`` — self-provisions
the 8-device virtual CPU platform in a child process) or from the test
suite (``tests/test_multichip.py`` runs it under the ``multichip``
marker). ``__graft_entry__.dryrun_multichip`` prints the same metrics
line.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_DEVICES = 8
LOSS_TOL = 1e-5


def _fit_losses(mesh_axes, devices, batch_size=32, seed=0, plan=None,
                body_layers=0):
    """Train the probe model under a fresh orca context; returns
    (losses, model, placed-params, step-HLO). ``body_layers`` inserts a
    homogeneous Dense run (the pipeline plan's stackable body);
    ``plan`` is forwarded to ``compile``."""
    import numpy as np

    from zoo_tpu.orca import init_orca_context, stop_orca_context
    from zoo_tpu.pipeline.api.keras import Sequential
    from zoo_tpu.pipeline.api.keras.layers import Dense
    from zoo_tpu.pipeline.api.keras.optimizers import Adam

    rs = np.random.RandomState(seed)
    x = rs.randn(4 * batch_size, 8).astype(np.float32)
    y = (x @ rs.randn(8, 1).astype(np.float32))
    init_orca_context(cluster_mode="local", devices=devices,
                      mesh_axes=mesh_axes)
    try:
        m = Sequential()
        m.add(Dense(16, activation="relu", input_shape=(8,)))
        for _ in range(body_layers):
            m.add(Dense(16, activation="relu"))
        m.add(Dense(1))
        m.compile(optimizer=Adam(lr=0.01), loss="mse", plan=plan)
        losses = m.fit(x, y, batch_size=batch_size, nb_epoch=3,
                       verbose=0)["loss"]
        hlo = m.lower_train_hlo(x, y, batch_size=batch_size)
        placed = m._place(m.params)
        return losses, m, placed, hlo
    finally:
        stop_orca_context()


def _tree_bytes_frac(placed):
    import jax
    import numpy as np
    local = total = 0
    for leaf in jax.tree_util.tree_leaves(placed):
        total += np.asarray(leaf).nbytes
        local += leaf.addressable_shards[0].data.nbytes \
            if hasattr(leaf, "addressable_shards") else np.asarray(
                leaf).nbytes
    return local / max(total, 1)


def _bit_exact(a, b) -> bool:
    import jax
    import numpy as np
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y),  # NaN-safe
                       equal_nan=True)
        for x, y in zip(la, lb)
        if hasattr(x, "ndim") or hasattr(y, "ndim"))


def collect_metrics(n_devices: int = N_DEVICES, verbose: bool = True
                    ) -> dict:
    """The measured multichip properties; raises on any violation."""
    import numpy as np

    import jax

    from zoo_tpu.parallel import build_mesh
    from zoo_tpu.analysis.hlo import (
        assert_collectives,
        assert_fsdp_sharded,
        assert_llm_executable,
        assert_pipeline_sharded,
        assert_plan_sharded,
    )
    from zoo_tpu.parallel.plans import plan_lint_shapes

    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())}")
    m = {"n_devices": n_devices}

    # 1. sharded fit matches the single-device loss curve ----------------
    # full-width ZeRO: params sharded n_devices ways (the batch rides
    # the fsdp axis too — data_axes() treats them as one data group).
    # batch_size must divide by the data shards; 32 covers the 8-device
    # harness, other world sizes scale it
    bs = 32 if 32 % n_devices == 0 else 4 * n_devices
    ref, _, _, _ = _fit_losses(None, devices[:1], batch_size=bs)
    shd, model, placed, hlo = _fit_losses(
        {"fsdp": n_devices}, devices, batch_size=bs)
    diff = max(abs(a - b) for a, b in zip(ref, shd))
    m["fsdp_loss_max_abs_diff"] = diff
    assert diff <= LOSS_TOL, (
        f"sharded loss curve diverged from single-device by {diff} "
        f"(> {LOSS_TOL}): {shd} vs {ref}")
    frac = _tree_bytes_frac(placed)
    m["fsdp_param_bytes_frac"] = round(frac, 4)
    # ~1/n of the replicated bytes per device (small biases stay
    # replicated, hence the slack)
    assert frac <= 1.0 / n_devices + 0.05, (
        f"per-device param bytes {frac:.3f} of replicated — params are "
        "not actually ZeRO-sharded")

    # 2. the compiled step really is FSDP (HLO lint) ---------------------
    mesh = build_mesh(devices, axis_sizes={"fsdp": n_devices})
    sharded_shapes, replicated_shapes, local_shapes = plan_lint_shapes(
        model.params, mesh)
    counts = assert_collectives(
        hlo, require=["all-gather"],
        require_any=["reduce-scatter", "all-to-all", "all-reduce"],
        label="fsdp train step")
    assert_fsdp_sharded(hlo, sharded_shapes, replicated_shapes,
                        local_shapes=local_shapes,
                        label="fsdp train step")
    m["fsdp_collectives"] = counts
    m["hlo_lint"] = "pass"

    # 3. resharding-on-restore: save@8 -> restore@4 -> restore@1 --------
    import tempfile

    from zoo_tpu.orca.learn.ckpt import CheckpointManager
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td)
        state = {"params": model.params, "epoch": 3}
        cm.save(3, state)
        host = cm.restore(3)  # world-size-free host bytes
        half = max(2, n_devices // 2)
        mesh4 = build_mesh(devices[:half],
                           axis_sizes={"data": half // 2, "fsdp": 2})
        at4 = cm.restore(3, sharding=mesh4)
        mesh1 = build_mesh(devices[:1], axis_sizes={"data": 1})
        at1 = cm.restore(3, sharding=mesh1)
        ok4 = _bit_exact(host["params"], at4["params"])
        ok1 = _bit_exact(host["params"], at1["params"])
        m["reshard_save8_restore4_bitexact"] = ok4
        m["reshard_restore1_bitexact"] = ok1
        assert ok4 and ok1, "resharded restore is not bit-exact"
        frac4 = _tree_bytes_frac(at4["params"])
        m["reshard_restore4_param_bytes_frac"] = round(frac4, 4)

    # 4. sharded paged decode == unsharded reference ---------------------
    from zoo_tpu.serving.llm.spec import build_llm_engine
    ref_eng = build_llm_engine("llama:tiny:slots=2,blocks=32",
                               start=True)
    tp_eng = build_llm_engine("llama:tiny:slots=2,blocks=32,tp=2",
                              start=True)
    try:
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, 256, n).tolist() for n in (5, 23)]
        outs = []
        for eng in (ref_eng, tp_eng):
            toks = []
            hs = [eng.submit(p, 12, rid=f"r{i}")
                  for i, p in enumerate(prompts)]
            for h in hs:
                got, done = [], False
                while not done:
                    new, done = h.wait_new(len(got), 30.0)
                    assert new or done, "decode stalled"
                    got.extend(new)
                toks.append(got)
            outs.append(toks)
        ident = outs[0] == outs[1]
        m["llm_tp_token_identical"] = ident
        assert ident, f"tp decode diverged: {outs}"
        stats = tp_eng.stats()
        m["llm_decode_compiles"] = stats["compiles"]["decode"]
        m["llm_tp"] = stats["tp"]
        m["llm_kv_blocks_leaked"] = stats["blocks_used"]
        assert stats["compiles"]["decode"] == 1, stats
        assert stats["blocks_used"] == 0, stats
        m["llm_tp_param_bytes_frac"] = round(
            _tree_bytes_frac(tp_eng.model.params), 4)
        # plan-aware HLO lint on the compiled tp decode executable:
        # megatron-sharded weights must enter at LOCAL (1/tp) shape —
        # a full-global-shape entry parameter is "TP that isn't" — and
        # the donated cache must stay aliased with the token outfeed
        # at slots x 1 int32 (zoo-lint HLO-SHARDING / HLO-DONATION /
        # HLO-HOST-TRANSFER, docs/static_analysis.md)
        tp_model = tp_eng.model
        tp_sh, tp_rep, tp_loc = plan_lint_shapes(
            tp_model.params, tp_model.mesh)
        tp_hlo = tp_model.compiled_hlo("decode")
        assert_plan_sharded(tp_hlo, tp_sh, tp_rep,
                            local_shapes=tp_loc, plan="tp",
                            label="tp=2 decode executable")
        assert_llm_executable(tp_model, "decode")
        m["tp_hlo_lint"] = "pass"
        m["llm_decode_artifact_lint"] = "pass"
    finally:
        ref_eng.stop()
        tp_eng.stop()

    # 5. pipeline plan: GPipe microbatch schedule == plain dp ------------
    # same model/seed/data with a 4-layer homogeneous body trained once
    # without a plan (per-layer scan) and once under plan="pipeline" on
    # a data x pipe mesh; the loss curves must agree (on XLA CPU they
    # are bit-exact), the stacked body must ACTUALLY shard over the pipe
    # axis (~1/stages of its bytes per device), and the compiled step
    # must carry collective-permute — the "pipeline that isn't" lint
    from zoo_tpu.parallel.plans import PIPE_BODY_KEY

    pipe = 4 if n_devices % 4 == 0 else 2
    ref_p, _, _, _ = _fit_losses(None, devices[:1], batch_size=bs,
                                 body_layers=pipe)
    pshd, pmodel, pplaced, phlo = _fit_losses(
        {"data": n_devices // pipe, "pipe": pipe}, devices,
        batch_size=bs, plan="pipeline", body_layers=pipe)
    pdiff = max(abs(a - b) for a, b in zip(ref_p, pshd))
    m["pipeline_loss_max_abs_diff"] = pdiff
    assert pdiff <= LOSS_TOL, (
        f"pipeline loss curve diverged from dp by {pdiff} "
        f"(> {LOSS_TOL}): {pshd} vs {ref_p}")
    body_frac = _tree_bytes_frac(pplaced[PIPE_BODY_KEY])
    m["pipeline_body_bytes_frac"] = round(body_frac, 4)
    assert body_frac <= 1.0 / pipe + 0.05, (
        f"per-device stacked-body bytes {body_frac:.3f} of replicated — "
        "the body is not actually pipe-sharded")
    mesh_p = build_mesh(devices, axis_sizes={"data": n_devices // pipe,
                                             "pipe": pipe})
    psh, prep, ploc = plan_lint_shapes(pmodel.params, mesh_p, "pipeline")
    assert_pipeline_sharded(phlo, psh, prep, local_shapes=ploc,
                            label="pipeline train step")
    m["pipeline_collectives"] = assert_collectives(
        phlo, require=["collective-permute"],
        label="pipeline train step")
    m["pipeline_hlo_lint"] = "pass"

    # 6. moe plan: expert-sharded FFN == replicated reference ------------
    from zoo_tpu.ops.moe import init_moe_params, moe_ffn
    from zoo_tpu.parallel.plans import place_params

    mesh_e = build_mesh(devices, axis_sizes={"expert": n_devices})
    mp = init_moe_params(jax.random.PRNGKey(0), hidden=16,
                         intermediate=32, n_experts=n_devices)
    xt = np.asarray(np.random.RandomState(1).randn(2, 64, 16),
                    np.float32)
    moe_step = jax.jit(lambda p, t: moe_ffn(p, t, top_k=2,
                                            capacity_factor=1.25))
    y_ref, aux_ref = jax.tree_util.tree_map(
        np.asarray, moe_step(mp, xt))
    eplaced = place_params(mp, mesh_e, "moe")
    y_sh, aux_sh = jax.tree_util.tree_map(
        np.asarray, moe_step(eplaced, xt))
    mdiff = max(float(np.abs(y_ref - y_sh).max()),
                float(np.abs(aux_ref - aux_sh).max()))
    m["moe_out_max_abs_diff"] = mdiff
    assert mdiff <= LOSS_TOL, (
        f"expert-sharded moe_ffn diverged from replicated by {mdiff}")
    efrac = _tree_bytes_frac(
        {k: eplaced[k] for k in ("w_gate", "w_up", "w_down")})
    m["moe_expert_bytes_frac"] = round(efrac, 4)
    assert efrac <= 1.0 / n_devices + 0.05, (
        f"per-device expert-weight bytes {efrac:.3f} of replicated — "
        "experts are not actually sharded")
    moe_compiled = jax.jit(
        lambda p, t: moe_ffn(p, t, top_k=2, capacity_factor=1.25)
    ).lower(eplaced, xt).compile()
    m["moe_collectives"] = assert_collectives(
        moe_compiled,
        require_any=["all-to-all", "all-gather", "all-reduce",
                     "reduce-scatter", "collective-permute"],
        label="moe ffn")
    m["moe_hlo_lint"] = "pass"

    if verbose:
        print("ok: sharded fit matches 1-device within "
              f"{LOSS_TOL} (diff {diff:.3g}), per-device param bytes "
              f"{frac:.3f} of replicated")
        print("ok: HLO lint passed", counts)
        print("ok: save@8 -> restore@4/restore@1 bit-exact")
        print("ok: tp=2 paged decode token-identical, decode "
              "compiles == 1, 0 leaked KV blocks")
        print(f"ok: pipeline plan matches dp (diff {pdiff:.3g}), body "
              f"bytes {body_frac:.3f} of replicated, collective-permute "
              "present")
        print(f"ok: moe plan matches replicated (diff {mdiff:.3g}), "
              f"expert bytes {efrac:.3f} of replicated")
    return m


def check() -> int:
    m = collect_metrics()
    print("MULTICHIP_METRICS " + json.dumps(m, sort_keys=True))
    return 0


def main() -> int:
    # self-provision the virtual multichip platform (a CPU rig — forced
    # to JAX_PLATFORMS=cpu, never a chip): XLA only honors
    # --xla_force_host_platform_device_count before the backend
    # initializes, so the real checks always run in a child process
    # with the env forced (same bootstrap as __graft_entry__)
    if os.environ.get("_ZOO_MULTICHIP_INPROC") == "1":
        return check()
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={N_DEVICES}"])
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    env["_ZOO_MULTICHIP_INPROC"] = "1"
    return subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, timeout=840).returncode


if __name__ == "__main__":
    sys.exit(main())
