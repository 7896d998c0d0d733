"""Pipeline parallelism: GPipe-style microbatching over the ``pipe`` axis.

Net-new vs the reference (SURVEY §2.10 lists PP as absent upstream). The
TPU-native formulation: layer stages live on consecutive devices along
the mesh ``pipe`` axis (params sharded on their leading stage dim),
microbatches stream through a ``shard_map`` whose per-step hop is a
``ppermute`` — the canonical scaling-book pipeline, steady-state bubble
(S-1)/(M+S-1). Everything is a fixed-shape ``lax.scan``; autodiff flows
through ``ppermute``/``psum``, so ``jax.grad`` of a pipelined loss just
works (the backward pipeline is the transposed permute).

Composition: the microbatch row dim is sharded over the mesh's data
axes inside the ``shard_map`` (each data replica pipelines only its
batch shard; without the spec the batch would silently replicate and
every replica would redo the whole batch), so ``data×pipe`` meshes
behave like DP over pipelined workers. fsdp/tensor sharding applies
within a stage.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stages"]


def stack_stages(tree, n_stages: int):
    """Reshape each leaf's leading layer dim L into (S, L/S): a stack of
    per-stage parameter slices for :func:`pipeline_apply`."""
    def reshape(a):
        if a.ndim == 0 or a.shape[0] % n_stages:
            raise ValueError(
                f"leading dim {a.shape} must divide into {n_stages} "
                "stages")
        return a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(reshape, tree)


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   n_microbatch: int, axis: str = "pipe"):
    """Apply ``n_stages`` chained stages to ``x`` with GPipe scheduling.

    ``stage_params``: pytree whose leaves lead with the stage dim S
    (see :func:`stack_stages`); ``stage_fn(params_slice, h) -> h`` runs
    ONE stage (e.g. scans its sub-blocks). ``x``: (B, ...) with
    B % n_microbatch == 0; activations keep x's shape through stages.
    Returns the final-stage output, replicated over the ``pipe`` axis.
    """
    n_stages = mesh.shape[axis]
    if n_stages <= 1:
        raise ValueError(f"mesh axis {axis!r} must be > 1 for a pipeline")
    B = x.shape[0]
    if B % n_microbatch:
        raise ValueError(f"batch {B} not divisible into {n_microbatch} "
                         "microbatches")
    mbs = x.reshape(n_microbatch, B // n_microbatch, *x.shape[1:])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    n_steps = n_microbatch + n_stages - 1

    def worker(params, mbs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = lax.axis_index(axis)
        state = jnp.zeros_like(mbs[0])
        ys = jnp.zeros_like(mbs)
        # the carry becomes device-varying after the first ppermute; the
        # all-zero initial value must be marked varying up front or the
        # scan's carry types mismatch (shard_map vma check)
        try:
            state = lax.pcast(state, (axis,), to="varying")
            ys = lax.pcast(ys, (axis,), to="varying")
        except (AttributeError, TypeError):
            pass  # older jax: no vma tracking, nothing to mark

        def body(carry, t):
            state, ys = carry
            mb_t = lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_microbatch - 1), keepdims=False)
            h = jnp.where(idx == 0, mb_t, state)
            out = stage_fn(params, h)
            # the last stage completes microbatch j = t - (S-1)
            j = t - (n_stages - 1)
            updated = lax.dynamic_update_index_in_dim(
                ys, out, jnp.maximum(j, 0), 0)
            valid = (idx == n_stages - 1) & (j >= 0)
            ys = jnp.where(valid, updated, ys)
            state = lax.ppermute(out, axis, perm)
            return (state, ys), None

        (_, ys), _ = lax.scan(body, (state, ys), jnp.arange(n_steps))
        # only the last stage holds real outputs; psum replicates them
        # across the pipe group (others contribute zeros)
        return lax.psum(ys, axis)

    from zoo_tpu.parallel.mesh import data_axes

    specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    # microbatch ROW dim sharded over the data axes: each data replica
    # pipelines its own batch shard (P() here would replicate the batch
    # into every replica, which then redundantly computes all of it)
    daxes = data_axes(mesh)
    mb_spec = P(None, daxes if daxes else None)
    fn = jax.shard_map(worker, mesh=mesh, in_specs=(specs, mb_spec),
                       out_specs=mb_spec, check_vma=False)
    ys = fn(stage_params, mbs)
    return ys.reshape(B, *ys.shape[2:])
