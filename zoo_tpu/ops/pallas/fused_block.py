"""Fused ResNet-bottleneck forward kernel (Pallas, TPU).

One kernel computes ``relu(x + (relu(conv3x3(relu(x @ w1)))) @ w3)`` —
the full identity bottleneck (1x1 reduce, 3x3, 1x1 expand, residual
add, with BN folded into the weights as scale/shift at inference) —
reading ``x`` from HBM once and writing ``y`` once. The grid is over
batch tiles; each program holds K whole images in VMEM, so the 3x3's
halo is just zero padding at image edges (no cross-program exchange).

Measured by hand in round 4 on a v5e that was shared among users
(bf16, batch 128, 100-rep scanned chains, forward; ranges over
repeated sessions). Pre-PR-1 history, not ledger numbers:

=========  ==================  =========  =========  ==========
stage      geometry            XLA TF/s   fused      ratio
=========  ==================  =========  =========  ==========
conv2_x    56x56,  256->64     43-55      50-91      1.0-1.65x
conv3_x    28x28,  512->128    71-79      60-76      0.8-1.0x
conv4_x    14x14, 1024->256    79-87      79-86      ~1.0x
conv5_x    7x7,  2048->512     50-56      (K=0: XLA fallback)
=========  ==================  =========  =========  ==========

The conv2_x ratio tracks available HBM bandwidth: the kernel is
HBM-bound at ~182 FLOP/byte intensity, so at the ~250 GB/s those
sessions measured (a third of the 819 GB/s spec) its ceiling is
~48 TF/s and it sits at XLA
parity, while sessions with more headroom measured 74-91 TF/s vs
XLA's 45-55 (1.65x) — XLA's version of the block is stuck near 55
regardless because its narrow-N (64-lane) 1x1 matmuls starve the MXU.
At the deeper stages XLA's own producer-consumer fusion is already
excellent. Model-level training economics are thin (conv2_x is ~19%
of ResNet-50 FLOPs and backward stays on XLA), so the stock ResNet
keeps XLA convs; this op is for inference paths and early-stage-heavy
CNNs on chips with healthy HBM bandwidth.

No reference counterpart (the reference's conv fusion lives inside
MKL-DNN); geometry follows ``models/image/resnet.py`` bottlenecks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_VMEM_BUDGET = 12 << 20  # leave headroom under the 16MB scoped limit


def _xla_block(x, w1, w2, w3):
    """Reference semantics (also the off-TPU and fallback path)."""
    dn = ("NHWC", "HWIO", "NHWC")
    cin, cmid = w1.shape
    t1 = jax.nn.relu(jax.lax.conv_general_dilated(
        x, w1.reshape(1, 1, cin, cmid), (1, 1), "SAME",
        dimension_numbers=dn))
    t2 = jax.nn.relu(jax.lax.conv_general_dilated(
        t1, w2, (1, 1), "SAME", dimension_numbers=dn))
    z3 = jax.lax.conv_general_dilated(
        t2, w3.reshape(1, 1, cmid, cin), (1, 1), "SAME",
        dimension_numbers=dn)
    return jax.nn.relu(x + z3)


def _pick_k(batch: int, h: int, w: int, cin: int, cmid: int) -> int:
    """Largest power-of-two batch tile whose working set fits VMEM
    (double-buffered in/out blocks + padded-plane scratch + weights)."""
    weights = (cin * cmid + 9 * cmid * cmid + cmid * cin) * 2
    for k in (16, 8, 4, 2, 1):
        if batch % k:
            continue
        per_img = (2 * h * w * cin * 2        # x in + y out (bf16)
                   + (h + 2) * (w + 2) * cmid * 2   # padded t plane
                   + 2 * h * w * cmid * 4)    # t1 + f32 acc live values
        if 2 * k * per_img + 2 * weights <= _VMEM_BUDGET:
            return k
    return 0


def _kernel(x_ref, w1_ref, w2_ref, w3_ref, y_ref, t_scr, *, k, h, w,
            cin, cmid):
    xin = x_ref[:].reshape(k * h * w, cin)
    t1 = jnp.maximum(
        jnp.dot(xin, w1_ref[:], preferred_element_type=jnp.float32),
        0.0).astype(jnp.bfloat16)
    t_scr[:] = jnp.zeros_like(t_scr)
    t_scr[:, 1:h + 1, 1:w + 1, :] = t1.reshape(k, h, w, cmid)
    acc = jnp.zeros((k * h * w, cmid), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            win = t_scr[:, dy:dy + h, dx:dx + w, :]
            acc = acc + jnp.dot(win.reshape(k * h * w, cmid),
                                w2_ref[dy, dx],
                                preferred_element_type=jnp.float32)
    t2 = jnp.maximum(acc, 0.0).astype(jnp.bfloat16)
    z3 = jnp.dot(t2, w3_ref[:],
                 preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    y_ref[:] = jnp.maximum(z3 + xin, 0.0).reshape(k, h * w, cin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_bottleneck(x: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray,
                     w3: jnp.ndarray,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """``relu(x + expand(relu(conv3x3(relu(reduce(x))))))`` fused.

    ``x``: (B, H, W, Cin) bf16/f32; ``w1``: (Cin, Cmid); ``w2``:
    (3, 3, Cmid, Cmid) HWIO; ``w3``: (Cmid, Cin). Follows the package
    interpret contract (``interpret=None`` → Pallas interpreter
    off-TPU, compiled kernel on TPU); on TPU a geometry exceeding the
    kernel's VMEM plan falls back to the XLA composition.

    Differentiable via ``jax.custom_vjp``: the backward RECOMPUTES the
    XLA composition's residuals and reuses its VJP (the kernel writes
    only ``y``, so t1/t2 are not available to save — exporting them
    would double the HBM writes the fusion exists to avoid). Training
    cost is therefore fused_fwd + ~1 extra XLA forward vs the all-XLA
    block; with the conv2_x fused speedup at most 1.65x of one forward,
    the net train-step delta is negative — measured and documented in
    the module docstring. Train with the stock XLA convs; this op's
    win is inference.
    """
    return _fused_bottleneck_impl(x, w1, w2, w3, interpret)


def _fused_bottleneck_fwd(x, w1, w2, w3, interpret):
    return _fused_bottleneck_impl(x, w1, w2, w3, interpret), \
        (x, w1, w2, w3)


def _fused_bottleneck_bwd(interpret, res, g):
    x, w1, w2, w3 = res
    _, vjp = jax.vjp(_xla_block, x, w1, w2, w3)
    return vjp(g.astype(x.dtype))


fused_bottleneck.defvjp(_fused_bottleneck_fwd, _fused_bottleneck_bwd)


def _fused_bottleneck_impl(x: jnp.ndarray, w1: jnp.ndarray,
                           w2: jnp.ndarray, w3: jnp.ndarray,
                           interpret: Optional[bool] = None
                           ) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from zoo_tpu.ops.pallas import resolve_interpret

    b, h, w, cin = x.shape
    cmid = w1.shape[1]
    interpret = resolve_interpret(interpret)
    if interpret:
        # the interpreter has no VMEM; any batch tile works — keep it
        # small so CPU tests stay fast
        k = 1 if b % 2 else 2
    else:
        k = _pick_k(b, h, w, cin, cmid)
        if k == 0:  # geometry exceeds the kernel's VMEM plan
            return _xla_block(x, w1, w2, w3)

    dtype = jnp.bfloat16
    xf = x.astype(dtype).reshape(b, h * w, cin)
    kern = functools.partial(_kernel, k=k, h=h, w=w, cin=cin, cmid=cmid)
    y = pl.pallas_call(
        kern,
        grid=(b // k,),
        in_specs=[
            pl.BlockSpec((k, h * w, cin), lambda i: (i, 0, 0)),
            pl.BlockSpec((cin, cmid), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, cmid, cmid), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((cmid, cin), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((k, h * w, cin), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h * w, cin), dtype),
        scratch_shapes=[pltpu.VMEM((k, h + 2, w + 2, cmid), dtype)],
        interpret=interpret,
    )(xf, w1.astype(dtype), w2.astype(dtype), w3.astype(dtype))
    return y.reshape(b, h, w, cin).astype(x.dtype)
