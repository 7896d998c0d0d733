"""Lightning (linear) attention with a per-head decay: the decode step
over a per-slot state and the chunked scan of a prefill chunk.

Per head the layer keeps a float32 state ``S`` (D, D): ``S_t = lambda
S_{t-1} + k_t^T v_t``, ``o_t = D^-0.5 q_t S_t``, ``lambda = exp(-slope)``.

* :func:`lightning_decode` — one token for every slot: the Pallas kernel
  ``zoo_lightning_decode`` reads a (slot, heads) block of the state out of
  the WHOLE ``(n_layer, slots, heads, D, D)`` leaf (the layer a prefetched
  scalar), decays and updates it, takes the output row and writes the
  block back where it lay: the leaf is aliased to the output, so a
  decode step moves each state once in and once out and copies nothing.
  ``q`` and ``k`` arrive transposed, ``(slots, heads / hb, D, hb)``: a
  head's column is then a lane broadcast in the kernel and no transpose.
  A slot that is not live keeps its state (decay 1, ``k`` 0).
  :func:`lightning_decode_reference` is the plain-XLA twin.
* :func:`lightning_chunk` — ``C`` rows of one sequence with the state
  carried: ``O = ((Q K^T) * D) V + Lambda Q S_prev``, ``S_next =
  lambda^n S_prev + sum_{i<n} lambda^(n-1-i) k_i^T v_i`` with ``n`` the
  real rows of the chunk (pad rows leave the state untouched). Plain
  ``jnp`` on every platform: the chunk's products are 4 GFLOP beside the
  3.4 TFLOP of its dense ones. :func:`write_state` puts ``S_next`` back
  into the leaf (a kernel on a TPU, for the layout's sake alone).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from zoo_tpu.ops.pallas import resolve_interpret as _resolve_interpret

HEADS_PER_STEP = 8


def _heads_per_step(heads: int) -> int:
    """The most heads, up to ``HEADS_PER_STEP``, that divide ``heads``."""
    return next(b for b in range(min(HEADS_PER_STEP, heads), 0, -1)
                if heads % b == 0)


def _masked(k, decay, live):
    """A slot that is not live keeps its state: decay 1, no update."""
    return (jnp.where(live[:, None, None], k, 0.0),
            jnp.where(live[:, None], decay[None, :], 1.0))


def lightning_decode_reference(state, layer, q, k, v, decay, live):
    """The plain-XLA twin of :func:`lightning_decode`."""
    D = q.shape[-1]
    k, lam = _masked(k, decay, live)
    new = lam[..., None, None] * state[layer] \
        + k[..., :, None] * v[..., None, :]
    o = jnp.sum(q[..., :, None] * new, axis=-2) * D ** -0.5
    return o, state.at[layer].set(new)


def _kernel(lay_ref, qt_ref, kt_ref, v_ref, lam_ref, s_ref, o_ref,
            s_out_ref, *, hb, scale):
    for h in range(hb):
        new = lam_ref[h:h + 1, :] * s_ref[h] \
            + kt_ref[:, h:h + 1] * v_ref[h:h + 1, :]             # (D, D)
        s_out_ref[h] = new
        o_ref[h:h + 1, :] = jnp.sum(qt_ref[:, h:h + 1] * new, axis=0,
                                    keepdims=True) * scale


def lightning_decode(state, layer, q, k, v, decay, live, *,
                     interpret: Optional[bool] = None):
    """One decode step of a Lightning layer for every slot.

    ``state``: (n_layer, S, H, D, D) float32, every Lightning layer's,
    with ``layer`` the (traced) one stepped; ``q, k, v``: (S, H, D)
    float32; ``decay``: (H,) ``exp(-slope)``; ``live``: (S,) bool.
    Returns ``(o (S, H, D) float32, state)`` with the stepped layer's
    live slots updated in place."""
    S, H, D = q.shape
    hb = _heads_per_step(H)
    k, lam = _masked(k, decay, live)

    def cols(x):
        # (S, H, D) -> (S, H / hb, D, hb): a head's values down a column
        return x.reshape(S, H // hb, hb, D).transpose(0, 1, 3, 2)

    rows = pl.BlockSpec((None, hb, D), lambda s, j, lay: (s, j, 0))
    tcols = pl.BlockSpec((None, None, D, hb), lambda s, j, lay: (s, j, 0, 0))
    block = pl.BlockSpec((None, None, hb, D, D),
                         lambda s, j, lay: (lay[0], s, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb, scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // hb),
            in_specs=[tcols, tcols, rows, rows, block],
            out_specs=[rows, block]),
        out_shape=[jax.ShapeDtypeStruct((S, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (operand 5, the prefetched layer counted) is output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_resolve_interpret(interpret),
        name="zoo_lightning_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), cols(q), cols(k), v,
      jnp.broadcast_to(lam[..., None], (S, H, D)), state)
    return o, state


def _write_kernel(at_ref, new_ref, _, out_ref):
    out_ref[...] = new_ref[...]


def write_state(state, layer, slot, new, *,
                interpret: Optional[bool] = None):
    """``state[layer, slot] = new`` in place: ``state`` (n_layer, S, H,
    D, D) is aliased to the output and only the (layer, slot) blocks
    move. What ``state.at[layer, slot].set(new)`` says, for the chunk
    executable on a TPU: there the compiler gives the update the layout
    of the product that made ``new`` (the last two axes swapped), relays
    the WHOLE leaf into it in front of the dynamic-update-slice and back
    behind it, 2.4 GB a chunk at the benchmark's sizes; a kernel's
    operands keep the layout they are declared in."""
    _, _, H, D, _ = state.shape
    hb = _heads_per_step(H)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // hb,),
            in_specs=[pl.BlockSpec((hb, D, D), lambda j, at: (j, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, None, hb, D, D),
                                   lambda j, at: (at[0], at[1], j, 0, 0))),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={2: 0},
        interpret=_resolve_interpret(interpret),
        name="zoo_state_write",
    )(jnp.stack([jnp.asarray(layer, jnp.int32),
                 jnp.asarray(slot, jnp.int32)]), new.astype(state.dtype),
      state)


def lightning_chunk(q, k, v, slopes, s_prev, n_real):
    """``C`` consecutive rows of one sequence. ``q, k, v``: (C, H, D)
    float32; ``slopes``: (H,); ``s_prev``: (H, D, D) float32, the state
    before the chunk's first row; ``n_real``: how many leading rows are
    real. Returns ``(o (C, H, D), s_next (H, D, D))``: pad rows give
    garbage outputs and leave the state untouched."""
    C, _, D = q.shape
    i = jnp.arange(C)
    dist = i[:, None] - i[None, :]
    mask = jnp.exp(jnp.where(dist >= 0,
                             -slopes[:, None, None] * dist[None],
                             -jnp.inf))                          # (H, C, C)
    s = jnp.einsum("rhd,thd->hrt", q, k,
                   preferred_element_type=jnp.float32) * mask
    o = jnp.einsum("hrt,thd->rhd", s, v,
                   preferred_element_type=jnp.float32)
    into = jnp.exp(-slopes[None, :] * (i + 1)[:, None])          # (C, H)
    o = o + jnp.einsum("rhd,hde->rhe", q, s_prev,
                       preferred_element_type=jnp.float32) * into[..., None]
    left = n_real - 1 - i                    # steps a row's k v decays on
    w = jnp.where((left >= 0)[:, None],
                  jnp.exp(-slopes[None, :] * jnp.maximum(left, 0)[:, None]),
                  0.0)                                           # (C, H)
    s_next = s_prev * jnp.exp(-slopes * n_real)[:, None, None] \
        + jnp.einsum("thd,the->hde", k * w[..., None], v,
                     preferred_element_type=jnp.float32)
    return o * D ** -0.5, s_next
