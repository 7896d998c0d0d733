"""Mixture-of-Experts feed-forward with expert parallelism.

Net-new vs the reference (SURVEY §2.10 lists EP as absent upstream); the
TPU-native formulation is the public GShard/Switch dense-dispatch recipe:
token→expert routing becomes one-hot dispatch/combine einsums, expert
weights carry an ``E`` (expert) leading dim sharded over the mesh
``expert`` axis, and GSPMD inserts the token all-to-alls from the
sharding annotations — no hand-written collectives, fixed shapes
throughout (capacity-factor token dropping keeps the dispatch tensor
static for XLA).

Functional core only; ``models/llm/moe_llama.py`` wires it into the
Llama block.

:func:`moe_ffn_dropless` is the serving step's layer: no capacity and no
dropped token. Tokens are sorted by expert and one grouped matrix
product (the Pallas kernel ``zoo_moe_gmm`` on a TPU,
``jax.lax.ragged_dot`` elsewhere) runs over the experts that have rows,
so a decode tick reads only the experts its tokens chose and a prefill
chunk computes each token's own experts, with fixed shapes and one
executable for any routing.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_moe_params", "moe_ffn", "moe_param_specs",
           "expert_capacity", "route_topk", "moe_ffn_dropless"]


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots; multiples of 8 keep TPU tiling happy."""
    cap = int(np.ceil(top_k * n_tokens * capacity_factor / n_experts))
    return max(8, -(-cap // 8) * 8)


def init_moe_params(rng, hidden: int, intermediate: int, n_experts: int,
                    init=None) -> Dict[str, jnp.ndarray]:
    init = init or jax.nn.initializers.glorot_uniform()
    ks = jax.random.split(rng, 4)
    return {
        "router": init(ks[0], (hidden, n_experts), jnp.float32),
        "w_gate": init(ks[1], (n_experts, hidden, intermediate),
                       jnp.float32),
        "w_up": init(ks[2], (n_experts, hidden, intermediate),
                     jnp.float32),
        "w_down": init(ks[3], (n_experts, intermediate, hidden),
                       jnp.float32),
    }


def moe_param_specs(n_experts: int) -> Dict[str, Tuple]:
    """PartitionSpec tuples for :func:`init_moe_params` output: expert
    weights sharded over the ``expert`` mesh axis, router replicated."""
    from jax.sharding import PartitionSpec as P

    return {"router": P(None, None),
            "w_gate": P("expert", None, None),
            "w_up": P("expert", None, None),
            "w_down": P("expert", None, None)}


def moe_ffn(params: Dict, x: jnp.ndarray, *, top_k: int = 2,
            capacity_factor: Optional[float] = None,
            aux_loss_weight: float = 0.01, group_size: int = 512
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE SwiGLU feed-forward over tokens.

    ``x``: (B, T, H) → returns (y, aux_loss) where ``aux_loss`` is the
    Switch-style load-balancing term (already weighted); add it to the
    task loss. Tokens routed past an expert's capacity are dropped
    (standard GShard semantics — the residual connection carries them).
    ``capacity_factor=None`` reads ``ZOO_MOE_CAPACITY`` (default 1.25).

    Tokens are routed within fixed ``group_size`` GROUPS (GShard's 2-D
    dispatch): the dispatch/combine tensors are (g, G, E, C_g) with
    C_g ∝ G/E, so memory is linear in token count — a single global
    dispatch would be O(N²) and OOM at real sequence lengths. Capacity
    (and therefore dropping) is per-group.
    """
    if capacity_factor is None:
        from zoo_tpu.common import knobs
        capacity_factor = float(knobs.value("ZOO_MOE_CAPACITY"))
    B, T, H = x.shape
    E = params["router"].shape[1]
    N = B * T
    G = min(int(group_size), N)
    n_groups = -(-N // G)
    pad = n_groups * G - N
    xf = x.reshape(N, H)
    if pad:
        xf = jnp.concatenate(
            [xf, jnp.zeros((pad, H), x.dtype)], axis=0)
    xg = xf.reshape(n_groups, G, H)
    # padded rows must not claim capacity slots or bias the aux loss
    valid = (jnp.arange(n_groups * G) < N).astype(jnp.float32) \
        .reshape(n_groups, G)
    C = expert_capacity(G, E, top_k, capacity_factor)

    logits = jnp.einsum("gnh,he->gne", xg.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                # (g, G, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # (g, G, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    gate_vals = gate_vals * valid[..., None]

    # position of each (token, slot) in its expert's per-group queue.
    # Slot-major flattening makes top-1 choices win capacity over
    # top-2 spillover.
    oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)    # (g, G, k, E)
    oh = oh * valid[..., None, None]
    flat = oh.transpose(0, 2, 1, 3).reshape(n_groups, top_k * G, E)
    pos = (jnp.cumsum(flat, axis=1) - 1.0) * flat
    pos = pos.reshape(n_groups, top_k, G, E).transpose(0, 2, 1, 3)
    keep = (pos < C) & (oh > 0)                            # (g, G, k, E)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                          dtype=jnp.float32) * keep[..., None]
    combine = (slot * gate_vals[..., None, None]).sum(2)   # (g, G, E, C)
    dispatch = (combine > 0).astype(x.dtype)

    # dispatch → per-expert batches; with dispatch sharded on the group
    # (token) dim and the (E, g, C, H) result sharded on E, GSPMD lowers
    # this einsum to the token all-to-all
    expert_in = jnp.einsum("gnec,gnh->egch", dispatch, xg)
    a = jax.nn.silu(jnp.einsum("egch,ehf->egcf", expert_in,
                               params["w_gate"].astype(x.dtype)))
    b = jnp.einsum("egch,ehf->egcf", expert_in,
                   params["w_up"].astype(x.dtype))
    out_e = jnp.einsum("egcf,efh->egch", a * b,
                       params["w_down"].astype(x.dtype))
    y = jnp.einsum("egch,gnec->gnh", out_e, combine.astype(x.dtype))
    y = y.reshape(n_groups * G, H)[:N]

    # Switch load-balance loss: E * sum_e f_e * P_e  (f = token fraction
    # routed top-1 to e, P = mean router prob for e); 1.0 at uniform.
    # Means run over VALID tokens only.
    top1 = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32) \
        * valid[..., None]
    denom = jnp.maximum(valid.sum(), 1.0)
    f = top1.sum((0, 1)) / denom
    pm = (probs * valid[..., None]).sum((0, 1)) / denom
    aux = E * jnp.sum(f * pm) * aux_loss_weight
    return y.reshape(B, T, H), aux.astype(jnp.float32)


def route_topk(x: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray,
               top_k: int, scale: float = 1.0, norm_topk: bool = True,
               routing: str = "sigmoid"
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routing of tokens ``x`` (N, H) to ``top_k`` experts, the scores
    ``x . router`` in float32 at full precision → ``(idx (N, k) int32,
    w (N, k) f32)``. ``routing`` (fixed at trace time):

    * ``"sigmoid"``, with a selection bias (``noaux_tc``): ``s =
      sigmoid(x . router)``; the chosen experts are those of largest
      ``s + bias``; the weights are ``s`` of the chosen (the bias changes
      the choice, never the weight);
    * ``"softmax"``: ``s = softmax(x . router)`` over all experts; the
      chosen are those of largest ``s`` and ``bias`` is not read.

    The weights are renormalised to sum to 1 if ``norm_topk``, times
    ``scale``."""
    logits = jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if routing == "softmax":
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    elif routing == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
    else:
        raise ValueError(f"unknown routing {routing!r} (sigmoid / softmax)")
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def _held_dot(x, w, **kw):
    """``x . w`` in the dtype the weight is held in, f32 out."""
    if w.dtype.itemsize < x.dtype.itemsize:
        x = x.astype(w.dtype)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32, **kw)


def _grouped_dot(rows, weights, sizes):
    """``rows`` (M, K), sorted by expert, against each expert's own
    ``weights`` (E, K, N); ``sizes`` (E,) rows an expert. The Pallas
    kernel ``zoo_moe_gmm`` on a TPU, ``jax.lax.ragged_dot`` elsewhere
    (where the kernel would run interpreted)."""
    from zoo_tpu.ops.pallas import on_tpu
    if on_tpu():
        from zoo_tpu.ops.pallas.moe_gmm import moe_gmm
        return moe_gmm(rows, weights, sizes)
    return jax.lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=jnp.float32)


def moe_ffn_dropless(params: Dict, x: jnp.ndarray, *, top_k: int,
                     scale: float = 1.0, norm_topk: bool = True,
                     valid: Optional[jnp.ndarray] = None,
                     routing: str = "sigmoid"
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless routed SwiGLU experts plus the shared expert.

    ``x``: (N, H) tokens → ``(y (N, H) float32, counts (2,) int32)``.
    ``params``: ``router`` (H, E) and, for ``routing="sigmoid"``, ``bias``
    (E,) in float32 (:func:`route_topk`),
    ``w_gate`` / ``w_up`` (E, H, F) and ``w_down`` (E, F, H) in the
    dtype they are held in, and, where the layer has a shared expert,
    ``ws_gate`` / ``ws_up`` (H, Fs) and ``ws_down`` (Fs, H), added once
    for every token.

    Every token's ``top_k`` choices are computed: the N * k
    (token, choice) rows are sorted by expert and the three grouped
    products run over the rows of each expert in turn, whatever the
    routing (all rows on one expert included). There is no capacity
    and nothing is dropped. ``counts`` is (experts that had a row, rows)
    over the tokens ``valid`` marks (default: all): what a decode tick
    reports as ``zoo_llm_moe_expert_visits_total`` /
    ``zoo_llm_moe_rows_total``; ``valid`` changes no output."""
    N, H = x.shape
    E = params["router"].shape[-1]
    with jax.named_scope("zoo.moe_route"):
        idx, w = route_topk(x, params["router"], params.get("bias"),
                            top_k, scale, norm_topk, routing)
        flat = idx.reshape(N * top_k)
        order = jnp.argsort(flat)            # stable: rows of one expert
        inverse = jnp.argsort(order)         # keep their token order
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        rows = jnp.take(x, order // top_k, axis=0)       # (N * k, H)
        seen = sizes if valid is None else jnp.zeros(
            (E,), jnp.int32).at[flat].add(
                jnp.repeat(valid.reshape(N).astype(jnp.int32), top_k))
        counts = jnp.stack([jnp.sum(seen > 0),
                            jnp.sum(seen)]).astype(jnp.int32)
    with jax.named_scope("zoo.moe_experts"):
        if params["w_gate"].dtype.itemsize < rows.dtype.itemsize:
            rows = rows.astype(params["w_gate"].dtype)
        act = jax.nn.silu(_grouped_dot(rows, params["w_gate"], sizes)) \
            * _grouped_dot(rows, params["w_up"], sizes)
        out = _grouped_dot(act.astype(rows.dtype), params["w_down"], sizes)
        # back to token order, (N, k, H), and the weighted sum of each
        # token's own choices
        y = jnp.sum(jnp.take(out, inverse, axis=0).reshape(N, top_k, H)
                    * w[..., None], axis=1)
    if "ws_gate" in params:
        with jax.named_scope("zoo.moe_shared"):
            y = y + _held_dot(
                jax.nn.silu(_held_dot(x, params["ws_gate"]))
                * _held_dot(x, params["ws_up"]), params["ws_down"])
    return y, counts
