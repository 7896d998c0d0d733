"""Every exported Pallas kernel compiles for the chip — checked without one.

libtpu ships the TPU compiler, so a compile-only v5e target exists in
the CPU sandbox: ``jax.experimental.topologies`` describes the devices
of a ``v5e:2x2`` host, and ``jit(...).lower(...).compile()`` against
them runs the same XLA + Mosaic pipeline the chip run does
(``interpret=False``). Lowering is not running — numerics and VMEM
behaviour are ``chip_smoke.py``'s to check on the device — but a
BlockSpec the TPU lowering refuses, or an op Mosaic has not
implemented, fails here, where the interpreter would have passed it.

The shapes are the smoke's own (``chip_smoke.kernel_cases(FULL, ...)``),
so this file and the chip run cannot drift apart. If the topology
cannot be created the tests fail; they do not skip.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

MOSAIC_CALL = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(autouse=True)
def _no_mesh_context():
    """``fused_apply_adam`` takes its jnp path under a multi-device
    runtime context; a context left over from another test file must
    not turn this into a test of the fallback."""
    from zoo_tpu.orca import stop_orca_context
    stop_orca_context()


def _compile_for(fn, args, sharding):
    avals = [jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=s)
             for a, s in zip(args, sharding)]
    return jax.jit(fn).lower(*avals).compile().as_text()


def _compiled(kernel):
    """``kernel(q, kc, vc, bt, pos[, k_scale, v_scale])``, compiled."""
    def call(q, kc, vc, bt, pos, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return kernel(q, kc, vc, bt, pos, interpret=False, **kw)
    return call


_CASES = chip_smoke.kernel_cases(chip_smoke.FULL, interpret=False)


@pytest.mark.parametrize("case", _CASES, ids=[c.name for c in _CASES])
def test_kernel_compiles_for_v5e(v5e, case):
    args = case.make()
    one = SingleDeviceSharding(v5e[0])
    hlo = _compile_for(case.fn, args, [one] * len(args))
    assert MOSAIC_CALL in hlo, \
        f"{case.name}: no Mosaic kernel in the compiled executable"


def test_every_exported_kernel_is_covered():
    """The case list names each kernel ``zoo_tpu.ops.pallas`` exports
    (the other exports are jnp helpers and dispatch rules)."""
    kernels = {"flash_attention", "paged_flash_decode",
               "paged_flash_prefill", "sparse_paged_decode",
               "dsa_paged_decode", "lightning_decode", "quantized_matmul",
               "fused_quantized_matmul", "conv2d", "conv2d_int8",
               "fused_apply_sgd", "fused_apply_adam", "fused_bottleneck"}
    import zoo_tpu.ops.pallas as zp
    assert kernels <= set(zp.__all__)
    covered = {c.name.split("[")[0] for c in _CASES}
    assert covered == kernels, covered ^ kernels


def test_mla_decode_compiles_at_the_cell_widths(v5e):
    """``zoo_mla_decode`` at the widths and engine sizes of
    ``glm-4.7-flash.longctx-closed`` (20 heads, rank 512 + rope 64 in
    rows of 640, 7 layers x 18,432 blocks of 16, 544 table entries):
    Mosaic takes it, and the whole cache goes in as it lies (no
    relayout copy of the 2.6 GB operand in front of the kernel)."""
    from zoo_tpu.ops.pallas.mla_decode import mla_paged_decode
    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def call(ql, qr, cache, layer, bt, pos):
        return mla_paged_decode(ql, qr, cache, layer, bt, pos,
                                scale=256 ** -0.5, interpret=False)

    hlo = jax.jit(call).lower(
        sds((32, 20, 512), jnp.float32), sds((32, 20, 64), jnp.float32),
        sds((7, 18432, 16, 640), jnp.bfloat16), sds((), jnp.int32),
        sds((32, 544), jnp.int32), sds((32,), jnp.int32)
    ).compile().as_text()
    assert MOSAIC_CALL in hlo and "zoo_mla_decode" in hlo
    whole = [ln for ln in hlo.splitlines()
             if "= bf16[7,18432,16,640]" in ln and " parameter(" not in ln]
    assert not whole, whole[:2]


@pytest.mark.parametrize("entries", [160, 640])
def test_paged_decode_compiles_at_the_cell_widths(v5e, entries):
    """``zoo_paged_decode`` at the sizes of the Mistral cells (32 slots,
    32 query heads on 8 kv heads of 128, an int8 cache of 8 layers x
    5,120 blocks of 16 rows with its head-major scale planes, 160 table
    entries) and at the long table of 640: Mosaic takes the multi-entry
    step (the slabs and their DMAs out of HBM), and the cache and the
    scale planes go in as they lie (no operation but a parameter yields
    a leaf's whole shape)."""
    from zoo_tpu.ops.pallas.paged_decode import paged_flash_decode
    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def call(q, kc, vc, ks, vs, layer, bt, pos):
        return paged_flash_decode(q, kc, vc, bt, pos, layer=layer,
                                  k_scale=ks, v_scale=vs, interpret=False)

    hlo = jax.jit(call).lower(
        sds((32, 32, 128), jnp.float32),
        sds((8, 5120, 8, 16, 128), jnp.int8),
        sds((8, 5120, 8, 16, 128), jnp.int8),
        sds((8, 5120, 1, 128), jnp.float32),
        sds((8, 5120, 1, 128), jnp.float32), sds((), jnp.int32),
        sds((32, entries), jnp.int32), sds((32,), jnp.int32)
    ).compile().as_text()
    assert MOSAIC_CALL in hlo and "zoo_paged_decode" in hlo
    whole = [ln for ln in hlo.splitlines()
             if ("= s8[8,5120,8,16,128]" in ln
                 or "= f32[8,5120,1,128]" in ln)
             and " parameter(" not in ln]
    assert not whole, whole[:2]


@pytest.mark.parametrize("rows,k,n", [(128, 2048, 1536),
                                      (2048, 1536, 2048)])
def test_moe_gmm_compiles_at_the_cell_widths(v5e, rows, k, n):
    """``zoo_moe_gmm`` at GLM-4.7-Flash's expert widths (64 experts of
    2048 x 1536) with a decode tick's 128 rows and a prefill chunk's
    2,048: the experts' leaf goes in as it lies."""
    from zoo_tpu.ops.pallas.moe_gmm import moe_gmm
    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    hlo = jax.jit(lambda a, b, s: moe_gmm(a, b, s, interpret=False)).lower(
        sds((rows, k), jnp.bfloat16), sds((64, k, n), jnp.bfloat16),
        sds((64,), jnp.int32)).compile().as_text()
    assert MOSAIC_CALL in hlo and "zoo_moe_gmm" in hlo
    copies = [ln for ln in hlo.splitlines()
              if f"= bf16[64,{k},{n}]" in ln and " parameter(" not in ln]
    assert not copies, copies[:2]


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_paged_kernels_compile_under_two_way_shard_map(v5e, kv):
    """The tp=2 serving layout: cache and query heads split over two
    devices on the kv-head axis, each device running the kernel on its
    own heads (``PagedLlamaModel._paged_attend`` / ``_prefill_attend``)."""
    from zoo_tpu.ops.pallas import paged_flash_decode, paged_flash_prefill

    mesh = Mesh(np.array(v5e[:2]), ("model",))
    cases = {c.name: c for c in _CASES}
    heads = P(None, "model", None, None)
    scale = P(None, None, "model")        # one head-major row a block

    def sharded(kernel, q_spec, pos_spec, n_scales):
        return jax.shard_map(
            _compiled(kernel), mesh=mesh,
            in_specs=(q_spec, heads, heads, P(None, None), pos_spec)
            + (scale,) * n_scales,
            out_specs=q_spec, check_vma=False)

    def shardings(specs):
        return [NamedSharding(mesh, s) for s in specs]

    # decode: the case feeds (S, 1, H, D); the kernel takes (S, H, D)
    q, kc, vc, bt, pos, *sc = cases[f"paged_flash_decode[{kv}]"].make()
    q_spec = P(None, "model", None)
    hlo = _compile_for(
        sharded(paged_flash_decode, q_spec, P(None), len(sc)),
        [q[:, 0], kc, vc, bt, pos[:, 0], *sc],
        shardings((q_spec, heads, heads, P(None, None), P(None))
                  + (scale,) * len(sc)))
    assert MOSAIC_CALL in hlo

    q_spec = P(None, None, "model", None)
    for shape in ("chunk", "verify"):
        args = cases[f"paged_flash_prefill[{shape},{kv}]"].make()
        hlo = _compile_for(
            sharded(paged_flash_prefill, q_spec, P(None, None),
                    len(args) - 5),
            args,
            shardings((q_spec, heads, heads, P(None, None),
                       P(None, None)) + (scale,) * (len(args) - 5)))
        assert MOSAIC_CALL in hlo


def test_serving_geometry_other_block_sizes_compile(v5e):
    """Block sizes 8 and 32 beside the smoke's 16, all three KV dtypes:
    an int8 block of 8 or 16 rows sits under the (32, 128) int8 tile."""
    from zoo_tpu.ops.pallas import paged_flash_decode, paged_flash_prefill

    one = SingleDeviceSharding(v5e[0])
    S, H, n_kv, D, nb, W, C = 8, 12, 4, 64, 64, 16, 5
    for bs in (8, 32):
        for dt in (jnp.float32, jnp.bfloat16, jnp.int8):
            cache = jnp.zeros((nb, n_kv, bs, D), dt)
            sc = [jnp.zeros((nb, 1, n_kv * bs), jnp.float32)] * 2 \
                if dt == jnp.int8 else []
            bt = jnp.zeros((S, W), jnp.int32)
            for kernel, q, pos in (
                    (paged_flash_decode, jnp.zeros((S, H, D)),
                     jnp.zeros((S,), int)),
                    (paged_flash_prefill, jnp.zeros((S, C, H, D)),
                     jnp.zeros((S, C), int))):
                args = [q, cache, cache, bt, pos, *sc]
                assert MOSAIC_CALL in _compile_for(
                    _compiled(kernel), args, [one] * len(args)), \
                    (kernel.__name__, bs, dt)


def _compile_paged_kernels(monkeypatch):
    """Compile the paged kernels, although this process sits on a CPU."""
    for mod in ("paged_decode", "paged_prefill"):
        monkeypatch.setattr(sys.modules[f"zoo_tpu.ops.pallas.{mod}"],
                            "_resolve_interpret", lambda i: False)


def _step_hlo(model, which, params, cache, one):
    """Optimized HLO of one of ``model``'s four serving steps for the
    v5e target, the cache donated, from shapes alone: ``params`` and
    ``cache`` are trees of arrays or of ``ShapeDtypeStruct``."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def avals(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)

    # every step takes its host operands as one packed word buffer
    fn, args = {
        "decode": (model._decode_fn, [
            sds((model.num_slots,), jnp.int32),
            model._layouts["decode"].aval(one)]),
        "prefill_chunk": (model._prefill_chunk_fn, [
            model._layouts["prefill_chunk"].aval(one)]),
        "prefill": (model._prefill_fn, [
            model._prefill_layouts[model.prefill_buckets[-1]].aval(one)]),
        "verify": (model._verify_fn, [model._layouts["verify"].aval(one)]),
    }[which]
    return jax.jit(fn, donate_argnums=(1,)).lower(
        avals(params), avals(cache), *args).compile().as_text()


@pytest.mark.parametrize("which", ["decode", "prefill_chunk"])
def test_serving_step_holds_no_weight_convert(v5e, monkeypatch, which):
    """An f32 x f32 dot is one bf16 pass on the MXU: handed f32 weights
    the compiler converts each whole ``(n_layer, ...)`` stack to bf16
    outside the layer scan, once per call (the largest line of both
    serving cells in the ledger of PR 25). With the leaves narrowed as
    ``PagedLlamaModel`` narrows them on a TPU the optimized HLO holds
    no such convert; with the f32 leaves it does, so this test bites."""
    import re

    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import (
        DOT_BLOCK_LEAVES,
        PagedLlamaModel,
        narrow_dot_weights,
    )

    _compile_paged_kernels(monkeypatch)
    one = SingleDeviceSharding(v5e[0])
    cfg = LlamaConfig(vocab=512, hidden=512, n_block=2, n_head=4,
                      n_kv_head=2, intermediate=1024, rope_theta=1e6)
    C = 32
    model = PagedLlamaModel(
        cfg, seed=0, num_slots=8, block_size=16, num_blocks=64,
        max_blocks_per_seq=8, prefill_buckets=(C,), prefill_chunk=C,
        kv_dtype="int8", decode_impl="flash", prefill_impl="flash")
    assert model.weight_dtype == "float32"       # a CPU holds what it got

    def hlo(params):
        return _step_hlo(model, which, params, model._cache, one)

    stacks = {",".join(map(str, model.params["blocks"][n].shape))
              for n in DOT_BLOCK_LEAVES}
    stacks.add(",".join(map(str, model.params["head"].shape)))

    def weight_converts(text):
        return [shape for shape in re.findall(
            r"= bf16\[([\d,]+)\]\S* convert\(", text) if shape in stacks]

    narrow = hlo(narrow_dot_weights(model.params, "tpu"))
    assert MOSAIC_CALL in narrow
    assert weight_converts(narrow) == []
    assert len(set(weight_converts(hlo(model.params)))) >= 4


# what moves an array: a relayout or plain copy, its asynchronous form,
# and a slice taken out of or written back into a larger array
_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def cache_moves(hlo_text, leaf_shapes):
    """Instructions of the optimized HLO, fused or not, that move a
    whole cache leaf or one layer's slab of it: an opcode of ``_MOVES``
    whose result has the shape of a leaf ``(n_layer, ...)``, of
    ``(1, ...)`` or of ``(...)``. The in-place scatters of
    ``zoo.kv_append`` are none of these. One thing is let through: a
    ``copy-start`` whose two sides have ONE layout and differ in their
    memory space alone, which is the compiler's own prefetch of a small
    operand into VMEM and no relayout (at the cells' 8 layers and 32
    slots it does that to the int8 scale planes, 21 MB each; a K or V
    leaf never fits). Returns ``(moves, prefetches)``."""
    import re
    dims = set()
    for shape in leaf_shapes:
        dims |= {shape, (1,) + shape[1:], shape[1:]}
    dims = {",".join(map(str, d)) for d in dims}
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = \(?(\w+)\[([\d,]*)\]"
                     r"(\{[^}]*\})?(?:, \w+\[[\d,]*\](\{[^}]*\})?)?"
                     r".*? ([\w-]+)\(", line)
        if not m or m.group(6) not in _MOVES or m.group(3) not in dims:
            continue

        def space_free(layout):
            return re.sub(r"S\(\d+\)", "", layout or "")
        prefetch = m.group(6) == "copy-start" and m.group(5) is not None \
            and space_free(m.group(4)) == space_free(m.group(5))
        found.append((prefetch, f"{m.group(1)} = {m.group(2)}"
                      f"[{m.group(3)}]{m.group(4) or ''} {m.group(6)}"))
    return [f for p, f in found if not p], [f for p, f in found if p]


@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("which", ["decode", "prefill_chunk", "prefill",
                                   "verify"])
def test_serving_step_moves_no_cache(v5e, monkeypatch, which, kv):
    """The cache rides the layer loop's carry and both paged kernels
    read it at a layer index: every leaf is aliased input to output,
    and no operation copies, relays, slices out or writes back a whole
    leaf or a layer's slab of one (K, V and the int8 scale planes
    alike). At the geometry of the Mistral cells (5,120 blocks of 8 kv
    heads x 16 rows x 128, two layers of it), where the parent's
    ``xs``/``ys`` loop held 24 / 24 / 20 / 24 such operations (int8)
    and 12 / 12 / 10 / 12 (bf16): the ledger's top lines. Shapes
    alone: the blocks are never allocated."""
    from zoo_tpu.analysis.hlo import input_output_aliases
    from zoo_tpu.models.llm.llama import LlamaConfig
    from zoo_tpu.serving.llm.model import (
        PagedLlamaModel,
        narrow_dot_weights,
    )

    _compile_paged_kernels(monkeypatch)
    cfg = LlamaConfig(vocab=512, hidden=2048, n_block=2, n_head=16,
                      n_kv_head=8, intermediate=512, rope_theta=1e6)
    model = PagedLlamaModel(
        cfg, seed=0, num_slots=8, block_size=16, num_blocks=4,
        max_blocks_per_seq=16, prefill_buckets=(32,), prefill_chunk=32,
        kv_dtype=kv, spec_k=2, decode_impl="flash", prefill_impl="flash")
    cache = {name: jax.ShapeDtypeStruct(
        (a.shape[0], 5120) + a.shape[2:], a.dtype)
        for name, a in model._cache.items()}
    text = _step_hlo(model, which, narrow_dot_weights(model.params, "tpu"),
                     cache, SingleDeviceSharding(v5e[0]))
    if which != "prefill":                    # the bucket attends no cache
        assert MOSAIC_CALL in text
    n_leaves = model.donated_cache_leaves()
    assert n_leaves == (4 if kv == "int8" else 2)
    assert len({p for _, p in input_output_aliases(text)}) == n_leaves
    kv_leaves = [a.shape for a in cache.values() if len(a.shape) == 5]
    planes = [a.shape for a in cache.values() if len(a.shape) == 4]
    assert cache_moves(text, kv_leaves) == ([], [])
    assert cache_moves(text, planes)[0] == []


@pytest.mark.parametrize("which", ["decode", "prefill_chunk"])
def test_sparse_and_state_step_moves_no_cache(v5e, monkeypatch, which):
    """The block with two kinds of per-sequence memory, at the head
    widths and the cache geometry of ``minicpm-sala.ctx32k-closed``
    (17,664 pages of 2 K/V heads x 64 rows x 128, 544 table entries; a
    per-slot state of heads x 128 x 128 float32): all four leaves are
    aliased input to output, and no operation copies, relays, slices out
    or writes back a whole leaf (K, V, the compressed keys, the state).
    Two things this test has caught: a gather of a window's keys with
    the head axis left as a slice made the compiler relay the whole K
    leaf, once a sparse layer and tick (1.7 GB each); writing a chunk's
    state back with ``.at[layer, slot].set`` gave the update the layout
    of the product that made it and relaid the whole state leaf, there
    and back, which is why ``zoo_state_write`` exists."""
    from zoo_tpu.analysis.hlo import input_output_aliases
    from zoo_tpu.models.llm.minicpm_sala import MiniCpmSalaConfig
    from zoo_tpu.serving.llm.model import narrow_dot_weights
    from zoo_tpu.serving.llm.model_sala import PagedMiniCpmSalaModel

    for mod in ("sparse_decode", "lightning"):
        monkeypatch.setattr(sys.modules[f"zoo_tpu.ops.pallas.{mod}"],
                            "_resolve_interpret", lambda i: False)
    cfg = MiniCpmSalaConfig(
        vocab=512, hidden=512, intermediate=512, lightning_heads=8,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"))
    model = PagedMiniCpmSalaModel(
        cfg, seed=0, num_slots=8, block_size=64, num_blocks=4,
        max_blocks_per_seq=544, prefill_buckets=(512,), prefill_chunk=512,
        kv_dtype="bf16", spec_k=0, decode_impl="flash")
    one = SingleDeviceSharding(v5e[0])
    cache = {name: jax.ShapeDtypeStruct(
        a.shape if name in model.UNPAGED_LEAVES
        else (a.shape[0], 17664) + a.shape[2:], a.dtype)
        for name, a in model._cache.items()}
    params = narrow_dot_weights(model.params, "tpu", model.DOT_LEAVES)
    text = _step_hlo(model, which, params, cache, one)
    if which == "decode":
        assert "zoo_sparse_decode" in text
        assert "zoo_lightning_decode" in text
    else:
        # (the chunk of a stateful model reads its slot's word)
        assert "zoo_state_write" in text
    assert model.donated_cache_leaves() == 4
    assert len({p for _, p in input_output_aliases(text)}) == 4
    # (at this test's 8 slots of 8 heads the compiler may prefetch the
    # 8 MB state into VMEM, one layout on both sides: no relayout; the
    # cell's 604 MB never fits)
    assert cache_moves(text, [a.shape for a in cache.values()])[0] == []


@pytest.mark.parametrize("which", ["decode", "prefill_chunk"])
def test_token_selection_step_moves_no_cache(v5e, monkeypatch, which):
    """The learned indexer's block at the head widths and the cache
    geometry of ``keye-vl-2.0.doc32k-closed`` (8,704 pages of 64 tokens,
    a token's K and V of 4 heads of 128 one (8, 128) row, its index key
    in a row of 128 lanes, 528 table entries, 16 slots; the experts'
    widths, fewer of them): both leaves are aliased input to output,
    and no operation copies, relays, slices out or writes back a whole
    leaf. What this test has caught: with the index keys' 64 values as
    the leaf's minor dimension the compiler laid the leaf out with its
    blocks minor and relaid all of it for the table's gather, once a
    layer and tick."""
    import zoo_tpu.ops.pallas as zp
    from zoo_tpu.analysis.hlo import input_output_aliases
    from zoo_tpu.ops.pallas import dsa_decode, moe_gmm
    from zoo_tpu.serving.llm.model import narrow_dot_weights
    from zoo_tpu.serving.llm.model_keye import KeyeConfig, PagedKeyeModel

    for mod in (dsa_decode, moe_gmm):
        monkeypatch.setattr(mod, "_resolve_interpret", lambda i: False)
    # the grouped product's kernel, as on the chip
    monkeypatch.setattr(zp, "on_tpu", lambda: True)
    cfg = KeyeConfig(vocab=512, hidden=512, n_block=2, n_experts=16)
    model = PagedKeyeModel(
        cfg, seed=0, num_slots=16, block_size=64, num_blocks=4,
        max_blocks_per_seq=528, prefill_buckets=(512,), prefill_chunk=512,
        kv_dtype="bf16", spec_k=0, decode_impl="flash")
    one = SingleDeviceSharding(v5e[0])
    cache = {name: jax.ShapeDtypeStruct((a.shape[0], 8704) + a.shape[2:],
                                        a.dtype)
             for name, a in model._cache.items()}
    params = narrow_dot_weights(model.params, "tpu", model.DOT_LEAVES)
    text = _step_hlo(model, which, params, cache, one)
    assert "zoo_moe_gmm" in text
    if which == "decode":
        assert "zoo_dsa_decode" in text
    assert model.donated_cache_leaves() == 2
    assert len({p for _, p in input_output_aliases(text)}) == 2
    assert cache_moves(text, [a.shape for a in cache.values()])[0] == []


def test_flash_attention_compiles_on_a_mesh(v5e, monkeypatch):
    """Under a multi-device jit GSPMD refuses a bare Mosaic kernel;
    ``dot_product_attention`` places it with shard_map (batch rows over
    the data axes, heads over ``model``) — the layout of a tp=2 bucket
    prefill and of a Llama ``fit`` at S >= 512 on several chips."""
    import zoo_tpu.ops.pallas as zp
    from zoo_tpu.ops.attention import dot_product_attention
    from zoo_tpu.parallel import build_mesh

    # compile, do not interpret, although this process sits on a CPU
    monkeypatch.setattr(zp, "resolve_interpret", lambda i: False)
    # (the package re-exports the function under the module's name)
    monkeypatch.setattr(sys.modules["zoo_tpu.ops.pallas.flash_attention"],
                        "_resolve_interpret", lambda i: False)
    mesh = build_mesh(list(v5e), axis_sizes={"data": 2, "model": 2})
    spec = P("data", "model", None, None)
    args = [jnp.zeros((4, 12, 512, 64), jnp.bfloat16),
            jnp.zeros((4, 4, 512, 64), jnp.bfloat16),
            jnp.zeros((4, 4, 512, 64), jnp.bfloat16)]
    shardings = [NamedSharding(mesh, spec)] * 3

    def attend(mesh):
        return lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, impl="flash", mesh=mesh)

    assert MOSAIC_CALL in _compile_for(attend(mesh), args, shardings)
    # and this is what the placement is for
    with pytest.raises(NotImplementedError, match="partitioned"):
        _compile_for(attend(None), args, shardings)
