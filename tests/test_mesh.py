import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from zoo_tpu.parallel import (
    batch_sharding,
    build_mesh,
    fsdp_param_sharding,
    replicated_sharding,
)
from zoo_tpu.parallel.mesh import shard_params, validate_batch_size


def test_build_default_mesh():
    mesh = build_mesh()
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1


def test_build_mesh_wildcard_and_explicit():
    mesh = build_mesh(axis_sizes={"data": -1, "model": 2})
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2
    with pytest.raises(ValueError):
        build_mesh(axis_sizes={"data": 3})
    with pytest.raises(ValueError):
        build_mesh(axis_sizes={"bogus": 2})


def test_batch_sharding_places_data():
    mesh = build_mesh()
    x = np.arange(32, dtype=np.float32).reshape(16, 2)
    arr = jax.device_put(x, batch_sharding(mesh, ndim=2))
    assert arr.sharding.is_equivalent_to(batch_sharding(mesh, 2), 2)
    # each of the 8 devices holds 2 rows
    assert arr.addressable_shards[0].data.shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(arr), x)


def test_fsdp_param_sharding_picks_divisible_dim():
    mesh = build_mesh(axis_sizes={"data": 2, "fsdp": 4})
    s = fsdp_param_sharding(mesh, (12, 7))
    assert s.spec[0] == "fsdp"  # 12 % 4 == 0 → dim 0
    s = fsdp_param_sharding(mesh, (7, 16))
    assert s.spec[1] == "fsdp"
    # nothing divisible → replicated
    s = fsdp_param_sharding(mesh, (7, 5))
    assert s.spec == P()


def test_shard_params_tree():
    mesh = build_mesh(axis_sizes={"fsdp": 8})
    params = {"w": jnp.ones((16, 4)), "b": jnp.ones((3,))}
    sharded = shard_params(params, mesh)
    assert sharded["w"].addressable_shards[0].data.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(sharded["w"]), np.ones((16, 4)))


def test_validate_batch_size():
    mesh = build_mesh()
    assert validate_batch_size(16, mesh) == 2
    with pytest.raises(ValueError):
        validate_batch_size(12, mesh)


def test_validate_batch_size_error_text():
    """The error must say WHAT must divide by WHAT — it is the first
    thing a user hits moving a single-chip script to a mesh."""
    mesh = build_mesh(axis_sizes={"data": 2, "fsdp": 4})
    with pytest.raises(ValueError,
                       match=r"batch_size \(12\) must be divisible by "
                             r"the number of data-parallel shards \(8\)"):
        validate_batch_size(12, mesh)


def test_factor_shape_edge_cases():
    """Mesh factoring at the world sizes the elastic path actually
    visits (8 → 6 → 1): wildcard absorption, full coverage checks, and
    the error modes."""
    from zoo_tpu.parallel.mesh import _factor_shape

    axes = ("data", "fsdp", "model")
    # 1 device: everything collapses to 1s
    assert _factor_shape(1, {"data": -1}, axes) == (1, 1, 1)
    assert _factor_shape(1, {}, axes) == (1, 1, 1)
    # 6 devices (a scale-down world size): wildcard absorbs the rest
    assert _factor_shape(6, {"data": -1, "model": 2}, axes) == (3, 1, 2)
    assert _factor_shape(6, {"data": 6}, axes) == (6, 1, 1)
    # 8 devices, fully explicit
    assert _factor_shape(8, {"data": 2, "fsdp": 2, "model": 2},
                         axes) == (2, 2, 2)
    with pytest.raises(ValueError, match="not divisible"):
        _factor_shape(6, {"data": 4}, axes)
    with pytest.raises(ValueError, match="cover 3 devices but 6"):
        _factor_shape(6, {"data": 3}, ("data",))
    with pytest.raises(ValueError, match="only one mesh axis may be -1"):
        _factor_shape(8, {"data": -1, "fsdp": -1}, axes)
    with pytest.raises(ValueError, match="positive size"):
        _factor_shape(8, {"data": 0}, axes)


def test_pick_divisible_dim_fallback_to_replication():
    """Nothing divides → None → the plan replicates instead of erroring
    (odd embedding vocab on an even mesh is a real case)."""
    from zoo_tpu.parallel.mesh import pick_divisible_dim

    assert pick_divisible_dim((7, 5), 4) is None
    assert pick_divisible_dim((12, 8), 4) == 0       # largest divisible
    assert pick_divisible_dim((12, 8), 4, taken=(0,)) == 1
    assert pick_divisible_dim((12, 7), 4, taken=(0,)) is None
    assert pick_divisible_dim((), 4) is None
    s = fsdp_param_sharding(build_mesh(axis_sizes={"fsdp": 8}), (7, 5))
    assert s.spec == P()


def test_mesh_axes_from_env(monkeypatch):
    from zoo_tpu.parallel.mesh import mesh_axes_from_env

    monkeypatch.delenv("ZOO_MESH_DATA", raising=False)
    assert mesh_axes_from_env() is None
    monkeypatch.setenv("ZOO_MESH_FSDP", "4")
    monkeypatch.setenv("ZOO_MESH_DATA", "-1")
    assert mesh_axes_from_env() == {"data": -1, "fsdp": 4}
    mesh = build_mesh(axis_sizes=mesh_axes_from_env())
    assert mesh.shape["fsdp"] == 4 and mesh.shape["data"] == 2


def test_psum_over_mesh_collective():
    """Real allreduce over the virtual mesh via shard_map — the rebuild's
    equivalent of the reference's DistriEstimatorSpec on local[4]."""
    mesh = build_mesh()
    x = jnp.arange(8.0)

    f = jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8,), x.sum()))
