"""Mesh construction + sharding-helper edge cases: client padding below the
device count, the 2-D ``clients x model`` mesh layouts, and the per-leaf
FSDP fallback rules of ``param_partition_spec`` / ``param_sharding``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nanofed_tpu.core.types import ClientData
from nanofed_tpu.parallel import (
    CLIENT_AXIS,
    MODEL_AXIS,
    client_axis_size,
    make_mesh,
    mesh_shape,
    model_axis_size,
    pad_client_count,
    pad_clients,
    param_partition_spec,
    param_sharding,
    shard_client_data,
    shard_params,
)


def _client_data(c=3, n=4, feat=2):
    rng = np.random.default_rng(0)
    return ClientData(
        x=rng.normal(size=(c, n, feat)).astype(np.float32),
        y=rng.integers(0, 2, size=(c, n)).astype(np.int32),
        mask=np.ones((c, n), np.float32),
    )


# ---------------------------------------------------------------------------
# pad_client_count / pad_clients with num_clients < n_devices
# ---------------------------------------------------------------------------


def test_pad_client_count_below_device_count():
    """Fewer clients than shards pads UP to one client per shard, never down."""
    assert pad_client_count(3, 8) == 8
    assert pad_client_count(1, 8) == 8
    assert pad_client_count(8, 8) == 8
    assert pad_client_count(9, 8) == 16


def test_pad_clients_below_device_count_zero_masks_dummies(devices):
    data = _client_data(c=3)
    padded = pad_clients(data, 8)
    assert padded.x.shape[0] == 8
    # Real clients' rows are untouched; dummies carry zero mask (=> zero weight).
    np.testing.assert_array_equal(np.asarray(padded.x[:3]), data.x)
    np.testing.assert_array_equal(np.asarray(padded.mask[3:]), 0.0)


def test_pad_clients_refuses_to_truncate():
    with pytest.raises(ValueError, match="cannot pad"):
        pad_clients(_client_data(c=5), 3)


# ---------------------------------------------------------------------------
# make_mesh shapes
# ---------------------------------------------------------------------------


def test_make_mesh_1d_default(devices):
    mesh = make_mesh()
    assert mesh.axis_names == (CLIENT_AXIS,)
    assert mesh_shape(mesh) == (8,)
    assert client_axis_size(mesh) == 8
    assert model_axis_size(mesh) == 1


def test_make_mesh_2d_shapes(devices):
    for shape in [(4, 2), (2, 4), (8, 1), (1, 8)]:
        mesh = make_mesh(shape=shape)
        assert mesh.axis_names == (CLIENT_AXIS, MODEL_AXIS)
        assert mesh_shape(mesh) == shape
        assert client_axis_size(mesh) == shape[0]
        assert model_axis_size(mesh) == shape[1]


def test_make_mesh_2d_rejects_bad_shapes(devices):
    with pytest.raises(ValueError, match="needs 6 devices"):
        make_mesh(shape=(3, 2))
    with pytest.raises(ValueError, match="positive"):
        make_mesh(shape=(0, 8))


# ---------------------------------------------------------------------------
# param_partition_spec fallback rules
# ---------------------------------------------------------------------------


def test_param_partition_spec_picks_largest_divisible_dim():
    assert param_partition_spec((8, 16), 2) == P(None, MODEL_AXIS)
    assert param_partition_spec((16, 4), 2) == P(MODEL_AXIS)
    # Tie on size: the first largest dim wins.
    assert param_partition_spec((16, 16), 2) == P(MODEL_AXIS)


def test_param_partition_spec_non_divisible_falls_back_to_replication():
    # No dim divisible by 4 -> replicate the whole leaf.
    assert param_partition_spec((3, 7), 4) == P()
    # Scalars and empty shapes replicate.
    assert param_partition_spec((), 4) == P()
    # One divisible dim among non-divisible ones is still sharded.
    assert param_partition_spec((3, 8, 5), 4) == P(None, MODEL_AXIS)


def test_param_partition_spec_single_shard_replicates():
    assert param_partition_spec((8, 16), 1) == P()


def test_param_partition_spec_never_shards_stacked_layer_dim():
    """Rank>=3 leaves are scan-stacked layer params [L, ...]: the leading dim
    indexes layers, so sharding it across the model axis would split the scan
    carry — dim 0 must never be chosen even when it is the largest divisible
    dim."""
    # L=8 divisible and largest: still skipped, largest remaining dim wins.
    assert param_partition_spec((8, 4, 6), 2) == P(None, None, MODEL_AXIS)
    # Only dim 0 divisible -> replicate rather than split the stack.
    assert param_partition_spec((8, 3, 5), 2) == P()
    # Rank-2 leaves keep the old behavior (dim 0 eligible).
    assert param_partition_spec((8, 5), 2) == P(MODEL_AXIS)
    # Stacked conv-style rank-4 leaves also skip dim 0.
    assert param_partition_spec((4, 3, 8, 5), 4) == P(None, None, MODEL_AXIS)


@pytest.mark.parametrize("shape,shards,want", [
    # [layers, experts, d, f]: neither stacking dim, though both divide and the
    # expert axis is the largest divisible dim of the second.
    ((4, 8, 2688, 1856), 4, P(None, None, MODEL_AXIS)),
    ((4, 8, 6, 5), 4, P()),
    ((4, 8, 6, 8), 2, P(None, None, None, MODEL_AXIS)),
    # [layers, d, f] keeps the rank-3 rule.
    ((4, 2688, 10304), 4, P(None, None, MODEL_AXIS)),
])
def test_param_partition_spec_stacked_expert_leaves(shape, shards, want):
    """An expert layer's stacked kernels get a spec that shards neither the layer nor
    the expert axis, and the rule does not throw at rank 4."""
    assert param_partition_spec(shape, shards) == want


def test_param_sharding_mixed_tree(devices):
    mesh = make_mesh(shape=(2, 4))
    tree = {"kernel": jnp.zeros((8, 16)), "odd_bias": jnp.zeros((3,)), "s": jnp.zeros(())}
    shardings = param_sharding(mesh, tree)
    assert shardings["kernel"].spec == P(None, MODEL_AXIS)
    # 3 % 4 != 0 -> per-leaf replication fallback; scalar likewise.
    assert shardings["odd_bias"].is_fully_replicated
    assert shardings["s"].is_fully_replicated
    placed = shard_params(tree, mesh)
    assert placed["kernel"].sharding.spec == P(None, MODEL_AXIS)
    assert placed["odd_bias"].sharding.is_fully_replicated


def test_param_sharding_1d_mesh_is_replicated(devices):
    mesh = make_mesh()
    shardings = param_sharding(mesh, {"k": jnp.zeros((8, 16))})
    assert shardings["k"].is_fully_replicated


# ---------------------------------------------------------------------------
# 2-D shard_client_data layouts
# ---------------------------------------------------------------------------


def test_shard_client_data_2d_layout(devices):
    """Client data on a 2-D mesh: leading axis over clients, replicated over
    model — each model column holds its clients whole."""
    mesh = make_mesh(shape=(4, 2))
    data = shard_client_data(pad_clients(_client_data(c=3), 4), mesh)
    for leaf in jax.tree.leaves(data):
        spec = leaf.sharding.spec
        assert spec[0] == CLIENT_AXIS
        assert all(e is None for e in tuple(spec)[1:])
        # 4 client shards x 2 model columns: every device holds a quarter of
        # the clients, so each leaf has 8 addressable shards of 1 client each.
        assert len(leaf.sharding.device_set) == 8
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert shard_rows == {1}


def test_shard_client_data_1d_unchanged(devices):
    mesh = make_mesh()
    data = shard_client_data(pad_clients(_client_data(c=3), 8), mesh)
    for leaf in jax.tree.leaves(data):
        assert leaf.sharding.spec[0] == CLIENT_AXIS
