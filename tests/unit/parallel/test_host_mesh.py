"""The 3-axis ``hosts x clients x model`` mesh: construction rules, the
hierarchical client-axis collectives, the generalized :class:`MeshLayout`, and
— the acceptance bar — every round-program variant's parity against the 1-D
mesh on the virtual 8-device CPU grid (single-process virtual hosts; the REAL
2-process ``jax.distributed`` run is ``make multihost-smoke``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nanofed_tpu.core.types import ClientData
from nanofed_tpu.parallel import (
    CLIENT_AXIS,
    HOST_AXIS,
    MeshLayout,
    client_axes,
    client_shard_count,
    client_sharding,
    hierarchical_all_gather,
    hierarchical_pmean,
    hierarchical_psum,
    host_axis_size,
    host_client_slice,
    make_mesh,
    mesh_shape,
    mesh_shape_for_topology,
    pad_client_count,
    pad_clients,
    shard_client_data,
    shard_host_local_data,
)
from nanofed_tpu.parallel.mesh import shard_map


# ---------------------------------------------------------------------------
# construction + shape helpers
# ---------------------------------------------------------------------------


def test_make_mesh_3d_axes_and_sizes(devices):
    mesh = make_mesh(shape=(2, 2, 2))
    assert mesh.axis_names == (HOST_AXIS, CLIENT_AXIS, "model")
    assert mesh_shape(mesh) == (2, 2, 2)
    assert host_axis_size(mesh) == 2
    assert client_shard_count(mesh) == 4  # hosts x clients jointly
    assert client_axes(mesh) == (HOST_AXIS, CLIENT_AXIS)


def test_make_mesh_3d_rejects_bad_products(devices):
    with pytest.raises(ValueError, match="needs 12 devices"):
        make_mesh(shape=(3, 2, 2))
    with pytest.raises(ValueError, match="positive"):
        make_mesh(shape=(0, 4, 2))
    with pytest.raises(ValueError, match="hosts, clients, model"):
        make_mesh(shape=(2, 2, 2, 1))


def test_mesh_shape_for_topology_rules():
    # hosts == 1 delegates to the 2-axis validator (None for the 1-D layout).
    assert mesh_shape_for_topology(1, 1, 8) is None
    assert mesh_shape_for_topology(1, 2, 8) == (4, 2)
    assert mesh_shape_for_topology(2, 1, 8) == (2, 4, 1)
    assert mesh_shape_for_topology(2, 2, 8) == (2, 2, 2)
    with pytest.raises(ValueError, match="does not divide"):
        mesh_shape_for_topology(3, 1, 8)
    with pytest.raises(ValueError, match="hosts must be"):
        mesh_shape_for_topology(0, 1, 8)


def test_client_sharding_is_joint_on_hosts_mesh(devices):
    mesh = make_mesh(shape=(2, 2, 2))
    spec = client_sharding(mesh).spec
    assert tuple(spec) == ((HOST_AXIS, CLIENT_AXIS),)
    # 1-D/2-D meshes keep the classic single-axis spec.
    assert tuple(client_sharding(make_mesh()).spec) == (CLIENT_AXIS,)


def test_host_client_slice_single_process_covers_everything(devices):
    mesh = make_mesh(shape=(2, 2, 2))
    assert host_client_slice(16, mesh) == (0, 16)


def test_shard_host_local_data_matches_global(devices):
    mesh = make_mesh(shape=(2, 4, 1))
    rng = np.random.default_rng(0)
    data = ClientData(
        x=rng.normal(size=(8, 4, 2)).astype(np.float32),
        y=rng.integers(0, 2, size=(8, 4)).astype(np.int32),
        mask=np.ones((8, 4), np.float32),
    )
    start, stop = host_client_slice(8, mesh)
    local = jax.tree.map(lambda a: a[start:stop], data)
    via_local = shard_host_local_data(local, mesh, 8)
    via_global = shard_client_data(data, mesh)
    np.testing.assert_array_equal(
        np.asarray(via_local.x), np.asarray(via_global.x)
    )
    assert via_local.x.sharding.spec == via_global.x.sharding.spec


# ---------------------------------------------------------------------------
# hierarchical collectives == flat collectives
# ---------------------------------------------------------------------------


def test_hierarchical_psum_matches_flat(devices):
    mesh = make_mesh(shape=(2, 4, 1))
    x = jnp.arange(8.0)

    def hier(v):
        return hierarchical_psum(v.sum(), (HOST_AXIS, CLIENT_AXIS))

    def flat(v):
        from jax import lax

        return lax.psum(v.sum(), (HOST_AXIS, CLIENT_AXIS))

    kw = dict(mesh=mesh, in_specs=P((HOST_AXIS, CLIENT_AXIS)), out_specs=P())
    flag = {"check_vma": False}
    got_h = jax.jit(shard_map(hier, **kw, **flag))(x)
    got_f = jax.jit(shard_map(flat, **kw, **flag))(x)
    assert float(got_h) == pytest.approx(float(got_f))
    assert float(got_h) == pytest.approx(28.0)


def test_hierarchical_helpers_single_axis_degenerate(devices):
    mesh = make_mesh()

    def body(v):
        s = hierarchical_psum(v.sum(), CLIENT_AXIS)
        m = hierarchical_pmean(v.sum(), CLIENT_AXIS)
        g = hierarchical_all_gather(v, CLIENT_AXIS)
        return s, m, g

    flag = {"check_vma": False}
    s, m, g = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P(CLIENT_AXIS),
                  out_specs=(P(), P(), P(CLIENT_AXIS)), **flag)
    )(jnp.arange(8.0))
    assert float(s) == 28.0
    assert float(m) == 28.0 / 8
    assert g.shape == (8 * 8,)


def test_hierarchical_all_gather_collects_every_row(devices):
    mesh = make_mesh(shape=(2, 4, 1))

    def body(v):
        return hierarchical_all_gather(v, (HOST_AXIS, CLIENT_AXIS))

    flag = {"check_vma": False}
    out = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P((HOST_AXIS, CLIENT_AXIS)),
                  out_specs=P((HOST_AXIS, CLIENT_AXIS)), **flag)
    )(jnp.arange(8.0))
    # Every device gathered all 8 values (order may interleave host blocks —
    # consumers are permutation-invariant); the tiled output stacks 8 copies.
    assert out.shape == (64,)
    assert sorted(np.asarray(out)[:8].tolist()) == sorted(
        set(np.asarray(out).tolist())
    )


# ---------------------------------------------------------------------------
# MeshLayout generalization
# ---------------------------------------------------------------------------


def test_mesh_layout_client_axes(devices):
    assert MeshLayout(make_mesh()).client_axes == CLIENT_AXIS
    assert MeshLayout(make_mesh(shape=(4, 2))).client_axes == CLIENT_AXIS
    layout = MeshLayout(make_mesh(shape=(2, 2, 2)))
    assert layout.client_axes == (HOST_AXIS, CLIENT_AXIS)
    assert layout.n_hosts == 2
    assert layout.n_model_shards == 2
    assert tuple(layout.data_spec) == ((HOST_AXIS, CLIENT_AXIS),)
    assert layout.multi_axis and layout.raw_keys_at_boundary


def test_model_axis_layout_alias_still_importable():
    from nanofed_tpu.parallel import ModelAxisLayout

    assert ModelAxisLayout is MeshLayout


# ---------------------------------------------------------------------------
# round-program parity: 3-axis hierarchical == 1-D flat (float tolerance)
# ---------------------------------------------------------------------------


def _population(num_clients=16, cap=8):
    rng = np.random.default_rng(3)
    y = rng.integers(0, 10, size=(num_clients, cap)).astype(np.int32)
    x = rng.normal(size=(num_clients, cap, 8, 8, 1)).astype(np.float32)
    return ClientData(x=x, y=y, mask=np.ones((num_clients, cap), np.float32))


def _setup(shape, data, model, strategy):
    from nanofed_tpu.parallel import init_server_state, param_sharding

    mesh = make_mesh(shape=shape)
    padded = pad_client_count(data.x.shape[0], client_shard_count(mesh))
    d = pad_clients(data, padded)
    num_samples = jnp.asarray(np.asarray(d.mask).sum(axis=1), jnp.float32)
    d = shard_client_data(d, mesh)
    ph = model.init(jax.random.key(0))
    params = jax.device_put(ph, param_sharding(mesh, ph))
    sos_h = init_server_state(strategy, ph)
    sos = jax.device_put(sos_h, param_sharding(mesh, sos_h))
    return mesh, padded, d, num_samples, params, sos, ph


def _flat(tree):
    return np.concatenate([
        np.asarray(jax.device_get(x)).ravel() for x in jax.tree.leaves(tree)
    ])


@pytest.mark.parametrize("shape", [(2, 2, 2), (2, 4, 1)])
def test_round_step_parity_3d_vs_1d(devices, shape):
    from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
    from nanofed_tpu.models import get_model
    from nanofed_tpu.parallel import build_round_step
    from nanofed_tpu.trainer import TrainingConfig
    from nanofed_tpu.trainer.local import stack_rngs

    model = get_model("digits_mlp")
    training = TrainingConfig(batch_size=8, local_epochs=1, learning_rate=0.1)
    strategy = fedavg_strategy()
    data = _population()
    outs = {}
    for tag, s in (("1d", None), ("3d", shape)):
        mesh, padded, d, ns, params, sos, _ = _setup(s, data, model, strategy)
        step = build_round_step(
            model.apply, training, mesh, strategy, params_like=params
        )
        weights = compute_weights(ns)
        rngs = stack_rngs(jax.random.key(7), padded)
        for _ in range(2):
            res = step(params, sos, d, weights, rngs)
            params, sos = res.params, res.server_opt_state
        outs[tag] = (_flat(params), float(res.metrics["loss"]))
    np.testing.assert_allclose(outs["1d"][0], outs["3d"][0], atol=5e-6)
    assert outs["1d"][1] == pytest.approx(outs["3d"][1], abs=1e-5)


@pytest.mark.slow  # ~22s of compiles; the tier-1 870s budget has no headroom.
# Tier-1 keeps the fused-block 3-D parity (test_3d_fused_round_block_matches_
# single_rounds) and step parity (test_round_step_parity_3d_vs_1d); the
# variants additionally run on the mesh in dryrun_multichip and CI's
# multihost-smoke exercises the real 2-process program.
def test_round_block_and_variants_parity_3d(devices):
    """Fused block, validated, robust, SCAFFOLD, and chunked-streaming paths
    all match the 1-D program on the (2, 2, 2) mesh — the hierarchical reduce
    is a re-association of the same sum, never different math."""
    from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
    from nanofed_tpu.aggregation.robust import RobustAggregationConfig
    from nanofed_tpu.models import get_model
    from nanofed_tpu.parallel import (
        build_round_block,
        build_round_step,
        build_scaffold_round_step,
        stack_round_keys,
    )
    from nanofed_tpu.security.validation import ValidationConfig
    from nanofed_tpu.trainer import TrainingConfig
    from nanofed_tpu.trainer.local import stack_rngs
    from nanofed_tpu.trainer.scaffold import stack_zero_controls, zero_controls
    from nanofed_tpu.parallel import param_sharding

    model = get_model("digits_mlp")
    training = TrainingConfig(batch_size=8, local_epochs=1, learning_rate=0.1)
    strategy = fedavg_strategy()
    data = _population()
    out = {}
    for tag, shape in (("1d", None), ("3d", (2, 2, 2))):
        mesh, padded, d, ns, params, sos, ph = _setup(
            shape, data, model, strategy
        )
        weights = compute_weights(ns)
        rngs = stack_rngs(jax.random.key(7), padded)

        block = build_round_block(
            model.apply, training, mesh, strategy, num_clients=16,
            padded_clients=padded, params_like=params,
            collect_client_detail=False,
        )
        mask = jnp.asarray(np.tile(np.asarray(ns > 0, np.float32), (3, 1)))
        res = block(params, sos, d, ns, stack_round_keys(0, [0, 1, 2]),
                    jnp.ones(3), cohort_mask=mask)
        out[tag, "block"] = _flat(res.params)

        for kind, kwargs in (
            ("validated", dict(validation=ValidationConfig(max_norm=100.0))),
            ("robust", dict(robust=RobustAggregationConfig(trim_k=1))),
            ("chunked", dict(client_chunk=1)),
        ):
            step = build_round_step(
                model.apply, training, mesh, strategy, params_like=params,
                **kwargs,
            )
            res = step(params, sos, d, weights, rngs)
            out[tag, kind] = _flat(res.params)

        sstep = build_scaffold_round_step(
            model.apply, training, mesh, 16, strategy=strategy,
            params_like=params,
        )
        cg = jax.device_put(zero_controls(ph), param_sharding(mesh, ph))
        cs = jax.device_put(
            stack_zero_controls(ph, padded), client_sharding(mesh)
        )
        res = sstep(params, sos, cg, cs, d, weights, rngs)
        out[tag, "scaffold"] = _flat(res.params)

    for kind in ("block", "validated", "robust", "chunked", "scaffold"):
        np.testing.assert_allclose(
            out["1d", kind], out["3d", kind], atol=5e-6, err_msg=kind
        )
