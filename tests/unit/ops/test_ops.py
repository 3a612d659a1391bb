"""Pallas ops: parity against the XLA/numpy reference implementations (interpret mode on
the CPU mesh; the same code runs as real kernels on TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu import ops
from nanofed_tpu.ops import (
    add_mask,
    dequantize_u32,
    quantize_u32,
    weighted_mean_flat,
    weighted_mean_tree,
)
from nanofed_tpu.security.secure_agg import dequantize as np_dequantize
from nanofed_tpu.security.secure_agg import quantize as np_quantize
from nanofed_tpu.utils.trees import tree_weighted_mean


class TestWeightedMean:
    def test_matches_tree_weighted_mean(self):
        rng = np.random.default_rng(0)
        c, p = 7, 1000  # P deliberately not a multiple of the tile
        x = jnp.asarray(rng.normal(size=(c, p)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.5, 2.0, size=(c,)), jnp.float32)
        got = weighted_mean_flat(x, w)
        want = (x * w[:, None]).sum(0) / w.sum()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_zero_weights_drop_clients(self):
        x = jnp.stack([jnp.full((600,), 1.0), jnp.full((600,), 5.0)])
        w = jnp.asarray([1.0, 0.0])
        np.testing.assert_allclose(np.asarray(weighted_mean_flat(x, w)), 1.0, rtol=1e-6)

    def test_tree_variant(self):
        rng = np.random.default_rng(1)
        c = 3
        stacked = {
            "a": jnp.asarray(rng.normal(size=(c, 5, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(c, 17)), jnp.float32),
        }
        w = jnp.asarray([1.0, 2.0, 3.0])
        got = weighted_mean_tree(stacked, w)
        want = tree_weighted_mean(stacked, w)
        for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5, atol=1e-6)


class TestQuantize:
    def test_roundtrip_and_numpy_parity(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(777,)).astype(np.float32) * 10
        q = quantize_u32(jnp.asarray(x), frac_bits=16)
        back = dequantize_u32(q, frac_bits=16)
        np.testing.assert_allclose(np.asarray(back), x, atol=2**-16)
        # Same encoding as the host path (int32 range): modular equality.
        np.testing.assert_array_equal(np.asarray(q), np_quantize(x, 16))
        np.testing.assert_allclose(np_dequantize(np.asarray(q), 16), x, atol=2**-16)

    def test_modular_sum_exact(self):
        a = quantize_u32(jnp.asarray([-1.5, 2.0]), frac_bits=16)
        b = quantize_u32(jnp.asarray([2.25, -3.0]), frac_bits=16)
        out = dequantize_u32(a + b, frac_bits=16)
        np.testing.assert_allclose(np.asarray(out), [0.75, -1.0], atol=2**-15)


class TestMask:
    def test_pairwise_cancellation(self):
        rng = np.random.default_rng(0)
        xa = rng.normal(size=(600,)).astype(np.float32)
        xb = rng.normal(size=(600,)).astype(np.float32)
        qa = quantize_u32(jnp.asarray(xa))
        qb = quantize_u32(jnp.asarray(xb))
        seed = jnp.int32(12345)
        ma = add_mask(qa, seed, jnp.int32(+1))
        mb = add_mask(qb, seed, jnp.int32(-1))
        total = dequantize_u32(ma + mb)
        np.testing.assert_allclose(np.asarray(total), xa + xb, atol=2**-14)

    def test_mask_hides_and_differs_by_seed(self):
        q = quantize_u32(jnp.asarray(np.ones(600, np.float32)))
        m1 = add_mask(q, jnp.int32(1), jnp.int32(1))
        m2 = add_mask(q, jnp.int32(2), jnp.int32(1))
        assert np.mean(np.asarray(m1) == np.asarray(q)) < 0.01
        assert np.mean(np.asarray(m1) == np.asarray(m2)) < 0.01




# Every other test here runs the kernels through the Pallas interpreter; this one takes
# each through the real Pallas -> Mosaic lowering for the TPU platform, which needs no
# TPU (the Mosaic compile itself does — chip_smoke.py covers that with interpret=False).
_X = jnp.zeros((40, 1300), jnp.float32)
_W = jnp.ones((40,), jnp.float32)
_TPU_LOWERINGS = {
    "quantize_u32": (ops.quantize_u32, (_X[0],)),
    "dequantize_u32": (ops.dequantize_u32, (jnp.zeros((1300,), jnp.uint32),)),
    "add_mask": (
        ops.add_mask,
        (jnp.zeros((1300,), jnp.uint32), jnp.arange(4, dtype=jnp.int32), jnp.int32(1)),
    ),
    "weighted_mean_flat": (ops.weighted_mean_flat, (_X, _W)),
    "masked_weighted_mean_flat": (ops.masked_weighted_mean_flat, (_X, _W, _W)),
    "dequant_accumulate_flat": (
        ops.dequant_accumulate_flat, (_X.astype(jnp.int8), _W, _W, _X[0]),
    ),
    "weighted_mean_tree": (ops.weighted_mean_tree, ({"w": _X.reshape(40, 13, 100)}, _W)),
    "causal_attention": (ops.causal_attention, (jnp.zeros((1, 2, 512, 64), jnp.bfloat16),) * 3),
}


@pytest.mark.parametrize("name", sorted(_TPU_LOWERINGS))
def test_kernel_lowers_for_tpu(name):
    fn, args = _TPU_LOWERINGS[name]
    lowered = jax.jit(functools.partial(fn, interpret=False)).trace(*args).lower(
        lowering_platforms=("tpu",)
    )
    assert "tpu_custom_call" in lowered.as_text()


def test_every_exported_kernel_has_a_tpu_lowering_case():
    assert set(_TPU_LOWERINGS) == set(ops.__all__)
