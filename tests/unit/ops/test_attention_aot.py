"""The attention kernels compiled ahead of time for a described ``v5e:2x2`` chip, at the
GPT-2 cell's shapes: what the TPU's compiler refuses (a tiling, a VMEM budget, a
transpose it cannot place) fails here, at no chip time.  Nothing runs: this says nothing
about values or times.  All such compiles live in this one file (one worker loads the
TPU's library, inside the fixture)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nanofed_tpu.ops.attention import causal_attention

CELL = (4, 12, 1024, 64)  # gpt2-124m-xsilo-8: batch 4, 12 heads, 1024 positions of 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attend(q, k, v):
    return causal_attention(q, k, v, interpret=False)


def _loss(q, k, v):
    return _attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("shape,dtype,kernels", [
    (CELL, jnp.bfloat16, 1),
    ((2, 4, 2048, 128), jnp.bfloat16, 1),
    ((1, 2, 512, 64), jnp.float32, 1),
], ids=["gpt2-cell", "T2048-hd128", "float32"])
def test_forward_kernel_compiles(one_chip, shape, dtype, kernels):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(_attend).lower(x, x, x).compile().as_text()
    assert text.count("causal_attention_fwd") >= kernels
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype", [
    (CELL, jnp.bfloat16),
    ((2, 4, 2048, 128), jnp.bfloat16),
    ((1, 2, 512, 64), jnp.float32),
], ids=["gpt2-cell", "T2048-hd128", "float32"])
def test_backward_kernel_compiles(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(jax.grad(_loss, (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert "causal_attention_fwd" in text and "causal_attention_bwd" in text


def test_compiles_under_vmap_and_scan(one_chip):
    """The round program's nesting around the call: ``vmap`` over a chunk of one client,
    ``grad`` of a ``scan`` over layers."""
    def through(x):
        layer = lambda h, _: (h + _attend(h, h, h), None)
        return jax.lax.scan(layer, x, None, length=3)[0].astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, *CELL), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.vmap(jax.grad(through))).lower(x).compile().as_text()
    assert "causal_attention_bwd" in text
