"""``ops.attention``: the blockwise causal-attention kernels (Pallas's interpreter on the
CPU mesh; the same code compiles for the TPU, ``test_attention_aot.py``) against the dense
spelling computed in float32; and what a ``jax.checkpoint`` around them keeps under
``KEEP_KERNEL_OUTPUTS``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nanofed_tpu.models import get_model
from nanofed_tpu.ops import attention
from nanofed_tpu.ops.attention import (
    BLOCKS,
    KEEP_KERNEL_OUTPUTS,
    MAX_SEQ,
    MIN_SEQ,
    block_for,
    causal_attention,
    dense_causal_attention,
    engages,
)
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.trainer.local import make_grad_fn

SHAPES = [(2, 3, 512, 64), (1, 2, 1024, 64), (1, 2, 768, 32)]
DTYPES = [jnp.float32, jnp.bfloat16]
#: Error allowed, as a share of the largest reference value: float32 rounding of a few
#: hundred terms; bfloat16's 2**-8 on the probabilities and on dS.
REL = {jnp.float32: 2e-6, jnp.bfloat16: 1.5e-2}


def _inputs(shape, dtype, seed=0):
    """``q, k, v`` and a cotangent, float32 values already rounded to ``dtype`` so that
    the reference sees the same numbers."""
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype) for k in keys]


def _f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


def _close(got, want, dtype, what):
    want = np.asarray(want)
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max()
    assert err <= REL[dtype] * np.abs(want).max(), f"{what}: {err} of {np.abs(want).max()}"


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_output_matches_dense(shape, dtype):
    q, k, v, _ = _inputs(shape, dtype)
    got = jax.jit(causal_attention)(q, k, v)
    assert got.shape == shape and got.dtype == dtype
    _close(got, dense_causal_attention(*_f32(q, k, v)), dtype, "output")


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_gradients_match_dense(shape, dtype):
    q, k, v, w = _inputs(shape, dtype)
    loss = lambda fn, w: lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()
    got = jax.jit(jax.grad(loss(causal_attention, w.astype(jnp.float32)), (0, 1, 2)))(q, k, v)
    want = jax.grad(loss(dense_causal_attention, w.astype(jnp.float32)), (0, 1, 2))(
        *_f32(q, k, v))
    for g, r, name in zip(got, want, "qkv"):
        assert g.dtype == dtype
        _close(g, r, dtype, f"d{name}")


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_under_vmap_over_a_chunk_of_one(dtype):
    """The round program's ``vmap`` over a chunk of one client: a batch dimension through
    ``pallas_call``'s batching rule, forward and backward."""
    q, k, v, w = _inputs((1, 2, 2, 512, 64), dtype)
    fn = lambda q, k, v, w: jax.grad(
        lambda q: (causal_attention(q, k, v).astype(jnp.float32) * w).sum())(q)
    got = jax.jit(jax.vmap(fn))(q, k, v, w.astype(jnp.float32))
    ref = lambda q, k, v, w: jax.grad(lambda q: (dense_causal_attention(q, k, v) * w).sum())(q)
    _close(got[0], ref(*_f32(q[0], k[0], v[0], w[0])), dtype, "dq under vmap")


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_inside_scan_with_grad_through_it(dtype):
    """The nesting the scanned model has: ``grad`` of a ``lax.scan`` over layers whose
    body attends; the scan stacks the saved ``q, k, v``, output and log-sum-exp."""
    x, _, _, w = _inputs((1, 2, 512, 64), dtype)
    mix = (jax.random.normal(jax.random.key(7), (3, 64, 64), jnp.float32) / 8).astype(dtype)

    def through(attend, x, mix, w):
        def layer(h, m):
            return h + attend(h @ m, h, h @ m.T), None
        return (jax.lax.scan(layer, x, mix)[0].astype(jnp.float32) * w).sum()

    got = jax.jit(jax.grad(lambda x, mix: through(causal_attention, x, mix, w), (0, 1)))(x, mix)
    want = jax.grad(
        lambda x, mix: through(dense_causal_attention, x, mix, w.astype(jnp.float32)), (0, 1)
    )(*_f32(x, mix))
    # Three layers compound the rounding: three times the single-call allowance.
    for g, r, name in zip(got, want, ("x", "mix")):
        err = np.abs(np.asarray(g.astype(jnp.float32)) - np.asarray(r)).max()
        assert err <= 3 * REL[dtype] * np.abs(np.asarray(r)).max(), (name, err)


@pytest.mark.parametrize("at", [1, 255, 256, 700])
def test_causality(at):
    """A change to token ``at`` leaves every output before ``at`` as it was, bit for
    bit, and moves the output at ``at``."""
    q, k, v, _ = _inputs((1, 2, 1024, 64), jnp.float32, seed=3)
    bump = lambda a: a.at[:, :, at].add(1.0)
    fn = jax.jit(causal_attention)
    before, after = fn(q, k, v), fn(bump(q), bump(k), bump(v))
    np.testing.assert_array_equal(np.asarray(before[:, :, :at]), np.asarray(after[:, :, :at]))
    assert np.abs(np.asarray(before[:, :, at] - after[:, :, at])).max() > 1e-3


@pytest.mark.parametrize("seq_len,block", [
    (8, None), (256, None), (384, None), (MIN_SEQ - BLOCKS[-1], None), (MIN_SEQ, 512),
    (MIN_SEQ + 1, None), (MIN_SEQ + BLOCKS[-1], 256), (1024, 512), (2048, 512), (1000, None),
    (8192, 512), (16384, None),
])
def test_engages_reads_the_sequence_length(seq_len, block):
    """Whole blocks and at least ``MIN_SEQ`` positions; the largest block that fits."""
    assert engages(seq_len) is (block is not None)
    if block is not None:
        assert block_for(seq_len) == block
    assert MIN_SEQ <= 512 and MAX_SEQ == 8192


@pytest.mark.parametrize("block", [128, 256, 512])
def test_every_block_size_gives_the_same_function(block):
    q, k, v, w = _inputs((1, 2, 1024, 64), jnp.float32, seed=5)
    fn = lambda q, k, v: causal_attention(q, k, v, block=block)
    _close(jax.jit(fn)(q, k, v), dense_causal_attention(q, k, v), jnp.float32, "output")
    got = jax.jit(jax.grad(lambda q, k, v: (fn(q, k, v) * w).sum(), (0, 1, 2)))(q, k, v)
    want = jax.grad(lambda q, k, v: (dense_causal_attention(q, k, v) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        _close(g, r, jnp.float32, f"d{name}")


@pytest.mark.parametrize("bad", ["ragged", "shapes", "block"])
def test_refuses_what_it_cannot_tile(bad):
    q, k, v, _ = _inputs((1, 1, 512, 64), jnp.float32)
    with pytest.raises(ValueError):
        if bad == "ragged":
            causal_attention(q[:, :, :500], k[:, :, :500], v[:, :, :500])
        elif bad == "shapes":
            causal_attention(q, k[:, :, :256], v)
        else:
            causal_attention(q, k, v, block=192)


def test_interpreter_under_shard_map_answers_densely(devices):
    """Off the TPU, on values that vary over a ``shard_map`` axis with the varying-axes
    check on, the interpreter cannot run the kernel; the call still answers, with the
    dense spelling's values."""
    mesh = make_mesh(devices=devices[:2])
    q, k, v, _ = _inputs((2, 2, 512, 64), jnp.float32)
    spec = P(mesh.axis_names[0])
    got = jax.jit(jax.shard_map(causal_attention, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense_causal_attention(q, k, v)), atol=2e-6)


#: ``(heads, key/value heads, score head size, value head size, window)``: what the three
#: language models on the kernels ask of them.
LAYERS = {"full": (2, 2, 64, 64, None), "grouped": (4, 2, 64, 64, None),
          "windowed": (2, 2, 64, 64, 200), "grouped-windowed": (4, 1, 32, 32, 300),
          "24-over-16": (2, 2, 24, 16, None)}  # latent attention's 192 over 128, small


def _layer_inputs(kind, dtype):
    heads, kv_heads, hd, hd_v, _ = LAYERS[kind]
    shapes = [(1, heads, 512, hd), (1, kv_heads, 512, hd), (1, kv_heads, 512, hd_v),
              (1, heads, 512, hd_v)]
    keys = jax.random.split(jax.random.key(11), 4)
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _rematerialized(kind, policy, w):
    """Value and the three gradients of a rematerialized layer: the projections stand in
    for what a decoder layer recomputes around the call."""
    def layer(q, k, v):
        return causal_attention(1.5 * q, k + k, -v, window=LAYERS[kind][4])

    def loss(q, k, v):
        out = jax.checkpoint(layer, policy=policy)(q, k, v)
        return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

    return jax.value_and_grad(loss, (0, 1, 2))


@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_checkpoint_with_the_policy_does_not_launch_the_forward_kernel_again(kind, kernel_calls):
    """Partial evaluation keeps the two named outputs, nothing in the recomputation reads
    the ``pallas_call`` any more, and it goes; a plain checkpoint launches it twice."""
    q, k, v, w = _layer_inputs(kind, jnp.float32)
    end = "_window" if LAYERS[kind][4] else ""
    fwd, bwd = "causal_attention_fwd" + end, "causal_attention_bwd" + end
    assert kernel_calls(_rematerialized(kind, KEEP_KERNEL_OUTPUTS, w), q, k, v) == {fwd: 1, bwd: 1}
    assert kernel_calls(_rematerialized(kind, None, w), q, k, v) == {fwd: 2, bwd: 1}


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("kind", list(LAYERS))
def test_gradients_under_the_policy_are_the_plain_checkpoints_bit_for_bit(kind, dtype):
    """The backward kernel reads the same arrays, kept instead of computed again."""
    q, k, v, w = _layer_inputs(kind, dtype)
    kept = jax.jit(_rematerialized(kind, KEEP_KERNEL_OUTPUTS, w))(q, k, v)
    plain = jax.jit(_rematerialized(kind, None, w))(q, k, v)
    assert float(jnp.abs(plain[1][0].astype(jnp.float32)).max()) > 0
    jax.tree.map(np.testing.assert_array_equal, kept, plain)


def test_the_policy_keeps_only_what_carries_a_name(capsys):
    """Two residuals beside the checkpoint's inputs: the output and the log-sum-exp, not
    ``q``, ``k``, ``v`` as the kernel was handed them; a plain checkpoint keeps none."""
    q, k, v, _ = _layer_inputs("grouped", jnp.bfloat16)
    layer = lambda q, k, v: causal_attention(1.5 * q, k + k, -v)

    def kept(policy):
        jax.ad_checkpoint.print_saved_residuals(jax.checkpoint(layer, policy=policy), q, k, v)
        lines = capsys.readouterr().out.strip().splitlines()
        assert all("from the argument" in line for line in lines[:3])
        return [line.split()[0] for line in lines[3:]]

    assert kept(KEEP_KERNEL_OUTPUTS) == ["bf16[4,512,64]", "f32[4,1,1,512]"]
    assert kept(None) == []


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"], ids=["float32", "bfloat16"])
def test_outside_a_checkpoint_the_names_change_nothing(compute_dtype, monkeypatch, kernel_calls):
    """GPT-2's scanned stack calls the kernels with no layer checkpoint around them: its
    training step lowers to the same StableHLO with the names and without, operation for
    operation.  (A name is an equation of the jaxpr and lowers to nothing, but the lowering
    numbers the private functions that come after it one further: ``@_where_87`` for
    ``@_where_86``.  That number is all that differs.)"""
    m = get_model("transformer_lm_scan", vocab=64, seq_len=512, width=64, depth=2, heads=2)
    params = jax.eval_shape(m.init, jax.random.key(0))
    batch = (jax.ShapeDtypeStruct((2, 512), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32),
             jax.ShapeDtypeStruct((2,), jnp.float32))

    def step():  # a new function each time: a trace is cached by the function traced
        grad_fn = make_grad_fn(m.apply, compute_dtype=compute_dtype)
        return lambda p, x, y, mask: grad_fn(p, x, y, mask, jax.random.key(0))[0]

    def lowered():
        text = jax.jit(step()).lower(params, *batch).as_text()
        return re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1", text)

    named, names = lowered(), str(jax.make_jaxpr(step())(params, *batch)).count("name[name=")
    assert kernel_calls(step(), params, *batch) == {"causal_attention_fwd": 1,
                                                    "causal_attention_bwd": 1}  # the scan's body
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    assert names == 2 and "name[name=" not in str(jax.make_jaxpr(step())(params, *batch))
    assert lowered() == named
