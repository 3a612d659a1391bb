"""``ops.attention`` with score heads and value heads of different sizes (latent
attention: 192-wide ``q``/``k`` over 128-wide ``v``): the kernels (in Pallas's
interpreter) against the dense spelling in float32, forward and all three gradients.
Tolerances as in ``test_attention.py`` and for the same reasons — float32 rounding of a
few hundred terms; bfloat16's 2**-8 on the probabilities and on ``dS`` — the value width
changes which products are how wide, not how many terms a sum has."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention

REL = {jnp.float32: 4e-6, jnp.bfloat16: 1.5e-2}
BLOCK = 256


def _inputs(heads, kv_heads, hd, hd_v, dtype, t=512, seed=0):
    """``q, k, v`` and a weight on the output, all float32 draws cast to ``dtype``."""
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = [(1, heads, t, hd), (1, kv_heads, t, hd), (1, kv_heads, t, hd_v), (1, heads, t, hd_v)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _close(got, want, dtype, what):
    want = np.asarray(want)
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max()
    scale = max(np.abs(want).max(), 1.0)
    assert err <= REL[dtype] * scale, f"{what}: {err} of {scale}"


def _both(q, k, v, w, dtype, **options):
    """Output and the three gradients, kernels against the dense oracle in float32."""
    f32 = lambda *arrays: [a.astype(jnp.float32) for a in arrays]
    loss = lambda fn: lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()
    kernels = lambda q, k, v: causal_attention(q, k, v, block=BLOCK, **options)
    dense = lambda q, k, v: dense_causal_attention(q, k, v, **options)
    got = jax.jit(jax.value_and_grad(lambda q, k, v: (loss(kernels)(q, k, v), kernels(q, k, v)),
                                     (0, 1, 2), has_aux=True))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: (loss(dense)(q, k, v), dense(q, k, v)),
                              (0, 1, 2), has_aux=True)(*f32(q, k, v))
    assert got[0][1].shape == (*q.shape[:3], v.shape[-1])
    _close(got[0][1], want[0][1], dtype, "output")
    for g, r, like, name in zip(got[1], want[1], (q, k, v), "qkv"):
        assert g.shape == like.shape and g.dtype == dtype
        _close(g, r, dtype, f"d{name}")


@pytest.mark.parametrize("hd,hd_v,dtype", [
    (24, 16, jnp.float32), (24, 16, jnp.bfloat16), (16, 24, jnp.float32), (16, 24, jnp.bfloat16),
    (192, 128, jnp.float32),  # the published widths, once
], ids=["24-over-16-f32", "24-over-16-bf16", "16-over-24-f32", "16-over-24-bf16", "192-over-128-f32"])
def test_value_heads_of_another_size_match_the_dense_spelling(hd, hd_v, dtype):
    _both(*_inputs(2, 2, hd, hd_v, dtype), dtype)


@pytest.mark.parametrize("options,kv_heads", [({"window": 300}, 3), ({}, 1), ({"window": 200}, 1)],
                         ids=["window", "one-group-of-3", "group-and-window"])
def test_windows_and_groups_keep_working_with_it(options, kv_heads):
    _both(*_inputs(3, kv_heads, 24, 16, jnp.float32), jnp.float32, **options)


def test_the_scale_is_the_score_heads_size():
    """``1/sqrt(hd)`` of ``q``'s and ``k``'s width, whatever ``v``'s: against a softmax
    written out with the scale spelled."""
    q, k, v, _ = _inputs(1, 1, 24, 16, jnp.float32)
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / 24 ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((512, 512), bool)), scores, -jnp.inf)
    want = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(scores, axis=-1), v)
    np.testing.assert_allclose(causal_attention(q, k, v, block=BLOCK), want, atol=2e-6)
    np.testing.assert_allclose(dense_causal_attention(q, k, v), want, atol=2e-6)


def test_equal_sizes_trace_to_the_program_they_were():
    """Static branches only: with ``v`` as wide as ``k`` the backward kernel starts its two
    accumulators from ONE zeros constant, as it did before values had a width of their
    own; a second is traced only when the widths differ."""
    grad = lambda q, k, v: jax.grad(
        lambda q, k, v: causal_attention(q, k, v, block=BLOCK).sum(), (0, 1, 2))(q, k, v)
    equal = str(jax.make_jaxpr(grad)(*_inputs(2, 2, 16, 16, jnp.float32)[:3]))
    other = str(jax.make_jaxpr(grad)(*_inputs(2, 2, 24, 16, jnp.float32)[:3]))
    zeros = lambda text, width: text.count(f":f32[{BLOCK},{width}] = broadcast_in_dim[")
    assert zeros(equal, 16) == 1
    assert zeros(other, 24) == 1 and zeros(other, 16) == 1
    assert "vmem_limit_bytes=None" in equal  # no raised VMEM limit at these widths


@pytest.mark.parametrize("bad", ["k-narrower-than-q", "v-heads", "v-empty"])
def test_refuses_shapes_it_cannot_serve(bad):
    q, k, v, _ = _inputs(4, 2, 24, 16, jnp.float32)
    with pytest.raises(ValueError):
        if bad == "k-narrower-than-q":
            causal_attention(q, k[..., :16], v)
        elif bad == "v-heads":
            causal_attention(q, k, v[:, :1])
        else:
            causal_attention(q, k, v[..., :0])
