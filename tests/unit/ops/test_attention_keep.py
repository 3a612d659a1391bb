"""``ops.attention`` under a mask the program computed (``keep=``): the kernels (in
Pallas's interpreter) against the dense masked spelling in float32, forward and all
three gradients, with eight query heads reading one key/value head and one mask shared
by every head of a sequence.  Tolerances as in ``test_attention_grouped_windowed.py`` —
float32 rounding of a few hundred terms, doubled because where a query keeps a few keys
``dS = P (dP - delta)`` is a difference of near-equal numbers; bfloat16's 2**-8 on the
probabilities and on ``dS`` — and far under what a misread tile gives (a mask ignored,
transposed, or another sequence's, each tested below to FAIL the same comparison)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.ops import attention
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention

REL = {jnp.float32: 4e-6, jnp.bfloat16: 1.5e-2}


def _inputs(n, heads, kv_heads, t, dtype, hd=32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = [(n, heads, t, hd), (n, kv_heads, t, hd), (n, kv_heads, t, hd), (n, heads, t, hd)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _random_keep(n, t, share, seed=5):
    """``int8 [n, t, t]``, keys down and queries along: each pair kept with probability
    ``share``, every query keeping itself, so that no row is empty."""
    keep = jax.random.uniform(jax.random.key(seed), (n, t, t)) < share
    return (keep | jnp.eye(t, dtype=bool)[None]).astype(jnp.int8)


def _close(got, want, dtype, what):
    want = np.asarray(want)
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max()
    scale = max(np.abs(want).max(), 1.0)
    assert err <= REL[dtype] * scale, f"{what}: {err} of {scale}"


def _both(q, k, v, w, keep, dtype, block=None, dense_keep=None):
    """Output and the three gradients, kernels and dense oracle."""
    loss = lambda fn: lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()
    kernels = lambda q, k, v: causal_attention(q, k, v, keep=keep, block=block)
    dense = lambda q, k, v: dense_causal_attention(
        q, k, v, keep=keep if dense_keep is None else dense_keep)
    got = jax.jit(jax.value_and_grad(lambda q, k, v: (loss(kernels)(q, k, v), kernels(q, k, v)),
                                     (0, 1, 2), has_aux=True))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: (loss(dense)(q, k, v), dense(q, k, v)),
                              (0, 1, 2), has_aux=True)(*(a.astype(jnp.float32) for a in (q, k, v)))
    _close(got[0][1], want[0][1], dtype, "output")
    for g, r, like, name in zip(got[1], want[1], (q, k, v), "qkv"):
        assert g.shape == like.shape and g.dtype == dtype
        _close(g, r, dtype, f"d{name}")


@pytest.mark.parametrize("t,block", [(512, 256), (1024, None)], ids=["T512-blocks-of-256", "T1024"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_masked_kernels_match_the_dense_mask_grouped_eight_to_one(t, block, dtype):
    """Two sequences, each with a mask of its own, 16 query heads over 2 key/value heads:
    the forward's grid walks a group's eight heads under one strip of the mask."""
    q, k, v, w = _inputs(2, 16, 2, t, dtype)
    _both(q, k, v, w, _random_keep(2, t, 0.3), dtype, block=block)


def test_full_heads_and_a_sparse_mask():
    q, k, v, w = _inputs(1, 2, 2, 512, jnp.float32)
    _both(q, k, v, w, _random_keep(1, 512, 0.02), jnp.float32, block=256)


def test_a_row_that_keeps_one_key():
    """Every query keeps ONE key, query ``t`` key ``(7 t) % (t + 1)``: whole tiles of the
    mask are zero, a block's running maximum starts from masked scores, and the output is
    that key's value exactly."""
    t = 512
    q, k, v, w = _inputs(1, 8, 1, t, jnp.float32)
    chosen = (7 * jnp.arange(t)) % (jnp.arange(t) + 1)
    keep = (jnp.arange(t)[:, None] == chosen[None, :]).astype(jnp.int8)[None]  # [keys, queries]
    _both(q, k, v, w, keep, jnp.float32, block=256)
    out = causal_attention(q, k, v, keep=keep, block=256)
    np.testing.assert_allclose(out[0, 3], v[0, 0][chosen], atol=1e-6)


def test_the_future_stays_unseen_whatever_the_mask_says():
    """``keep`` all ones is plain causal attention: pair (t, s) needs ``s <= t`` too."""
    q, k, v, w = _inputs(1, 4, 2, 512, jnp.float32)
    ones = jnp.ones((1, 512, 512), jnp.int8)
    got = causal_attention(q, k, v, keep=ones, block=256)
    np.testing.assert_allclose(got, causal_attention(q, k, v, block=256), atol=2e-6)
    np.testing.assert_allclose(got, dense_causal_attention(q, k, v), atol=2e-6)


@pytest.mark.parametrize("wrong", ["ignored", "transposed", "the-other-sequence's"])
def test_a_misread_mask_fails_the_comparison(wrong):
    """The comparison above can fail: the kernels under the right mask against the dense
    spelling under a mask read wrongly."""
    q, k, v, w = _inputs(2, 8, 1, 512, jnp.float32)
    keep = _random_keep(2, 512, 0.3)
    other = {"ignored": jnp.ones_like(keep), "transposed": jnp.swapaxes(keep, 1, 2),
             "the-other-sequence's": keep[::-1]}[wrong]
    with pytest.raises(AssertionError):
        _both(q, k, v, w, keep, jnp.float32, block=256, dense_keep=other)


def test_the_mask_is_a_constant_of_the_backward_pass_and_any_dtype():
    """A boolean mask is taken as it is (cast to int8), and a float one takes no gradient."""
    q, k, v, _ = _inputs(1, 2, 1, 512, jnp.float32)
    keep = _random_keep(1, 512, 0.3)
    want = causal_attention(q, k, v, keep=keep)
    np.testing.assert_array_equal(causal_attention(q, k, v, keep=keep.astype(bool)), want)
    g = jax.grad(lambda m: causal_attention(q, k, v, keep=m).sum())(keep.astype(jnp.float32))
    assert not bool(g.any())


def test_a_mask_of_another_shape_or_beside_a_window_is_refused():
    q, k, v, _ = _inputs(2, 2, 1, 512, jnp.float32)
    with pytest.raises(ValueError, match="keep"):
        causal_attention(q, k, v, keep=jnp.ones((1, 512, 512), jnp.int8))
    with pytest.raises(ValueError, match="keep"):
        causal_attention(q, k, v, keep=jnp.ones((2, 512, 512), jnp.int8), window=64)


def test_without_a_mask_the_calls_are_what_they_were():
    """``keep=None`` traces to the program the kernels had before they took a mask: the
    same jaxpr as a call that never names it, kernels without the suffix on a (head,
    block) grid; with a mask the kernels carry ``_keep`` and the forward's grid puts the
    group's heads innermost."""
    q, k, v, _ = _inputs(1, 8, 1, 512, jnp.bfloat16)
    loss = lambda **kw: lambda q, k, v: causal_attention(q, k, v, **kw).astype(jnp.float32).sum()
    plain = str(jax.make_jaxpr(jax.grad(loss(), (0, 1, 2)))(q, k, v))
    assert plain == str(jax.make_jaxpr(jax.grad(loss(keep=None), (0, 1, 2)))(q, k, v))
    assert "_keep" not in plain and "name=causal_attention_fwd\n" in plain + "\n"
    assert "grid=(8, 1)" in plain and "grid=(1, 1, 8)" not in plain
    masked = str(jax.make_jaxpr(jax.grad(loss(keep=_random_keep(1, 512, 0.5)), (0, 1, 2)))(q, k, v))
    assert "name=causal_attention_fwd_keep" in masked and "name=causal_attention_bwd_keep" in masked
    assert "grid=(1, 1, 8)" in masked  # (key/value head, query block, head of the group)
    assert attention.KEPT == ("causal_attention_out", "causal_attention_lse")
