"""``ops.attention`` under the block-diffusion mask (``blocks=(half, size)``): the kernels
(in Pallas's interpreter) against the dense spelling in float32, forward and all three
gradients, with eight query heads reading one key/value head; the mask against the four
rules written out position by position; and the tiles the kernels visit, counted from
what a poisoned tile reaches.  Tolerances as in ``test_attention_keep.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.ops import attention
from nanofed_tpu.ops.attention import (
    block_diffusion_mask, causal_attention, dense_causal_attention, engages)

REL = {jnp.float32: 4e-6, jnp.bfloat16: 1.5e-2}


def _inputs(n, heads, kv_heads, t, dtype, hd=32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = [(n, heads, t, hd), (n, kv_heads, t, hd), (n, kv_heads, t, hd), (n, heads, t, hd)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _close(got, want, dtype, what):
    want = np.asarray(want)
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max()
    scale = max(np.abs(want).max(), 1.0)
    assert err <= REL[dtype] * scale, f"{what}: {err} of {scale}"


def _both(q, k, v, w, blocks, dtype, block=None, dense_blocks=None):
    """Output and the three gradients, kernels and dense oracle."""
    loss = lambda fn: lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()
    kernels = lambda q, k, v: causal_attention(q, k, v, blocks=blocks, block=block)
    dense = lambda q, k, v: dense_causal_attention(q, k, v, blocks=dense_blocks or blocks)
    got = jax.jit(jax.value_and_grad(lambda q, k, v: (loss(kernels)(q, k, v), kernels(q, k, v)),
                                     (0, 1, 2), has_aux=True))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: (loss(dense)(q, k, v), dense(q, k, v)),
                              (0, 1, 2), has_aux=True)(*(a.astype(jnp.float32) for a in (q, k, v)))
    _close(got[0][1], want[0][1], dtype, "output")
    for g, r, like, name in zip(got[1], want[1], (q, k, v), "qkv"):
        assert g.shape == like.shape and g.dtype == dtype
        _close(g, r, dtype, f"d{name}")


def test_the_mask_is_the_four_rules_position_by_position():
    half, size = 12, 4
    seen = np.asarray(block_diffusion_mask(2 * half, half, size))
    block = lambda j: (j % half) // size
    for j in range(2 * half):
        for s in range(2 * half):
            q_noised, k_noised = j >= half, s >= half
            if not q_noised and not k_noised:
                want = block(s) <= block(j)
            elif q_noised and not k_noised:
                want = block(s) < block(j)
            elif q_noised and k_noised:
                want = block(s) == block(j)
            else:
                want = False
            assert seen[j, s] == want, (j, s)
    assert seen.any(axis=1).all()  # every row sees its own block at least
    assert seen.sum() == half * half + half * size  # L^2 + L B
    assert not np.triu(seen, 1)[:, :half][half:].any()  # under the tile grid's diagonal by halves
    # One half alone: the clean rule, causal across blocks and whole inside one.
    np.testing.assert_array_equal(block_diffusion_mask(half, half, size), seen[:half, :half])


@pytest.mark.parametrize("t,half,size,block,dtype", [
    (1024, 512, 4, 128, jnp.float32), (1024, 512, 64, None, jnp.float32),
    (512, 512, 4, 256, jnp.float32), (1024, 1024, 128, 128, jnp.float32),
    (1024, 512, 4, 128, jnp.bfloat16), (1024, 512, 64, None, jnp.bfloat16),
], ids=["two-halves-tiles-of-128", "two-halves-blocks-of-64", "one-half", "one-half-a-block-a-tile",
        "bfloat16-tiles-of-128", "bfloat16-blocks-of-64"])
def test_block_diffusion_kernels_match_the_dense_mask_grouped_eight_to_one(t, half, size, block, dtype):
    """Two sequences, 16 query heads over 2 key/value heads: output and the gradients of
    ``q``, ``k`` and ``v``."""
    q, k, v, w = _inputs(2, 16, 2, t, dtype)
    _both(q, k, v, w, (half, size), dtype, block=block)


def test_a_wrong_mask_fails_the_same_comparison():
    """The causal rule, the clean rule over both halves, and another block length each
    miss by far more than the tolerance: the comparison can see a misread mask."""
    q, k, v, w = _inputs(1, 8, 1, 1024, jnp.float32)
    for wrong in ((1024, 4), (512, 8), (512, 1)):
        with pytest.raises(AssertionError):
            _both(q, k, v, w, (512, 4), jnp.float32, block=128, dense_blocks=wrong)
    got = causal_attention(q, k, v, blocks=(512, 4), block=128)
    assert float(jnp.abs(got - causal_attention(q, k, v, block=128)).max()) > 1e-2


def _visits(t, half, tile, size=4):
    """``(forward, backward)``: boolean ``[tiles, tiles]``, query tile down and key tile
    along, True where the kernels' walk touched the pair at all, masked or whole.  A value
    head of one column a tile: ``V`` is NaN in its tile's own column, so a query tile's
    output is NaN in column ``c`` iff its walk multiplied probabilities (zeros, where
    masked) with key tile ``c``; ``dO`` likewise, so ``dV`` of a key tile is NaN in column
    ``c`` iff its walk met query tile ``c``."""
    tiles = t // tile
    own = jnp.repeat(jnp.eye(tiles, dtype=bool), tile, axis=0)  # [t, tiles]
    poison = jnp.where(own, jnp.nan, 0.0)[None, None]
    q = jax.random.normal(jax.random.key(0), (1, 1, t, 8))
    attend = lambda v: causal_attention(q, q, v, blocks=(half, size), block=tile)
    out, pull = jax.vjp(attend, poison)
    (dv,) = pull(poison)
    by_tile = lambda a: np.isnan(np.asarray(a[0, 0])).reshape(tiles, tile, tiles).any(axis=1)
    return by_tile(out), by_tile(dv).T


def test_the_kernels_visit_80_of_the_136_causal_tile_pairs_at_the_cells_stream():
    """8192 positions in two halves of 4096, tiles of 512: a clean query tile ``i`` visits
    the clean tiles ``0..i``; a noised one ``8 + i`` the clean tiles ``0..i`` and its own
    tile; no clean query tile a noised key tile, no noised tile another noised tile.  The
    backward pass mirrors the walk by key tile."""
    forward, backward = _visits(8192, 4096, 512)
    want = np.zeros((16, 16), bool)
    for i in range(8):
        want[i, :i + 1] = True
        want[8 + i, :i + 1] = True
        want[8 + i, 8 + i] = True
    np.testing.assert_array_equal(forward, want)
    np.testing.assert_array_equal(backward, want)
    assert forward.sum() == 80 and np.tril(np.ones((16, 16), bool)).sum() == 136
    assert not forward[:8, 8:].any()  # no clean-query / noised-key tile
    assert forward[8:, 8:].sum() == 8  # of the noised-noised tiles the diagonal alone
    # Of the 80, the 24 a block boundary cuts hold a pair the mask hides.
    seen = np.asarray(block_diffusion_mask(8192, 4096, 4)).reshape(16, 512, 16, 512)
    whole, some = seen.all(axis=(1, 3)), seen.any(axis=(1, 3))
    np.testing.assert_array_equal(some, want)  # every visited tile holds a seen pair ...
    assert (some & ~whole).sum() == 24  # ... and no tile with one is skipped


def test_one_half_walks_the_causal_tiles():
    forward, backward = _visits(1024, 1024, 256)
    np.testing.assert_array_equal(forward, np.tril(np.ones((4, 4), bool)))
    np.testing.assert_array_equal(backward, forward)


def test_the_branch_names_its_kernels_and_engages_by_halves(kernel_calls):
    q, k, v, _ = _inputs(1, 8, 1, 1024, jnp.float32)
    loss = lambda q, k, v: causal_attention(q, k, v, blocks=(512, 4)).sum()
    assert kernel_calls(jax.grad(loss, (0, 1, 2)), q, k, v) == {
        "causal_attention_fwd_blocks": 1, "causal_attention_bwd_blocks": 1}
    # The accepted roofline reader finds kernels by prefix and a window by this suffix.
    assert not "causal_attention_fwd_blocks".endswith("_window")
    assert engages(8192, 4096) and engages(4096, 4096) and engages(512, 256)
    assert not engages(512, 128) and not engages(256, 128) and not engages(16384, 8192)
    assert engages(1024) and attention.block_for(256) == 256


def test_what_the_branch_refuses():
    q, k, v, _ = _inputs(1, 2, 1, 1024, jnp.float32)
    for bad in ({"blocks": (512, 3)}, {"blocks": (512, 4), "window": 8}, {"blocks": (300, 4)},
                {"blocks": (512, 4), "block": 256, "keep": jnp.ones((1, 1024, 1024), jnp.int8)},
                {"blocks": (256, 4)}, {"blocks": (512, 256), "block": 128}, {"blocks": (512, 0)}):
        with pytest.raises(ValueError):
            causal_attention(q, k, v, **bad)
