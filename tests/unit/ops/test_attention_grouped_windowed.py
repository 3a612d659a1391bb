"""``ops.attention`` with grouped key/value heads and a sliding window: the kernels (in
Pallas's interpreter) against the dense spelling in float32, forward and all three
gradients.  Tolerances as in ``test_attention.py`` — float32 rounding of a few hundred
terms, doubled here because under a window of a few keys ``dS = P (dP - delta)`` is a
difference of near-equal numbers; bfloat16's 2**-8 on the probabilities and on ``dS`` —
and nothing more for a group: its query heads' shares of ``dK``/``dV`` are summed in
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.ops import attention
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention

REL = {jnp.float32: 4e-6, jnp.bfloat16: 1.5e-2}
T, BLOCK = 1024, 256
#: Shorter than a block; a block; cut by two blocks' edges; whole blocks; all but
#: nothing of the sequence; the sequence and more (no window at all).
WINDOWS = [1, 100, 256, 300, 512, 1000, 1024, 4096]


def _inputs(heads, kv_heads, dtype, t=T, hd=64, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = [(1, heads, t, hd), (1, kv_heads, t, hd), (1, kv_heads, t, hd), (1, heads, t, hd)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


def _close(got, want, dtype, what):
    want = np.asarray(want)
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max()
    # At a window of 1 a query's gradient is exactly zero: the scale is then the inputs'.
    scale = max(np.abs(want).max(), 1.0)
    assert err <= REL[dtype] * scale, f"{what}: {err} of {scale}"


def _both(q, k, v, w, dtype, **options):
    """Output and the three gradients, kernels and dense oracle."""
    loss = lambda fn: lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()
    kernels = lambda q, k, v: causal_attention(q, k, v, block=BLOCK, **options)
    dense = lambda q, k, v: dense_causal_attention(q, k, v, **options)
    got = jax.jit(jax.value_and_grad(lambda q, k, v: (loss(kernels)(q, k, v), kernels(q, k, v)),
                                     (0, 1, 2), has_aux=True))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: (loss(dense)(q, k, v), dense(q, k, v)),
                              (0, 1, 2), has_aux=True)(*_f32(q, k, v))
    _close(got[0][1], want[0][1], dtype, "output")
    for g, r, like, name in zip(got[1], want[1], (q, k, v), "qkv"):
        assert g.shape == like.shape and g.dtype == dtype
        _close(g, r, dtype, f"d{name}")


@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_kernels_match_the_dense_mask(window):
    _both(*_inputs(2, 2, jnp.float32), jnp.float32, window=window)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: d.__name__)
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (7, 1), (14, 2)],
                         ids=["groups-of-1", "one-group-of-7", "two-groups-of-7"])
def test_grouped_kernels_match_the_dense_repeat(heads, kv_heads, dtype):
    _both(*_inputs(heads, kv_heads, dtype, t=512), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: d.__name__)
@pytest.mark.parametrize("window", [300, 512])
def test_grouped_and_windowed_together(window, dtype):
    _both(*_inputs(7, 1, dtype), dtype, window=window)


def test_the_window_is_a_window():
    """A change to token ``at`` moves the outputs at ``at .. at + window - 1`` and no
    other: nothing before it (causal), nothing from ``at + window`` on, bit for bit."""
    q, k, v, _ = _inputs(7, 1, jnp.float32, seed=3)
    at, window = 200, 300
    fn = jax.jit(lambda q, k, v: causal_attention(q, k, v, window=window, block=BLOCK))
    before = fn(q, k, v)
    after = fn(q, k.at[:, :, at].add(1.0), v.at[:, :, at].add(1.0))
    moved = np.abs(np.asarray(before - after)).max(axis=(0, 1, 3)) > 0
    assert moved[at:at + window].all() and not moved[:at].any() and not moved[at + window:].any()


def test_blocks_behind_the_window_are_not_visited():
    """Keys wholly behind the window are never read: poison there (a NaN would pass
    through a mask's ``where`` only if its score block were computed and summed)."""
    q, k, v, w = _inputs(2, 1, jnp.float32)
    window = 256  # query block i reads key blocks i - 1 and i
    poison = lambda a: a.at[:, :, :BLOCK].set(jnp.nan)
    fn = lambda q, k, v: causal_attention(q, k, v, window=window, block=BLOCK)
    out = jax.jit(fn)(q, poison(k), poison(v))
    assert bool(jnp.isfinite(out[:, :, 2 * BLOCK:]).all())
    grads = jax.jit(jax.grad(lambda q, k, v: (fn(q, k, v)[:, :, 2 * BLOCK:] * w[:, :, 2 * BLOCK:]).sum(),
                             (0, 1, 2)))(q, poison(k), poison(v))
    assert all(bool(jnp.isfinite(g[:, :, 2 * BLOCK:]).all()) for g in grads)


@pytest.mark.parametrize("window,block,steps", [
    (1, 512, (0, 1)), (2, 512, (0, 2)), (511, 512, (0, 2)), (512, 512, (1, 2)),
    (513, 512, (1, 2)), (514, 512, (1, 3)), (4096, 512, (8, 9)), (4096, 256, (16, 17)), (300, 256, (1, 3)),
])
def test_window_steps_by_hand(window, block, steps):
    """``(a, b)``: block pairs ``i - j < a`` lie wholly inside the window, ``i - j >= b``
    wholly behind it — against a count over every position pair."""
    assert attention._window_steps(window, block) == steps
    a, b = steps
    for apart in range(b + 2):
        gaps = [apart * block + qi - ki for qi in (0, block - 1) for ki in (0, block - 1)]
        assert all(g < window for g in gaps) == (apart < a) or apart == 0
        assert any(g < window for g in gaps if g >= 0) == (apart < b)


def test_full_heads_and_no_window_trace_to_the_program_they_were():
    """Static branches only: the jaxpr of the plain call has no trace of either argument
    (same equations as with ``window`` past the sequence's end, which is no window)."""
    q, k, v, _ = _inputs(2, 2, jnp.bfloat16, t=512)
    plain = str(jax.make_jaxpr(lambda q, k, v: causal_attention(q, k, v))(q, k, v))
    assert str(jax.make_jaxpr(lambda q, k, v: causal_attention(q, k, v, window=512))(q, k, v)) == plain
    assert "floor" not in plain and "clamp" not in plain  # no h // group, no window bounds


@pytest.mark.parametrize("bad", ["heads", "kv-shapes", "window"])
def test_refuses_groups_and_windows_it_cannot_serve(bad):
    q, k, v, _ = _inputs(6, 4, jnp.float32, t=512)
    with pytest.raises(ValueError):
        if bad == "heads":
            causal_attention(q, k, v)  # 6 query heads over 4
        elif bad == "kv-shapes":
            causal_attention(q, k[:, :2], v[:, :3])
        else:
            causal_attention(q, k[:, :3], v[:, :3], window=0)
