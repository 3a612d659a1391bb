"""``nn.embed_rows``: the token-embedding lookup of the zoo's three language models.

Its value and its gradient are plain indexing's, whatever the number of column bands the
gradient accumulates in: float32 exactly (the same sums, a band holds whole rows' columns),
bfloat16 to a rounding a duplicate of a row's token (the order of a row's duplicates may
differ, as it may between two runs of ``table[tokens]``'s own scatter-add)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu import nn
from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig

ROWS, WIDTH = 96, 512  # four 128-lane tiles: 1, 2 or 4 bands


def _plain(table, tokens):
    return table[tokens]


@pytest.fixture
def bands(request, monkeypatch):
    """Set the budget so that a ``[ROWS, WIDTH]`` table of either dtype splits in
    ``request.param`` bands."""
    k = request.param
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", ROWS * (WIDTH // k) * 4)
    assert nn.embed_bands(ROWS, WIDTH, 4) == k
    return k


def _case(dtype, duplication, shape):
    keys = jax.random.split(jax.random.key(7), 3)
    table = jax.random.normal(keys[0], (ROWS, WIDTH), dtype)
    count = int(np.prod(shape))
    if duplication == "heavy":  # 64 tokens over 5 rows
        tokens = jax.random.randint(keys[1], shape, 0, 5)
    else:
        tokens = jax.random.permutation(keys[1], ROWS)[:count].reshape(shape)
    weight = jax.random.normal(keys[2], (*shape, WIDTH), dtype)
    return table, tokens.astype(jnp.int32), weight


def _value_and_grad(lookup, table, tokens, weight):
    return jax.value_and_grad(
        lambda t: (lookup(t, tokens) * weight).astype(jnp.float32).sum())(table)


def _assert_same_gradient(got, want, tokens, weight):
    assert got.shape == want.shape and got.dtype == want.dtype
    if got.dtype == jnp.float32:
        np.testing.assert_array_equal(got, want)
        return
    # One bfloat16 rounding (2**-8 relative) of the running sum for every duplicate.
    flat = tokens.reshape(-1)
    size = jnp.zeros(got.shape, jnp.float32).at[flat].add(
        jnp.abs(weight.astype(jnp.float32)).reshape(flat.shape[0], -1))
    copies = jnp.zeros((got.shape[0],), jnp.float32).at[flat].add(1.0)
    gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    assert bool(jnp.all(gap <= 2.0**-8 * copies[:, None] * size))


@pytest.mark.parametrize("shape", [(64,), (4, 16)], ids=["N", "NxT"])
@pytest.mark.parametrize("duplication", ["heavy", "none"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bands", [1, 2, 4], indirect=True)
def test_value_and_gradient_are_plain_indexings(bands, dtype, duplication, shape):
    table, tokens, weight = _case(dtype, duplication, shape)
    value, grad = _value_and_grad(nn.embed_rows, table, tokens, weight)
    want_value, want_grad = _value_and_grad(_plain, table, tokens, weight)
    np.testing.assert_array_equal(nn.embed_rows(table, tokens), table[tokens])
    assert float(value) == float(want_value)
    _assert_same_gradient(grad, want_grad, tokens, weight)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bands", [1, 2, 4], indirect=True)
def test_under_jit(bands, dtype):
    table, tokens, weight = _case(dtype, "heavy", (4, 16))
    _, grad = jax.jit(lambda *a: _value_and_grad(nn.embed_rows, *a))(table, tokens, weight)
    _assert_same_gradient(grad, _value_and_grad(_plain, table, tokens, weight)[1], tokens, weight)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bands", [1, 2, 4], indirect=True)
def test_under_vmap_over_a_client_axis(bands, dtype):
    """One table, a batch of tokens a client: a gradient a client, as the round program's
    ``vmap`` of the local fit asks."""
    table, tokens, weight = _case(dtype, "heavy", (3, 2, 16))
    per_client = lambda lookup: jax.jit(jax.vmap(
        lambda tk, w: _value_and_grad(lookup, table, tk, w)[1]))(tokens, weight)
    got, want = per_client(nn.embed_rows), per_client(_plain)
    assert got.shape == (3, ROWS, WIDTH)
    for client in range(3):
        _assert_same_gradient(got[client], want[client], tokens[client], weight[client])


def _tiny_lm_apply(params, x, train=False, rng=None):
    """Mean of the looked-up rows through a head: log-probabilities ``[N, ROWS]``."""
    pooled = nn.embed_rows(params["embed"], x.astype(jnp.int32)).mean(axis=1)
    return nn.log_softmax(pooled @ params["head"])


@pytest.mark.parametrize("client_chunk", [None, 1], ids=["vmap", "chunks-of-1"])
@pytest.mark.parametrize("bands", [2, 4], indirect=True)
def test_inside_the_round_program_on_the_cpu_mesh(bands, client_chunk, monkeypatch):
    """The ``shard_map`` round over four devices, clients under ``vmap`` or one at a time:
    the band loop builds its accumulators inside it, and a round leaves the parameters
    where the plain lookup's round leaves them."""
    mesh = make_mesh(devices=jax.devices()[:4])
    training = TrainingConfig(batch_size=2, local_epochs=1, learning_rate=0.1)
    strategy = fedavg_strategy()
    keys = jax.random.split(jax.random.key(3), 4)
    params = {"embed": jax.random.normal(keys[0], (ROWS, WIDTH)),
              "head": 0.05 * jax.random.normal(keys[1], (WIDTH, ROWS))}
    data = ClientData(x=jax.random.randint(keys[2], (4, 4, 8), 0, 6),
                      y=jax.random.randint(keys[3], (4, 4), 0, ROWS), mask=jnp.ones((4, 4)))

    def one_round():
        step = build_round_step(_tiny_lm_apply, training, mesh, strategy,
                                client_chunk=client_chunk, params_like=params)
        return step(params, init_server_state(strategy, params), data, jnp.full((4,), 4.0),
                    jax.random.split(jax.random.key(6), 4)).params

    banded = one_round()
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", ROWS * WIDTH * 4)
    plain = one_round()
    assert float(jnp.abs(plain["embed"] - params["embed"]).max()) > 0
    jax.tree.map(np.testing.assert_array_equal, banded, plain)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_one_band_lowers_to_the_indexing_expression(dtype):
    """At one band there is no custom rule: the lowered gradient is ``table[tokens]``'s,
    character for character, so a model whose table fits keeps its program."""
    table, tokens, weight = _case(dtype, "heavy", (4, 16))
    assert nn.embed_bands(ROWS, WIDTH, table.dtype.itemsize) == 1

    def lowered(lookup):
        def loss(t, tk, w):
            return (lookup(t, tk) * w).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss)).lower(table, tokens, weight).as_text()

    assert lowered(nn.embed_rows) == lowered(_plain)
    assert "while" not in lowered(nn.embed_rows)


@pytest.mark.parametrize("rows,width,itemsize,bands", [
    (50257, 768, 2, 1),      # GPT-2's table in bfloat16: 77 MB
    (16384, 2688, 2, 1),     # the hybrid's held slice: 88 MB
    (37984, 2560, 2, 4),     # SmallThinker's held slice: 194 MB, four bands of 49 MB
    (37984, 2560, 4, 5),     # the same looked up in float32
    (151936, 2560, 2, 10),   # its whole vocabulary: 9 bands would fit, 9 does not divide 20 tiles
    (131072, 2688, 2, 21),   # the hybrid's whole vocabulary: 21 tiles, 8 bands would fit
    (10**7, 256, 2, 2),      # no split fits: the finest one, not an error
    (10**7, 64, 4, 1),       # not whole tiles: never split
    (64, 64, 4, 1),
], ids=["gpt2", "hybrid", "smallthinker", "smallthinker-f32", "smallthinker-full",
        "hybrid-full", "nothing-fits", "not-whole-tiles", "tiny"])
def test_band_count_by_shape(rows, width, itemsize, bands):
    assert nn.embed_bands(rows, width, itemsize) == bands


def test_the_gradient_scope_is_named(monkeypatch):
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", ROWS * WIDTH)
    table, tokens, weight = _case(jnp.float32, "heavy", (64,))
    text = jax.jit(jax.grad(lambda t: (nn.embed_rows(t, tokens) * weight).sum())).lower(
        table).as_text(debug_info=True)
    assert "embed_grad" in text
