"""The seam under the five decoders (``models.decoder``): the layer-stack runner against a
hand-written loop, the one checkpoint and its policy read from the jaxpr, the
language-model wrapper's head and counters, and the shared parts.  What each decoder
makes of them is held by its own file and by ``test_layer_checkpoints.py``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.models import decoder, experts

WIDTH, COUNTED = 8, 3


def _mix(p, x, scale, *, cfg):
    """A layer that counts: one product, the operand ``scale`` on its way out."""
    out = x + jnp.tanh(x @ p["w"]) * scale
    return out, jnp.stack([out.mean(), p["w"].sum(), jnp.float32(1.0)])


def _shift(p, x, scale, *, cfg):
    """A layer of another kind, with other leaves, that counts nothing."""
    return x * p["gain"] + p["bias"] * scale + cfg["offset"], jnp.zeros((COUNTED,), jnp.float32)


def _toy():
    """``(params, x, scale, plan(params))``: two kinds of layer stacked on a leading axis,
    interleaved mix, shift, mix, mix, shift; the three ``mix`` entries share ONE function."""
    k = jax.random.split(jax.random.key(0), 5)
    params = {"mix": {"w": 0.3 * jax.random.normal(k[0], (3, WIDTH, WIDTH))},
              "shift": {"gain": 1 + 0.1 * jax.random.normal(k[1], (2, WIDTH)),
                        "bias": jax.random.normal(k[2], (2, WIDTH))}}
    x = jax.random.normal(k[3], (2, 5, WIDTH))
    cfg = {"offset": 0.25}
    mix, shift = partial(_mix, cfg=cfg), partial(_shift, cfg=cfg)

    def plan(params):
        return [(mix, params["mix"], 0), (shift, params["shift"], 0), (mix, params["mix"], 1),
                (mix, params["mix"], 2), (shift, params["shift"], 1)]

    return params, x, jnp.float32(0.7), plan


def _by_hand(params, x, scale, plan):
    counters = jnp.zeros((COUNTED,), jnp.float32)
    for layer_fn, stacked, index in plan(params):
        x, counted = layer_fn({name: leaf[index] for name, leaf in stacked.items()}, x, scale)
        counters = counters + counted
    return x, counters


def test_run_layers_is_the_hand_written_loop_in_values_gradients_and_counters():
    params, x, scale, plan = _toy()
    run = lambda params, x, scale: decoder.run_layers(x, plan(params), COUNTED, scale)
    got, want = run(params, x, scale), _by_hand(params, x, scale, plan)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)
    assert got[1].dtype == jnp.float32 and float(got[1][2]) == 3.0  # three layers counted
    loss = lambda fn: lambda params, x, scale: (lambda out: out[0].sum() + out[1][0])(fn(params, x, scale))
    grads = jax.grad(loss(run), argnums=(0, 1, 2))(params, x, scale)
    by_hand = jax.grad(loss(partial(_by_hand, plan=plan)), argnums=(0, 1, 2))(params, x, scale)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(by_hand)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_run_layers_checkpoints_every_layer_under_the_one_policy(equations):
    """Five layers, five ``jax.checkpoint`` equations with ``KEEP_NAMED_OUTPUTS``; the operand
    is an argument of each (a layer's leaves, ``x``, ``scale``), not a constant closed over;
    entries that share one layer function share its trace; all under ``layer_scan``."""
    params, x, scale, plan = _toy()
    run = lambda params, x, scale: decoder.run_layers(x, plan(params), COUNTED, scale)
    remat = [e for e in equations(run, params, x, scale) if e.primitive.name == "remat2"]
    assert len(remat) == 5
    assert all(e.params["policy"] is experts.KEEP_NAMED_OUTPUTS for e in remat)
    assert [len(e.invars) for e in remat] == [3, 4, 3, 3, 4]
    assert all(e.invars[-1] is remat[0].invars[-1] for e in remat)  # the one ``scale``
    bodies = [e.params["jaxpr"] for e in remat]
    assert bodies[0] is bodies[2] is bodies[3] and bodies[1] is bodies[4]
    assert bodies[0] is not bodies[1]
    assert all("layer_scan" in str(e.source_info.name_stack) for e in remat)


def _language_model(counted_layers, check=None, hidden_states=None):
    """A toy decoder behind the wrapper: its ``hidden_states`` counts ``[1, 6, 12]`` in all,
    whatever the model is told its counted layers are."""
    cfg = {"vocab": 11, "seq_len": 4, "width": WIDTH, "eps": 1e-5}

    def init(rng, *, vocab, width, **_):
        k = jax.random.split(rng, 2)
        return {"embed": jax.random.normal(k[0], (vocab, width)), "norm_f": jnp.full((width,), 1.5),
                "head": jax.random.normal(k[1], (width, vocab))}

    def summed(params, tokens, cfg):
        assert cfg["seq_len"] == 4
        return jnp.cumsum(params["embed"][tokens], axis=1), jnp.array([1.0, 6.0, 12.0])

    return decoder.language_model("toy_lm", cfg, init, hidden_states or summed, ("a", "b", "c"),
                                  counted_layers, check=check)


@pytest.mark.parametrize("counted_layers", [0, 1, 3])
def test_language_model_reports_counters_only_where_a_layer_counts(counted_layers):
    model = _language_model(counted_layers)
    assert (model.name, model.input_shape, model.num_classes, model.token_stream) == (
        "toy_lm", (4,), 11, True)
    assert hasattr(model.apply, "with_counters") == (counted_layers > 0)
    if counted_layers:
        params = model.init(jax.random.key(1))
        tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]])
        logp, counters = model.apply.with_counters(params, tokens)
        np.testing.assert_array_equal(logp, model.apply(params, tokens))
        assert list(counters) == ["a", "b", "c"]  # the means over the counted layers
        np.testing.assert_allclose([float(v) for v in counters.values()],
                                   np.array([1.0, 6.0, 12.0]) / counted_layers, rtol=1e-6)
        grads = jax.grad(lambda p: model.apply.with_counters(p, tokens)[1]["a"])(params)
        assert all(float(jnp.abs(g).max()) == 0 for g in jax.tree.leaves(grads))


def test_language_model_returns_the_last_positions_float32_log_probs():
    seen = []
    model = _language_model(2, check=lambda x: seen.append(x.shape))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), model.init(jax.random.key(1)))
    tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 9, 10]])
    logp = model.apply(params, tokens, train=True, rng=jax.random.key(2))  # no dropout: ignored
    assert logp.shape == (3, 11) and logp.dtype == jnp.float32 and seen == [(3, 4)]
    last = jnp.cumsum(params["embed"][tokens], axis=1)[:, -1, :]
    normed = decoder.rms_norm(params["norm_f"], last, 1e-5)
    assert normed.dtype == jnp.bfloat16
    want = jax.nn.log_softmax((normed @ params["head"]).astype(jnp.float32))
    np.testing.assert_array_equal(logp, want)
    np.testing.assert_allclose(jnp.exp(logp).sum(-1), 1.0, rtol=1e-5)
    # ``init`` is the model's own with the configuration bound: leaf for leaf from one key.
    again = _language_model(0).init(jax.random.key(1))
    assert all(bool((a == b.astype(jnp.bfloat16)).all())
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_a_refusing_check_stops_every_call():
    def whole_pairs(x):
        if x.shape[1] % 2:
            raise ValueError("odd")

    model = _language_model(1, check=whole_pairs)
    params = model.init(jax.random.key(1))
    model.apply(params, jnp.zeros((1, 4), jnp.int32))
    for call in (model.apply, model.apply.with_counters):
        with pytest.raises(ValueError, match="odd"):
            call(params, jnp.zeros((1, 3), jnp.int32))


def test_the_stack_and_the_head_carry_their_scopes():
    scaled = (lambda p, x: (x * p["g"], jnp.zeros((3,))), {"g": jnp.ones((1, WIDTH))}, 0)
    model = _language_model(1, hidden_states=lambda params, tokens, cfg: decoder.run_layers(
        params["embed"][tokens], [scaled], 3))
    params = model.init(jax.random.key(1))
    text = jax.jit(model.apply).lower(params, jnp.zeros((2, 4), jnp.int32)).as_text(debug_info=True)
    assert "layer_scan" in text and "lm_head" in text


def test_rotate_is_turn_pairs_by_the_text_angle():
    x = jax.random.normal(jax.random.key(4), (2, 9, 3, 16)).astype(jnp.bfloat16)
    freq = decoder.pair_frequencies(8, 1e4)
    np.testing.assert_allclose(freq, 1e4 ** (-np.arange(8) / 8), rtol=1e-6)
    angle = jnp.arange(9, dtype=jnp.float32)[:, None] * freq[None, :]
    got = decoder.rotate(x, 1e4)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got, decoder.turn_pairs(x, angle))
    np.testing.assert_array_equal(decoder.turn_pairs(x, jnp.zeros((9, 8))), x)  # no angle, no turn
    # A quarter turn sends (a, b) to (-b, a), pair i with pair i + 8.
    quarter = decoder.turn_pairs(x.astype(jnp.float32), jnp.full((9, 8), np.pi / 2))
    np.testing.assert_allclose(quarter[..., :8], -x[..., 8:].astype(jnp.float32), atol=1e-6)
    np.testing.assert_allclose(quarter[..., 8:], x[..., :8].astype(jnp.float32), atol=1e-6)


def test_gated_mlp_is_the_silu_gated_product_on_a_fused_leaf():
    k = jax.random.split(jax.random.key(5), 3)
    w_gate_up, w_down = jax.random.normal(k[0], (WIDTH, 12)), jax.random.normal(k[1], (6, WIDTH))
    h = jax.random.normal(k[2], (4, 5, WIDTH))
    want = (jax.nn.silu(h @ w_gate_up[:, :6]) * (h @ w_gate_up[:, 6:])) @ w_down
    np.testing.assert_allclose(decoder.gated_mlp(w_gate_up, w_down, h), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("held, refused", [
    (dict(experts=16, first_expert=0, experts_held=16, top_k=3), False),
    (dict(experts=16, first_expert=12, experts_held=4, top_k=16), False),
    (dict(experts=16, first_expert=-1, experts_held=4, top_k=3), True),
    (dict(experts=16, first_expert=14, experts_held=4, top_k=3), True),
    (dict(experts=16, first_expert=0, experts_held=4, top_k=17), True),
], ids=["all-held", "the-last-four", "before-the-first", "past-the-last", "more-picks-than-experts"])
def test_check_held_refuses_experts_outside_the_routed_ones(held, refused):
    if refused:
        with pytest.raises(ValueError, match="held experts must lie among the routed ones"):
            experts.check_held(**held)
    else:
        experts.check_held(**held)
