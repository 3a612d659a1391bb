"""The gated, sandwich-normed window/full decoder (``models.gated_moe``) against its plain
reference (``benchmark/reference/afmoe.py``) at a small size on the CPU.

In float32 both sides compute the same real numbers, and what is left is the order of the
sums (rows laid out in blocks against dense products an expert, a fused ``W_gate | W_up``
product against two, blockwise softmax against whole rows): a few ulps amplified through
four layers and eight out-norms, hence 1e-5 on log-probabilities and 1e-4 relative on a
leaf's gradient, far under anything a missing term would give.  In bfloat16 (the cell's
compute precision) every product rounds at 2**-8: log-probabilities agree to 0.1 and a
leaf's gradient to a tenth of its norm, which a dropped term (the gate left out, the
rotation in a full layer, a norm on the wrong side of the residual add) passes several
times over; each of those is also tested on its own below."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import experts, gated_moe, get_model
from nanofed_tpu.ops import experts as ops_experts
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig

#: The layout's block for experts as small as the tests': the largest row tile.
DEFAULT_BLOCK = ops_experts.TILES[0]

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "width": 64, "sliding_layout": [1, 1, 0, 1], "window": 8,
    "rope_theta": 10000.0, "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "dense_layers": 1,
    "dense_width": 160, "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 24, "shared_width": 24, "routed_scale": 2.826, "eps": 1e-5,
}
#: Long enough for ``ops.attention``'s kernels (the interpreter here): eight query heads a
#: key/value head, a window of 200 that binds in the sliding layers, and a full layer.
KERNELS = {**SMALL, "seq_len": 512, "window": 200, "sliding_layout": [1, 0, 1],
           "attn_heads": 8, "kv_heads": 1}
IDENTITY = lambda t: t


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "afmoe_reference", REPO / "benchmark" / "reference" / "afmoe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(reference, kw, batch=3):
    """The reference's seeded tree with the embeddings brought down to N(0, 0.025), where
    the branches outweigh them in the stream (which is what the benchmark's
    initialisation avoids, for its routing's sake), a selection bias as large as the
    scores' spread, so that it decides picks, and norm weights that are not all 1, so that
    a norm given another's weight shows."""
    params = reference.init_params(jax.random.key(0), kw)
    params["embed"] = 0.025 * params["embed"]
    params["moe"]["router_bias"] = 20.0 * params["moe"]["router_bias"]
    keys = iter(jax.random.split(jax.random.key(9), 64))
    for kind in ("dense", "moe"):
        params[kind] = {name: leaf * (1 + 0.2 * jax.random.normal(next(keys), leaf.shape))
                        if name.startswith("norm") else leaf for name, leaf in params[kind].items()}
    tokens = jax.random.randint(jax.random.key(1), (batch, kw["seq_len"]), 0, kw["vocab"])
    return params, tokens


def _one_layer(params, kind, index=0):
    return jax.tree.map(lambda leaf: leaf[index], params[kind])


@pytest.fixture(params=[8, None], ids=["blocks-of-8", "one-block-an-expert"])
def expert_block(request, monkeypatch):
    """At 8 rows a block an expert's ~18 picks span several blocks; at the block the experts' shape gives every
    expert fits one."""
    if request.param:
        monkeypatch.setattr(ops_experts, "tile_rows", lambda d, f_in: request.param)
    return request.param


def _reference_log_probs(reference, params, tokens, kw):
    return jax.jit(lambda p: reference.log_probs(p, tokens, None, kw))(params)


def _nll(logp, labels):
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def _worst_gradient_gap(model, reference, kw, params, tokens, cast=IDENTITY):
    """``(loss gap, the worst leaf and its relative gap)``; the bias's gradient is
    exactly zero on both sides."""
    labels = jnp.arange(tokens.shape[0]) * 7 % kw["vocab"]
    ours = jax.jit(jax.value_and_grad(lambda p: _nll(model.apply(jax.tree.map(cast, p), tokens), labels)))
    theirs = jax.jit(jax.value_and_grad(lambda p: _nll(reference.log_probs(p, tokens, None, kw), labels)))
    (loss, got), (want_loss, want) = ours(params), theirs(params)
    assert not got["moe"]["router_bias"].any() and not want["moe"]["router_bias"].any()
    gaps = {jax.tree_util.keystr(path): float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))
            if path[-1].key != "router_bias"}
    return abs(float(loss - want_loss)), max(gaps.items(), key=lambda kv: kv[1])


def test_zoo_tree_is_the_references_tree(reference):
    own = jax.eval_shape(get_model("gated_moe_lm", **SMALL).init, jax.random.key(0))
    ours = jax.eval_shape(lambda: reference.init_params(jax.random.key(0), SMALL))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)))
    assert own["dense"]["w_gate_up"].shape == (1, 64, 320)  # [dense layers, d, 2f]
    assert own["moe"]["w_gate_up"].shape == (3, 4, 64, 48)  # [expert layers, experts held, d, 2f]
    assert own["moe"]["wq"].shape == own["moe"]["wg"].shape == (3, 64, 64)  # the gate is q's size
    assert own["moe"]["wk"].shape == (3, 64, 32) and own["moe"]["wo"].shape == (3, 64, 64)
    assert own["moe"]["norm_q"].shape == own["moe"]["norm_k"].shape == (3, 16)
    assert own["moe"]["router_bias"].shape == (3, 16)
    norms = [name for name in own["moe"] if name.startswith("norm")]
    assert len(norms) == 6 and {"norm_post_attn", "norm_post_mlp"} <= set(norms)


def test_zoo_and_reference_draw_their_leaves_alike(reference):
    """N(0, 1) embeddings, N(0, 0.02) matrices (the projections into the stream among
    them: the out-norms take their scale away), N(0, 0.005) selection bias, norms 1: one
    convention in both files, leaf by leaf."""
    kw = {**SMALL, "width": 128, "vocab": 512, "experts": 64}
    own = get_model("gated_moe_lm", **kw).init(jax.random.key(0))
    ours = reference.init_params(jax.random.key(1), kw)
    want = {"embed": 1.0, "router_bias": 0.005}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own), jax.tree.leaves(ours)):
        name = path[-1].key
        if name.startswith("norm"):
            assert bool((a == 1).all() and (b == 1).all()), name
        else:
            std = want.get(name, 0.02)
            assert float(a.std()) == pytest.approx(std, rel=0.1), name
            assert float(b.std()) == pytest.approx(std, rel=0.1), name


def test_log_probs_loss_and_gradients_match_the_reference_in_float32(reference, expert_block):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("gated_moe_lm", **SMALL)
    got = jax.jit(model.apply)(params, tokens)
    assert got.shape == (3, SMALL["vocab"])
    np.testing.assert_allclose(got, _reference_log_probs(reference, params, tokens, SMALL), atol=1e-5)
    loss_gap, (leaf, gap) = _worst_gradient_gap(model, reference, SMALL, params, tokens)
    assert loss_gap < 1e-5 and gap < 1e-4, (loss_gap, leaf, gap)


def test_bfloat16_compute_stays_near_the_float32_reference(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("gated_moe_lm", **SMALL)
    low = jax.jit(model.apply)(jax.tree.map(lambda p: p.astype(jnp.bfloat16), params), tokens)
    assert low.dtype == jnp.float32 and bool(jnp.isfinite(low).all())
    assert float(jnp.abs(low - _reference_log_probs(reference, params, tokens, SMALL)).max()) < 0.1
    _, (leaf, gap) = _worst_gradient_gap(model, reference, SMALL, params, tokens,
                                         cast=lambda p: p.astype(jnp.bfloat16))
    assert gap < 0.1, (leaf, gap)


def test_on_the_kernels_path_with_eight_heads_a_group_under_a_window_that_binds(reference):
    """512 positions: attention runs in ``ops.attention``'s kernels (the interpreter), the
    sliding layers' under a window of 200, the full layer's without."""
    params, tokens = _seeded(reference, KERNELS, batch=2)
    model = get_model("gated_moe_lm", **KERNELS)
    text = str(jax.make_jaxpr(model.apply)(params, tokens)) + "\n"
    assert "name=causal_attention_fwd_window\n" in text and "name=causal_attention_fwd\n" in text
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens),
                               _reference_log_probs(reference, params, tokens, KERNELS), atol=2e-5)
    loss_gap, (leaf, gap) = _worst_gradient_gap(model, reference, KERNELS, params, tokens)
    assert loss_gap < 2e-5 and gap < 2e-4, (loss_gap, leaf, gap)


def test_the_bias_moves_picks_and_takes_a_gradient_of_exactly_zero(reference):
    """Leaving the bias out would show: the seeded bias changes picks, the program follows
    the reference with it and without, and no step moves it."""
    params, tokens = _seeded(reference, SMALL)
    model = get_model("gated_moe_lm", **SMALL)
    without = {**params, "moe": {**params["moe"], "router_bias": jnp.zeros_like(params["moe"]["router_bias"])}}
    layer, plain = _one_layer(params, "moe"), _one_layer(without, "moe")
    h = jax.random.normal(jax.random.key(2), (40, SMALL["width"]))
    route = lambda p: experts.sigmoid_route(p["router"], h, 3, 2.826, bias=p["router_bias"])
    assert bool((route(layer)[0] != route(plain)[0]).any())
    apply = jax.jit(model.apply)
    assert float(jnp.abs(apply(params, tokens) - apply(without, tokens)).max()) > 1e-4
    np.testing.assert_allclose(apply(without, tokens),
                               _reference_log_probs(reference, without, tokens, SMALL), atol=1e-5)
    grad = jax.grad(lambda p: model.apply(p, tokens).sum())(params)
    assert not grad["moe"]["router_bias"].any() and bool(grad["moe"]["router"].any())


def test_a_full_layer_has_no_positional_term_and_a_sliding_layer_sees_its_window_alone(reference):
    """A full layer's answer at a position is a function of the SET of tokens at or before
    it: move the earlier tokens among themselves and it stays.  A sliding layer turns
    queries and keys by their positions, so the same move inside its window shows; and it
    sees nothing at or beyond ``window`` positions back, whatever stands there."""
    params, _ = _seeded(reference, SMALL)
    layer = _one_layer(params, "moe")
    cfg = dict(SMALL)
    u = jax.random.normal(jax.random.key(3), (2, 32, SMALL["width"]))
    attend = lambda u, sliding: gated_moe.gated_attention(layer, u, cfg, sliding=sliding)
    moved = u.at[:, :31].set(u[:, :31][:, ::-1])  # the first 31 positions, reversed
    np.testing.assert_allclose(attend(moved, False)[:, 31], attend(u, False)[:, 31], atol=1e-6)
    assert float(jnp.abs(attend(moved, True)[:, 31] - attend(u, True)[:, 31]).max()) > 1e-3
    # Window 8: position 31 reads keys 24..31, and nothing of 0..23.
    other = u.at[:, :24].set(jax.random.normal(jax.random.key(4), (2, 24, SMALL["width"])))
    np.testing.assert_array_equal(attend(other, True)[:, 31], attend(u, True)[:, 31])
    assert float(jnp.abs(attend(other, False)[:, 31] - attend(u, False)[:, 31]).max()) > 1e-3
    assert float(jnp.abs(attend(other.at[:, 24].add(1.0), True)[:, 31] - attend(u, True)[:, 31]).max()) > 1e-4
    # Rotary positions are relative: no rotation at a query's own key, so position 0 of a
    # sliding layer (one key: itself) answers as the full layer's does.
    np.testing.assert_allclose(attend(u, True)[:, 0], attend(u, False)[:, 0], atol=1e-6)
    for sliding in (True, False):
        np.testing.assert_allclose(attend(u, sliding), reference.attention_branch(
            layer, u, SMALL, IDENTITY, sliding), atol=1e-6)


def test_at_a_gate_of_zero_the_output_projection_reads_half_the_kernels_output(reference):
    """``sigmoid(0) = 1/2`` in every head dimension: with ``W_g = 0`` the attention branch
    is exactly half of what it is with no gate at all, and the out-norm, which takes a
    branch's scale away, hands the stream what it would without a gate."""
    params, _ = _seeded(reference, SMALL)
    layer = _one_layer(params, "moe")
    open_ = {**layer, "wg": jnp.zeros_like(layer["wg"])}
    cfg = dict(SMALL)
    u = jax.random.normal(jax.random.key(5), (2, 32, SMALL["width"]))
    for sliding in (True, False):
        ungated = reference.attended(layer, u, SMALL, IDENTITY, sliding) @ layer["wo"]
        half = gated_moe.gated_attention(open_, u, cfg, sliding=sliding)
        np.testing.assert_allclose(half, 0.5 * ungated, rtol=1e-5, atol=1e-7)
        assert float(jnp.abs(gated_moe.gated_attention(layer, u, cfg, sliding=sliding) - half).max()) > 1e-3
    x = jax.random.normal(jax.random.key(6), (2, 32, SMALL["width"]))
    out, _ = gated_moe.decoder_layer(open_, x, cfg, dense=False, sliding=True)
    # Norm(y / 2; eps) is Norm(y; 4 eps): the ungated branch under four times the eps.
    normed = reference._rms_norm(layer["norm_post_attn"], reference.attended(
        layer, reference._rms_norm(layer["norm_in"], x, SMALL["eps"]), SMALL, IDENTITY, True) @ layer["wo"],
        4 * SMALL["eps"])
    after = x + normed
    m = reference.feed_forward(layer, reference._rms_norm(layer["norm_pre_mlp"], after, SMALL["eps"]),
                               SMALL, IDENTITY, False)
    want = after + reference._rms_norm(layer["norm_post_mlp"], m, SMALL["eps"])
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_each_branch_is_normed_going_in_and_coming_out(reference):
    """The residual stream is never what a product reads or writes: scale the stream and
    the branches' contributions do not scale with it, and each out-norm's weight scales
    its own branch's contribution alone."""
    params, _ = _seeded(reference, SMALL)
    layer = _one_layer(params, "moe")
    cfg = dict(SMALL)
    x = jax.random.normal(jax.random.key(6), (2, 32, SMALL["width"]))
    run = lambda p, x: gated_moe.decoder_layer(p, x, cfg, dense=False, sliding=False)[0]
    added = run(layer, x) - x
    rms = lambda a: float(jnp.sqrt(jnp.mean(a * a)))
    assert rms(added) == pytest.approx(rms(run(layer, 100.0 * x) - 100.0 * x), rel=0.05)
    # Twice the weight of the attention branch's out-norm: twice that branch, to the letter.
    eps = SMALL["eps"]
    branch = reference._rms_norm(layer["norm_post_attn"], reference.attention_branch(
        layer, reference._rms_norm(layer["norm_in"], x, eps), SMALL, IDENTITY, False), eps)
    doubled = {**layer, "norm_post_attn": 2.0 * layer["norm_post_attn"]}
    after = x + 2.0 * branch
    m = reference.feed_forward(layer, reference._rms_norm(layer["norm_pre_mlp"], after, eps), SMALL, IDENTITY, False)
    np.testing.assert_allclose(run(doubled, x), after + reference._rms_norm(layer["norm_post_mlp"], m, eps), atol=1e-5)


def test_the_embedding_is_scaled_by_the_root_of_the_width(reference):
    params, tokens = _seeded(reference, SMALL)
    deep = {**SMALL, "sliding_layout": [1], "dense_layers": 1}
    one = {**params, "moe": jax.tree.map(lambda a: a[:0], params["moe"])}  # no expert layer
    hidden, _ = gated_moe.hidden_states(one, tokens, {**deep, "sliding_layout": (1,)})
    x0 = params["embed"][tokens] * 8.0  # sqrt(64)
    want = reference.layer(_one_layer(params, "dense"), x0, deep, IDENTITY, True, True)
    np.testing.assert_allclose(hidden, want, atol=1e-5)
    np.testing.assert_allclose(reference.hidden_states(one, tokens, deep), want, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(reference, expert_block):
    """The deployment's cut at a small width: sixteen chips of 8 experts each, 8 picks of
    128.  What the second branch makes BEFORE its out-norm is where the shares add up (a
    deployment's exchange sums them there): the routed parts all sixteen shares give, with
    the shared expert, which every chip computes alike, counted once, add up to what the
    uncut reference gives; the out-norm of that sum on the attended stream, which every
    chip computes alike too, is the uncut layer.  The dense layer has no share to cut."""
    kw = {**SMALL, "experts": 128, "top_k": 8}
    n_experts, per_chip = kw["experts"], 8
    params, _ = _seeded(reference, {**kw, "experts_held": n_experts})
    whole = _one_layer(params, "moe")
    whole = {**whole, "router": 25.0 * whole["router"]}  # scores spread over (0, 1)
    x = jax.random.normal(jax.random.key(7), (2, 16, kw["width"]))
    eps = kw["eps"]
    uncut_kw = {**kw, "first_expert": 0, "experts_held": n_experts}
    uncut = reference.layer(whole, x, uncut_kw, IDENTITY, False, True)
    # What every chip computes alike: the attention branch with its two norms, the norm
    # the second branch reads through, and the shared expert.
    attended = x + reference._rms_norm(whole["norm_post_attn"], reference.attention_branch(
        whole, reference._rms_norm(whole["norm_in"], x, eps), kw, IDENTITY, True), eps)
    h = reference._rms_norm(whole["norm_pre_mlp"], attended, eps)
    shared = reference.shared_expert(whole, h, IDENTITY)
    total, landed = shared, 0.0
    for chip in range(n_experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_gate_up": whole["w_gate_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**kw, "first_expert": first, "experts_held": per_chip}
        m, counted = gated_moe.feed_forward(share, h, cfg, dense=False)
        np.testing.assert_allclose(m, reference.feed_forward(share, h, cfg, IDENTITY, False), atol=1e-5)
        out, _ = gated_moe.decoder_layer(share, x, cfg, dense=False, sliding=True)
        np.testing.assert_allclose(out, reference.layer(share, x, cfg, IDENTITY, False, True), atol=1e-5)
        total, landed = total + (m - shared), landed + float(counted[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total, reference.feed_forward(whole, h, uncut_kw, IDENTITY, False), atol=2e-5)
    np.testing.assert_allclose(attended + reference._rms_norm(whole["norm_post_mlp"], total, eps),
                               uncut, atol=2e-5)
    assert float(jnp.abs(total - m).max()) > 1e-3  # one chip alone is a cut
    dense = _one_layer(params, "dense")
    out, counted = gated_moe.decoder_layer(dense, x, dict(kw), dense=True, sliding=True)
    np.testing.assert_allclose(out, reference.layer(dense, x, kw, IDENTITY, True, True), atol=1e-5)
    assert not counted.any()


def test_the_whole_stack_is_causal(reference):
    params, tokens = _seeded(reference, SMALL)
    cfg = {**SMALL, "sliding_layout": tuple(SMALL["sliding_layout"])}
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % SMALL["vocab"])
    before, _ = gated_moe.hidden_states(params, tokens, cfg)
    after, _ = gated_moe.hidden_states(params, changed, cfg)
    np.testing.assert_array_equal(before[:, :20], after[:, :20])
    assert float(jnp.abs(before[:, 20:] - after[:, 20:]).max()) > 1e-3


def test_counters_are_the_mean_over_the_expert_layers(reference):
    params, tokens = _seeded(reference, SMALL)
    _, counters = get_model("gated_moe_lm", **SMALL).apply.with_counters(params, tokens)
    assert tuple(counters) == experts.COUNTERS == gated_moe.COUNTERS
    assert 0.1 < float(counters["moe_held_pick_share"]) < 0.5  # 4 of 16 held: 0.25 if uniform
    assert 1.0 <= float(counters["moe_load_max_over_mean"]) <= SMALL["experts_held"]
    one_block_each = float(counters["moe_held_pick_share"]) * 96 * 3 / (4 * DEFAULT_BLOCK)
    assert one_block_each * 0.999 <= float(counters["moe_block_fill"]) <= 4 * one_block_each
    all_dense = get_model("gated_moe_lm", **{**SMALL, "sliding_layout": [1, 0], "dense_layers": 2})
    assert not hasattr(all_dense.apply, "with_counters")


def test_factory_refuses_what_it_cannot_build():
    for bad in ({"head_dim": 7}, {"sliding_layout": []}, {"dense_layers": 5}, {"first_expert": 14},
                {"top_k": 17}, {"window": 0}, {"attn_heads": 3}, {"dense_layers": -1}):
        with pytest.raises(ValueError):
            get_model("gated_moe_lm", **{**SMALL, **bad})


def test_trains_through_the_round_program_with_its_counters():
    model = get_model("gated_moe_lm", **SMALL)
    mesh = make_mesh(devices=jax.devices()[:1])
    training = TrainingConfig(batch_size=2, local_epochs=1, learning_rate=0.01)
    strategy = fedavg_strategy()
    params = model.init(jax.random.key(0))
    step = build_round_step(model.apply, training, mesh, strategy, client_chunk=1, params_like=params)
    k = jax.random.split(jax.random.key(5), 2)
    data = ClientData(x=jax.random.randint(k[0], (2, 4, SMALL["seq_len"]), 0, SMALL["vocab"]),
                      y=jax.random.randint(k[1], (2, 4), 0, SMALL["vocab"]), mask=jnp.ones((2, 4)))
    result = step(params, init_server_state(strategy, params), data, jnp.full((2,), 4.0),
                  jax.random.split(jax.random.key(6), 2))
    assert set(result.metrics) == {"loss", "accuracy", "samples", "participating_clients",
                                   *gated_moe.COUNTERS}
    assert 0.1 < float(result.metrics["moe_held_pick_share"]) < 0.5
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), result.params, params)
    bias = moved["moe"].pop("router_bias")
    assert bias == 0.0  # no gradient, so no step: the bias's own update rule is not built
    assert all(v > 0 for v in jax.tree.leaves(moved))  # every other leaf learns, the gate too


@pytest.fixture(scope="module")
def lowered_gradient(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("gated_moe_lm", **SMALL)
    return jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum())).lower(params).as_text(debug_info=True)


def test_the_scopes_are_in_the_lowered_program(lowered_gradient):
    for scope in ("attention_proj", "attention_gate", "rope", "attention_window", "attention_full",
                  "dense_mlp", "moe_router", "moe_shared", "moe_dispatch", "moe_experts",
                  "layer_scan", "token_embed", "lm_head"):
        assert scope in lowered_gradient, scope


@pytest.mark.parametrize("path", [
    # The gate's product and its sigmoid-multiply: forward, again in the backward pass's
    # recomputation (the checkpoint keeps the kernels' output, which comes before the
    # gate), and backward.
    "jvp(layer_scan)/attention_gate/dot_general", "jvp(layer_scan)/attention_gate/logistic",
    "rematted_computation/attention_gate/dot_general", "rematted_computation/attention_gate/logistic",
    "jvp(layer_scan)/checkpoint/attention_gate/dot_general", "jvp(layer_scan)/checkpoint/attention_gate/mul",
    "jvp(token_embed)/mul", "transpose(jvp(token_embed))/",
    "checkpoint/attention_proj/dot_general", "rematted_computation/attention_proj/dot_general",
    "jvp(lm_head)/dot_general", "transpose(jvp(lm_head))/dot_general",
])
def test_the_gate_the_scale_and_the_projections_have_scopes(lowered_gradient, path):
    assert path in lowered_gradient, path
