"""The latent-attention / mixture-of-experts decoder (``models.latent_moe``) against its
plain reference (``benchmark/reference/deepseek_v3.py``) at a small size on the CPU.

In float32 both sides compute the same real numbers, and what is left is the order of the
sums (sorted rows in blocks against dense products an expert, a fused ``W_gate | W_up``
product against two, blockwise softmax against whole rows): a few ulps amplified through
three layers — hence 1e-5 on log-probabilities and 1e-4 relative on a leaf's gradient,
far under anything a missing term would give.  In bfloat16 (the cell's compute precision)
every product rounds at 2**-8: log-probabilities agree to 0.1 and a leaf's gradient to a
tenth of its norm, which a dropped term (the bias left out, the rotation off, a key a
head) passes several times over — each of those is also tested on its own below."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import experts, get_model, hybrid, latent_moe
from nanofed_tpu.ops import experts as ops_experts
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig

#: The layout's block for experts as small as the tests': the largest row tile.
DEFAULT_BLOCK = ops_experts.TILES[0]

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "width": 64, "heads": 4, "latent_rank": 32, "nope_dim": 16,
    "rope_dim": 8, "value_dim": 16, "rope_theta": 50000, "dense_layers": 1, "dense_width": 160,
    "expert_layers": 2, "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 24, "shared_width": 48, "routed_scale": 2.446, "eps": 1e-5,
}
#: Long enough for ``ops.attention``'s kernels (the interpreter here): 24-wide scores
#: over 16-wide values.
KERNELS = {**SMALL, "seq_len": 512, "expert_layers": 1}
IDENTITY = lambda t: t


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v3_reference", REPO / "benchmark" / "reference" / "deepseek_v3.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(reference, kw, batch=3):
    """The reference's seeded tree with the embeddings brought down to N(0, 0.02) and the
    projections into the stream up to it, and a selection bias as large as the scores'
    spread: there the attention, MLP and expert branches outweigh the embeddings in the
    residual stream (which is what the benchmark's initialisation avoids, for its
    routing's sake) and the bias decides picks, so a branch computed wrongly shows in the
    log-probabilities and not only in its own gradient."""
    params = reference.init_params(jax.random.key(0), kw)
    into_stream = (2 * (kw["dense_layers"] + kw["expert_layers"])) ** 0.5
    params["embed"] = 0.02 * params["embed"]
    for kind in ("dense", "moe"):
        params[kind] = {name: into_stream * leaf if name in ("wo", "w_down", "shared_down") else leaf
                        for name, leaf in params[kind].items()}
    params["moe"]["router_bias"] = 20.0 * params["moe"]["router_bias"]
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


def _gradients(model, reference, kw, params, tokens, cast=IDENTITY):
    labels = jnp.arange(tokens.shape[0]) * 7 % kw["vocab"]
    got = jax.jit(jax.grad(lambda p: _nll(model.apply(jax.tree.map(cast, p), tokens), labels)))(params)
    want = jax.jit(jax.grad(lambda p: _nll(reference.log_probs(p, tokens, None, kw), labels)))(params)
    return got, want


def _worst_gradient_gap(model, reference, kw, params, tokens, cast=IDENTITY):
    """The worst leaf's relative gap; the bias's gradient is zero on both sides."""
    got, want = _gradients(model, reference, kw, params, tokens, cast)
    assert not got["moe"]["router_bias"].any() and not want["moe"]["router_bias"].any()
    gaps = {jax.tree_util.keystr(path): float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))
            if path[-1].key != "router_bias"}
    return max(gaps.items(), key=lambda kv: kv[1])


def test_zoo_tree_is_the_references_tree(reference):
    own = jax.eval_shape(get_model("latent_moe_lm", **SMALL).init, jax.random.key(0))
    ours = jax.eval_shape(lambda: reference.init_params(jax.random.key(0), SMALL))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)))
    assert own["dense"]["w_gate_up"].shape == (1, 64, 320)  # [dense layers, d, 2f]
    assert own["moe"]["w_gate_up"].shape == (2, 4, 64, 48)  # [expert layers, experts held, d, 2f]
    assert own["moe"]["wq"].shape == (2, 64, 4 * 24) and own["moe"]["wkv_a"].shape == (2, 64, 32 + 8)
    assert own["moe"]["wkv_b"].shape == (2, 32, 4 * 32) and own["moe"]["wo"].shape == (2, 64, 64)
    assert own["moe"]["router_bias"].shape == (2, 16)


def test_zoo_and_reference_draw_their_leaves_alike(reference):
    """N(0, 1) embeddings, N(0, 0.02) matrices, N(0, 0.02 / sqrt(2 layers)) into the
    residual stream, N(0, 0.005) selection bias, norms 1: one convention in both files,
    leaf by leaf."""
    kw = {**SMALL, "width": 128, "vocab": 512, "experts": 64}
    own = get_model("latent_moe_lm", **kw).init(jax.random.key(0))
    ours = reference.init_params(jax.random.key(1), kw)
    into_stream = 0.02 / 6 ** 0.5
    want = {"embed": 1.0, "wo": into_stream, "w_down": into_stream, "shared_down": into_stream,
            "router_bias": 0.005}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own), jax.tree.leaves(ours)):
        name = path[-1].key
        if name.startswith("norm"):
            assert bool((a == 1).all() and (b == 1).all()), name
        else:
            std = want.get(name, 0.02)
            assert float(a.std()) == pytest.approx(std, rel=0.1), name
            assert float(b.std()) == pytest.approx(std, rel=0.1), name


def test_log_probs_and_gradients_match_the_reference_in_float32(reference, expert_block):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("latent_moe_lm", **SMALL)
    got = jax.jit(model.apply)(params, tokens)
    assert got.shape == (3, SMALL["vocab"])
    np.testing.assert_allclose(got, _reference_log_probs(reference, params, tokens, SMALL), atol=1e-5)
    leaf, gap = _worst_gradient_gap(model, reference, SMALL, params, tokens)
    assert gap < 1e-4, (leaf, gap)


def test_bfloat16_compute_stays_near_the_float32_reference(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("latent_moe_lm", **SMALL)
    low = jax.jit(model.apply)(jax.tree.map(lambda p: p.astype(jnp.bfloat16), params), tokens)
    assert low.dtype == jnp.float32 and bool(jnp.isfinite(low).all())
    assert float(jnp.abs(low - _reference_log_probs(reference, params, tokens, SMALL)).max()) < 0.1
    leaf, gap = _worst_gradient_gap(model, reference, SMALL, params, tokens,
                                    cast=lambda p: p.astype(jnp.bfloat16))
    assert gap < 0.1, (leaf, gap)


def test_on_the_kernels_path_with_value_heads_of_another_size(reference):
    """512 positions: attention runs in ``ops.attention``'s kernels (the interpreter), 24-wide
    score heads over 16-wide value heads."""
    params, tokens = _seeded(reference, KERNELS, batch=2)
    model = get_model("latent_moe_lm", **KERNELS)
    assert "name=causal_attention_fwd\n" in str(jax.make_jaxpr(model.apply)(params, tokens)) + "\n"
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens),
                               _reference_log_probs(reference, params, tokens, KERNELS), atol=2e-5)
    leaf, gap = _worst_gradient_gap(model, reference, KERNELS, params, tokens)
    assert gap < 2e-4, (leaf, gap)


def test_a_bias_moves_the_picks_and_not_the_weights(reference):
    """``picks = top_k(p + b)``, weights from ``p``: a bias that throws one expert out of
    every pick leaves each token's OTHER picks with the scores they had (renormalised over
    the new pick set: the ratio of two kept picks' weights is the ratio of their scores),
    and the bias takes no gradient."""
    router = jax.random.normal(jax.random.key(2), (SMALL["width"], SMALL["experts"]))
    x = jax.random.normal(jax.random.key(3), (40, SMALL["width"]))
    scores = jax.nn.sigmoid(x @ router)
    plain = experts.sigmoid_route(router, x, 3, 2.446)
    zero = experts.sigmoid_route(router, x, 3, 2.446, bias=jnp.zeros(SMALL["experts"]))
    np.testing.assert_array_equal(plain[0], zero[0])
    np.testing.assert_array_equal(plain[1], zero[1])
    out = int(plain[0][0, 0])  # token 0's first pick, pushed out for everyone
    picks, weights = experts.sigmoid_route(router, x, 3, 2.446,
                                           bias=jnp.zeros(SMALL["experts"]).at[out].set(-10.0))
    assert not bool((picks == out).any()) and bool((plain[0] == out).any())
    np.testing.assert_array_equal(picks, jnp.argsort(-scores.at[:, out].set(-1.0), axis=-1)[:, :3])
    picked = jnp.take_along_axis(scores, picks, axis=-1)  # the scores, WITHOUT the bias
    np.testing.assert_allclose(weights, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.446, rtol=1e-6)
    untouched = ~(plain[0] == out).any(axis=-1)  # tokens that never picked it: nothing moves
    np.testing.assert_array_equal(picks[untouched], plain[0][untouched])
    np.testing.assert_array_equal(weights[untouched], plain[1][untouched])
    bias = 0.5 * jax.random.normal(jax.random.key(4), (SMALL["experts"],))
    grad = jax.grad(lambda b: experts.sigmoid_route(router, x, 3, 2.446, bias=b)[1][:, 0].sum())(bias)
    assert not grad.any()


def test_the_model_reads_its_bias_leaf(reference):
    """Leaving the bias out would show: the seeded bias changes picks, and the program
    follows the reference with it and not without."""
    params, tokens = _seeded(reference, SMALL)
    model = get_model("latent_moe_lm", **SMALL)
    without = {**params, "moe": {**params["moe"], "router_bias": jnp.zeros_like(params["moe"]["router_bias"])}}
    apply = jax.jit(model.apply)
    assert float(jnp.abs(apply(params, tokens) - apply(without, tokens)).max()) > 1e-4
    np.testing.assert_allclose(apply(without, tokens),
                               _reference_log_probs(reference, without, tokens, SMALL), atol=1e-5)


def test_one_rotary_key_serves_all_heads_on_the_rope_dimensions_alone(reference):
    """The keys the program hands attention: ``rope_dim`` of every head's ``nope + rope``
    score dimensions are ONE vector, rotated, the same for every head (a key a head would
    need ``heads * rope_dim`` columns of ``W_kv_a``); the other ``nope`` differ by head and
    are not rotated."""
    params, tokens = _seeded(reference, SMALL)
    layer = _one_layer(params, "moe")
    u = reference._rms_norm(layer["norm_in"], params["embed"][tokens], SMALL["eps"])
    keys, values = reference.keys_and_values(layer, u, SMALL, IDENTITY)
    nope, rope = SMALL["nope_dim"], SMALL["rope_dim"]
    assert keys.shape == (3, 32, 4, nope + rope) and values.shape == (3, 32, 4, 16)
    assert layer["wkv_a"].shape[-1] == SMALL["latent_rank"] + rope  # one key's columns, not four
    for head in range(1, 4):
        np.testing.assert_array_equal(keys[:, :, head, nope:], keys[:, :, 0, nope:])
        assert float(jnp.abs(keys[:, :, head, :nope] - keys[:, :, 0, :nope]).max()) > 1e-3
    # The program's attention is the reference's with exactly these keys ...
    cfg = dict(SMALL)
    np.testing.assert_allclose(latent_moe.latent_attention(layer, u, cfg),
                               reference._attention(layer, u, SMALL, IDENTITY), atol=1e-6)
    # ... position 0 is not turned, later positions are, and only through rope_dim columns:
    raw = u @ layer["wkv_a"]
    np.testing.assert_allclose(keys[:, 0, 0, nope:], raw[:, 0, SMALL["latent_rank"]:], atol=1e-6)
    assert float(jnp.abs(keys[:, 5, 0, nope:] - raw[:, 5, SMALL["latent_rank"]:]).max()) > 1e-3
    # a test that fails if each head gets its own key: give head 1 another and the answer moves.
    other = keys.at[:, :, 1, nope:].set(jnp.roll(keys[:, :, 1, nope:], 1, axis=1))
    assert float(jnp.abs(other - keys).max()) > 1e-3


def test_the_rotation_touches_the_rope_dimensions_of_the_scores_alone(reference):
    """Attention's answer depends on positions through ``rope_dim`` of the ``nope + rope``
    score dimensions: with the rotary columns of ``W_q`` zeroed the scores are the
    no-position part alone, and the layer answers as if theta turned nothing."""
    params, tokens = _seeded(reference, SMALL)
    layer = _one_layer(params, "moe")
    u = reference._rms_norm(layer["norm_in"], params["embed"][tokens], SMALL["eps"])
    nope, rope, h = SMALL["nope_dim"], SMALL["rope_dim"], SMALL["heads"]
    columns = jnp.arange(h * (nope + rope)).reshape(h, nope + rope)
    no_pe = {**layer, "wq": layer["wq"].at[:, columns[:, nope:].reshape(-1)].set(0.0)}
    turned = latent_moe.latent_attention(no_pe, u, dict(SMALL))
    still = latent_moe.latent_attention(no_pe, u, {**SMALL, "rope_theta": 1.0})
    np.testing.assert_allclose(turned, still, atol=1e-6)
    with_pe = latent_moe.latent_attention(layer, u, dict(SMALL))
    assert float(jnp.abs(with_pe - latent_moe.latent_attention(layer, u, {**SMALL, "rope_theta": 1.0})).max()) > 1e-4
    assert rope * 3 == nope + rope  # 8 of 24 here; 64 of 192 at the published widths


def test_the_shares_add_up_to_the_uncut_layer(reference, expert_block):
    """The deployment's cut at a small width: eight chips of 8 experts each, 6 picks of 64.
    The routed parts all eight shares give add up to what the uncut reference gives for the
    whole layer; attention and the shared experts (what every chip computes alike) are
    counted once.  The dense layer has no share to cut: every chip's is the reference's."""
    kw = {**SMALL, "dense_layers": 1, "expert_layers": 1, "experts": 64, "top_k": 6}
    n_experts, per_chip = kw["experts"], 8
    params, _ = _seeded(reference, {**kw, "experts_held": n_experts})
    whole = _one_layer(params, "moe")
    whole = {**whole, "router": 25.0 * whole["router"]}  # scores spread over (0, 1)
    x = jax.random.normal(jax.random.key(7), (2, 16, kw["width"]))
    uncut = reference.layer(whole, x, {**kw, "first_expert": 0, "experts_held": n_experts},
                            IDENTITY, False)
    # What every chip computes alike: the attention block and the shared experts.
    attended = x + reference._attention(whole, reference._rms_norm(whole["norm_in"], x, kw["eps"]),
                                        kw, IDENTITY)
    h = reference._rms_norm(whole["norm_post"], attended, kw["eps"])
    alike = attended + reference._gated_mlp(whole["shared_gate_up"], whole["shared_down"], h, IDENTITY)
    total, landed = alike, 0.0
    for chip in range(n_experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_gate_up": whole["w_gate_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**kw, "first_expert": first, "experts_held": per_chip}
        out, counted = latent_moe.decoder_layer(share, x, cfg, dense=False)
        np.testing.assert_allclose(out, reference.layer(share, x, cfg, IDENTITY, False), atol=1e-5)
        total, landed = total + (out - alike), landed + float(counted[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(total - out).max()) > 1e-3  # one chip alone is a cut
    dense = _one_layer(params, "dense")
    out, counted = latent_moe.decoder_layer(dense, x, dict(kw), dense=True)
    np.testing.assert_allclose(out, reference.layer(dense, x, kw, IDENTITY, True), atol=1e-5)
    assert not counted.any()


def test_the_whole_stack_is_causal(reference):
    params, tokens = _seeded(reference, SMALL)
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % SMALL["vocab"])
    before, _ = latent_moe.hidden_states(params, tokens, dict(SMALL))
    after, _ = latent_moe.hidden_states(params, changed, dict(SMALL))
    np.testing.assert_array_equal(before[:, :20], after[:, :20])
    assert float(jnp.abs(before[:, 20:] - after[:, 20:]).max()) > 1e-3


def test_counters_are_the_mean_over_the_expert_layers(reference):
    params, tokens = _seeded(reference, SMALL)
    _, counters = get_model("latent_moe_lm", **SMALL).apply.with_counters(params, tokens)
    assert tuple(counters) == experts.COUNTERS == latent_moe.COUNTERS
    assert 0.1 < float(counters["moe_held_pick_share"]) < 0.5  # 4 of 16 held: 0.25 if uniform
    assert 1.0 <= float(counters["moe_load_max_over_mean"]) <= SMALL["experts_held"]
    # 96 tokens x 3 picks x ~1/4 land here, four experts, one block each that got a pick
    # (under the test's large bias one may get none); the dense layer counts nothing and
    # is not in the mean.
    one_block_each = float(counters["moe_held_pick_share"]) * 96 * 3 / (4 * DEFAULT_BLOCK)
    assert one_block_each * 0.999 <= float(counters["moe_block_fill"]) <= 4 * one_block_each
    assert not hasattr(get_model("latent_moe_lm", **{**SMALL, "expert_layers": 0}).apply, "with_counters")


def test_swiglu_backward_is_autodiffs():
    pre = jax.random.normal(jax.random.key(5), (7, 24))
    d_hidden = jax.random.normal(jax.random.key(6), (7, 12))
    hidden, pull = experts.SWIGLU.with_grad(pre)
    want_hidden, vjp = jax.vjp(experts.SWIGLU.apply, pre)
    np.testing.assert_array_equal(hidden, want_hidden)
    np.testing.assert_allclose(hidden, jax.nn.silu(pre[:, :12]) * pre[:, 12:], rtol=1e-6)
    np.testing.assert_allclose(pull(d_hidden), vjp(d_hidden)[0], rtol=1e-5, atol=1e-6)


def test_the_hybrid_routes_through_the_shared_router(monkeypatch):
    """One sigmoid router in the zoo: what ``hybrid.routed_experts`` hands the held experts
    is ``experts.sigmoid_route`` with no bias, and that is the arithmetic the hybrid's own
    router had (written out here as it stood)."""
    kw = {"top_k": 3, "routed_scale": 2.5, "first_expert": 0}
    router = jax.random.normal(jax.random.key(8), (32, 16))
    x = jax.random.normal(jax.random.key(9), (40, 32))
    handed = {}

    def held_experts(x, picks, weights, *_, **__):
        handed.update(picks=picks, weights=weights)
        return x, jnp.zeros((3,))

    monkeypatch.setattr(hybrid, "held_experts", held_experts)
    hybrid.routed_experts({"router": router, "w_up": None, "w_down": None}, x, kw)
    picks, weights = handed["picks"], handed["weights"]
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=jax.lax.Precision.HIGHEST))
    top, want = jax.lax.top_k(scores, 3)
    np.testing.assert_array_equal(picks, want)
    np.testing.assert_array_equal(weights, 2.5 * top / (top.sum(axis=-1, keepdims=True) + 1e-20))
    shared = experts.sigmoid_route(router, x, 3, 2.5)
    np.testing.assert_array_equal(picks, shared[0])
    np.testing.assert_array_equal(weights, shared[1])


def test_factory_refuses_what_it_cannot_build():
    for bad in ({"rope_dim": 7}, {"dense_layers": 0, "expert_layers": 0}, {"first_expert": 14},
                {"top_k": 17}, {"value_dim": 0}, {"expert_layers": -1}):
        with pytest.raises(ValueError):
            get_model("latent_moe_lm", **{**SMALL, **bad})


def test_trains_through_the_round_program_with_its_counters():
    model = get_model("latent_moe_lm", **SMALL)
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
                                   *latent_moe.COUNTERS}
    assert 0.1 < float(result.metrics["moe_held_pick_share"]) < 0.5
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), result.params, params)
    bias = moved["moe"].pop("router_bias")
    assert bias == 0.0  # no gradient, so no step: the bias's own update rule is not built
    assert all(v > 0 for v in jax.tree.leaves(moved))  # every other leaf learns, the router too


@pytest.fixture(scope="module")
def lowered_gradient(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("latent_moe_lm", **SMALL)
    return jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum())).lower(params).as_text(debug_info=True)


def test_the_scopes_are_in_the_lowered_program(lowered_gradient):
    for scope in ("mla_q", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_attention", "dense_mlp",
                  "moe_router", "moe_shared", "moe_dispatch", "moe_experts"):
        assert scope in lowered_gradient, scope


@pytest.mark.parametrize("path", [
    "jvp(token_embed)/", "transpose(jvp(token_embed))/",
    "jvp(layer_scan)/squeeze", "transpose(jvp(layer_scan))/",  # the stacked leaves' slices
    "checkpoint/attention_proj/dot_general", "rematted_computation/attention_proj/dot_general",
    "jvp(lm_head)/dot_general", "transpose(jvp(lm_head))/dot_general",
])
def test_the_output_projection_the_lookup_and_the_head_have_scopes(lowered_gradient, path):
    assert path in lowered_gradient, path
