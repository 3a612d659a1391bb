"""The windowed / full mixture-of-experts decoder (``models.moe_decoder``) against its plain
reference (``benchmark/reference/smallthinker.py``) at a small size on the CPU.

In float32 both sides compute the same real numbers, and what is left is the order of the
sums (sorted rows in blocks against dense products an expert, blockwise softmax against
whole rows): a few ulps amplified through four layers — hence 1e-5 on log-probabilities
and 1e-4 relative on a leaf's gradient, far under anything a missing term would give.  In
bfloat16 (the cell's compute precision) every product rounds at 2**-8: log-probabilities
agree to 0.1 and a leaf's gradient to a tenth of its norm, which a dropped term (a
window ignored, rotation off, the router reading the wrong tensor) passes several times
over — each of those is also tested on its own below."""

import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu import nn
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.models import decoder, experts, get_model, hybrid, moe_decoder
from nanofed_tpu.ops import attention
from nanofed_tpu.ops import experts as ops_experts
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig

#: The layout's block for experts as small as the tests': the largest row tile.
DEFAULT_BLOCK = ops_experts.TILES[0]

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "width": 64, "rope_layout": [0, 1, 1, 1],
    "window_layout": [0, 1, 1, 1], "window": 8, "rope_theta": 1500000,
    "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "experts": 16, "first_expert": 0,
    "experts_held": 4, "top_k": 3, "expert_width": 48, "eps": 1e-6,
}
#: Long enough for ``ops.attention``'s kernels (the interpreter here), seven heads a group.
KERNELS = {**SMALL, "seq_len": 512, "window": 200, "attn_heads": 7, "kv_heads": 1,
           "rope_layout": [0, 1], "window_layout": [0, 1]}
IDENTITY = lambda t: t
#: The four kinds of layer the two layouts can name.
KINDS = {"full-nope": (0, 0), "full-rope": (1, 0), "window-nope": (0, 1), "window-rope": (1, 1)}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference", REPO / "benchmark" / "reference" / "smallthinker.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(reference, kw, batch=3):
    """The reference's seeded tree with every matrix brought to N(0, 0.02): there the
    attention and expert branches outweigh the embeddings in the residual stream (which
    is what the benchmark's initialisation avoids, for its routing's sake), so a branch
    computed wrongly shows in the log-probabilities and not only in its own gradient."""
    params = reference.init_params(jax.random.key(0), kw)
    into_stream = (2 * len(kw["rope_layout"])) ** 0.5
    params["embed"] = 0.02 * params["embed"]
    params["layers"] = {**params["layers"], "wo": into_stream * params["layers"]["wo"],
                        "w_down": into_stream * params["layers"]["w_down"]}
    tokens = jax.random.randint(jax.random.key(1), (batch, kw["seq_len"]), 0, kw["vocab"])
    return params, tokens


@pytest.fixture(params=[8, None], ids=["blocks-of-8", "one-block-an-expert"])
def expert_block(request, monkeypatch):
    """At 8 rows a block an expert's ~18 picks span several blocks; at the block the experts' shape gives every
    expert fits one."""
    if request.param:
        monkeypatch.setattr(ops_experts, "tile_rows", lambda d, f_in: request.param)
    return request.param


def _nll(logp, labels):
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def _worst_gradient_gap(model, reference, kw, params, tokens, cast=IDENTITY):
    labels = jnp.arange(tokens.shape[0]) * 7 % kw["vocab"]
    got = jax.grad(lambda p: _nll(model.apply(jax.tree.map(cast, p), tokens), labels))(params)
    want = jax.grad(lambda p: _nll(reference.log_probs(p, tokens, None, kw), labels))(params)
    gaps = {jax.tree_util.keystr(path): float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))}
    return max(gaps.items(), key=lambda kv: kv[1])


def test_zoo_tree_is_the_references_tree(reference):
    own = jax.eval_shape(get_model("moe_decoder_lm", **SMALL).init, jax.random.key(0))
    ours = jax.eval_shape(lambda: reference.init_params(jax.random.key(0), SMALL))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)))
    assert own["layers"]["w_gate_up"].shape == (4, 4, 64, 96)  # [layers, experts, d, 2f]


def test_zoo_and_reference_draw_their_leaves_alike(reference):
    """N(0, 1) embeddings, N(0, 0.02) matrices, N(0, 0.02 / sqrt(2 layers)) into the
    residual stream, norms 1: one convention in both files, leaf by leaf."""
    kw = {**SMALL, "width": 128, "vocab": 512}
    own = get_model("moe_decoder_lm", **kw).init(jax.random.key(0))
    ours = reference.init_params(jax.random.key(1), kw)
    want = {"embed": 1.0, "wo": 0.02 / 8 ** 0.5, "w_down": 0.02 / 8 ** 0.5}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own), jax.tree.leaves(ours)):
        name = path[-1].key
        if name.startswith("norm"):
            assert bool((a == 1).all() and (b == 1).all()), name
        else:
            std = want.get(name, 0.02)
            assert float(a.std()) == pytest.approx(std, rel=0.05), name
            assert float(b.std()) == pytest.approx(std, rel=0.05), name


def test_log_probs_and_gradients_match_the_reference_in_float32(reference, expert_block):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("moe_decoder_lm", **SMALL)
    got = model.apply(params, tokens)
    assert got.shape == (3, SMALL["vocab"])
    np.testing.assert_allclose(got, reference.log_probs(params, tokens, None, SMALL), atol=1e-5)
    leaf, gap = _worst_gradient_gap(model, reference, SMALL, params, tokens)
    assert gap < 1e-4, (leaf, gap)


def test_gradient_is_the_same_with_the_embedding_gradient_in_bands(reference, monkeypatch):
    """At a width of two lane tiles and a budget of one, ``nn.embed_rows`` accumulates the
    table's gradient band by band: every leaf's gradient is what one band gives."""
    kw = {**SMALL, "width": 256}
    params, tokens = _seeded(reference, kw)
    model = get_model("moe_decoder_lm", **kw)
    grads = lambda: jax.grad(lambda p: model.apply(p, tokens)[:, 5].sum())(params)
    whole = grads()
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", kw["vocab"] * 128 * 4)
    assert nn.embed_bands(kw["vocab"], 256, 4) == 2
    jax.tree.map(np.testing.assert_array_equal, grads(), whole)


def test_bfloat16_compute_stays_near_the_float32_reference(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("moe_decoder_lm", **SMALL)
    low = model.apply(jax.tree.map(lambda p: p.astype(jnp.bfloat16), params), tokens)
    assert low.dtype == jnp.float32 and bool(jnp.isfinite(low).all())
    assert float(jnp.abs(low - reference.log_probs(params, tokens, None, SMALL)).max()) < 0.1
    leaf, gap = _worst_gradient_gap(model, reference, SMALL, params, tokens,
                                    cast=lambda p: p.astype(jnp.bfloat16))
    assert gap < 0.1, (leaf, gap)


@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_of_layer_matches_the_reference(reference, kind):
    """One layer of each kind the layouts can name, twice over so that the second reads
    what the first wrote: log-probabilities and every gradient leaf."""
    rope, windowed = KINDS[kind]
    kw = {**SMALL, "rope_layout": [rope, rope], "window_layout": [windowed, windowed]}
    params, tokens = _seeded(reference, kw)
    model = get_model("moe_decoder_lm", **kw)
    np.testing.assert_allclose(model.apply(params, tokens),
                               reference.log_probs(params, tokens, None, kw), atol=1e-5)
    leaf, gap = _worst_gradient_gap(model, reference, kw, params, tokens)
    assert gap < 1e-4, (leaf, gap)


def test_the_kinds_are_four_functions(reference):
    """Rotation and window each change the answer: a flag ignored would not."""
    answers = []
    for rope, windowed in KINDS.values():
        kw = {**SMALL, "rope_layout": [rope] * 2, "window_layout": [windowed] * 2}
        params, tokens = _seeded(reference, kw)
        answers.append(get_model("moe_decoder_lm", **kw).apply(params, tokens))
    for i in range(4):
        for j in range(i):
            assert float(jnp.abs(answers[i] - answers[j]).max()) > 1e-4, (i, j)


def test_on_the_kernels_path_with_groups_of_seven(reference):
    """512 positions: attention runs in ``ops.attention``'s kernels (the interpreter),
    one key/value head for seven query heads, a window that is not whole blocks."""
    params, tokens = _seeded(reference, KERNELS, batch=2)
    model = get_model("moe_decoder_lm", **KERNELS)
    text = str(jax.make_jaxpr(model.apply)(params, tokens))
    assert "causal_attention_fwd_window" in text and "name=causal_attention_fwd\n" in text + "\n"
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens),
                               reference.log_probs(params, tokens, None, KERNELS), atol=2e-5)
    leaf, gap = _worst_gradient_gap(model, reference, KERNELS, params, tokens)
    assert gap < 2e-4, (leaf, gap)


def test_the_router_reads_the_layers_normed_input_not_the_post_attention_state(reference):
    """The picks are made BEFORE attention: a change to the attention's output projection
    moves ``h`` (what the experts read) and must not move a single pick of that layer."""
    kw = {**SMALL, "rope_layout": [1], "window_layout": [1]}
    params, tokens = _seeded(reference, kw)
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    x = params["embed"][tokens]
    cfg = {**kw, "rope_layout": (1,), "window_layout": (1,)}

    def counters(wo):
        return moe_decoder.decoder_layer({**layer, "wo": wo}, x, cfg, rope=True, window=8)

    out_a, counted_a = counters(layer["wo"])
    out_b, counted_b = counters(layer["wo"] * 25.0)
    assert float(jnp.abs(out_a - out_b).max()) > 1e-2  # h did move
    np.testing.assert_array_equal(counted_a, counted_b)  # ... the routing did not
    # And the picks are the reference's, made from u = RMSNorm_in(x).
    u = reference._rms_norm(layer["norm_in"], x, kw["eps"])
    gate = reference.gates(layer["router"], u, kw)[..., :kw["experts_held"]]
    share = float((gate > 0).sum()) / (tokens.size * kw["top_k"])
    assert float(counted_a[0]) == pytest.approx(share, abs=1e-6)
    # A router reading h would pick otherwise here: the test can fail.
    h = reference._rms_norm(layer["norm_post"],
                            x + reference._attention(layer, u, kw, IDENTITY, True, 8), kw["eps"])
    other = reference.gates(layer["router"], h, kw)[..., :kw["experts_held"]]
    assert bool(((gate > 0) != (other > 0)).any())


def test_the_weights_are_a_softmax_over_the_picked_logits(reference):
    router = jax.random.normal(jax.random.key(2), (SMALL["width"], SMALL["experts"]))
    u = jax.random.normal(jax.random.key(3), (10, SMALL["width"]))
    picks, weights = experts.route(router, u, SMALL["top_k"])
    logits = u @ router
    np.testing.assert_array_equal(picks, jnp.argsort(-logits, axis=-1)[:, :SMALL["top_k"]])
    picked = jnp.take_along_axis(logits, picks, axis=-1)
    np.testing.assert_allclose(weights, jnp.exp(picked) / jnp.exp(picked).sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)  # norm_topk_prob: nothing left to do


def test_rotation_is_the_rotate_half_pairing():
    x = jax.random.normal(jax.random.key(4), (1, 6, 2, 8))
    got = decoder.rotate(x, 1.5e6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)  # position 0: no turn
    for i in range(4):  # dimension i pairs with i + 4, angle t * theta^(-i/4)
        angle = jnp.arange(6.0) * 1.5e6 ** (-i / 4)
        a, b = x[0, :, 0, i], x[0, :, 0, i + 4]
        np.testing.assert_allclose(got[0, :, 0, i], a * jnp.cos(angle) - b * jnp.sin(angle), atol=1e-6)
        np.testing.assert_allclose(got[0, :, 0, i + 4], b * jnp.cos(angle) + a * jnp.sin(angle), atol=1e-6)
    # A rotation: norms kept, and q.k depends on the positions' difference alone.
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    same = jnp.broadcast_to(x[:, :1], x.shape)
    turned = decoder.rotate(same, 1.5e6)[0, :, 0]
    np.testing.assert_allclose(turned[1] @ turned[3], turned[2] @ turned[4], rtol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer(reference, expert_block):
    """Four chips of four experts each: the feed-forward parts all the shares give add up
    to what the uncut reference gives for the whole layer; attention (what every chip
    computes alike) is counted once, before them."""
    kw = {**SMALL, "rope_layout": [1], "window_layout": [1]}
    d, f, n_experts, per_chip = kw["width"], kw["expert_width"], kw["experts"], 4
    k = jax.random.split(jax.random.key(3), 9)
    whole = {
        "norm_in": jnp.ones(d), "norm_post": jnp.ones(d),
        "wq": 0.1 * jax.random.normal(k[0], (d, 64)), "wk": 0.1 * jax.random.normal(k[1], (d, 32)),
        "wv": 0.1 * jax.random.normal(k[2], (d, 32)), "wo": 0.1 * jax.random.normal(k[3], (64, d)),
        "router": 0.5 * jax.random.normal(k[4], (d, n_experts)),
        "w_gate_up": 0.2 * jax.random.normal(k[5], (n_experts, d, 2 * f)),
        "w_down": 0.2 * jax.random.normal(k[6], (n_experts, f, d)),
    }
    x = jax.random.normal(k[7], (2, 16, d))
    uncut = reference.layer(whole, x, {**kw, "first_expert": 0, "experts_held": n_experts},
                            IDENTITY, True, 8)
    # What every chip computes alike: the router's picks and the attention block.
    u = reference._rms_norm(whole["norm_in"], x, kw["eps"])
    attended = x + reference._attention(whole, u, kw, IDENTITY, True, 8)
    total, landed = attended, 0.0
    for chip in range(n_experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_gate_up": whole["w_gate_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**kw, "first_expert": first, "experts_held": per_chip}
        out, counted = moe_decoder.decoder_layer(share, x, cfg, rope=True, window=8)
        np.testing.assert_allclose(out, reference.layer(share, x, cfg, IDENTITY, True, 8), atol=1e-5)
        total, landed = total + (out - attended), landed + float(counted[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(total - out).max()) > 1e-2  # one chip alone is a cut


def test_the_whole_stack_is_causal_and_windowed(reference):
    """Causal bit for bit; and through ONE window layer a token's reach ends with the
    window (through the full layer 0 of the real pattern it does not)."""
    params, tokens = _seeded(reference, SMALL)
    cfg = {**SMALL, "rope_layout": (0, 1, 1, 1), "window_layout": (0, 1, 1, 1)}
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % SMALL["vocab"])
    before, _ = moe_decoder.hidden_states(params, tokens, cfg)
    after, _ = moe_decoder.hidden_states(params, changed, cfg)
    np.testing.assert_array_equal(before[:, :20], after[:, :20])
    assert float(jnp.abs(before[:, 20:] - after[:, 20:]).max()) > 1e-3
    one = {**cfg, "rope_layout": (1,), "window_layout": (1,)}
    p = {**params, "layers": jax.tree.map(lambda leaf: leaf[1:2], params["layers"])}
    changed = tokens.at[:, 5].set((tokens[:, 5] + 1) % SMALL["vocab"])
    before, _ = moe_decoder.hidden_states(p, tokens, one)
    after, _ = moe_decoder.hidden_states(p, changed, one)
    moved = np.abs(np.asarray(before - after)).max(axis=(0, 2)) > 0
    assert moved[5:13].all() and not moved[13:].any() and not moved[:5].any()


def test_counters_count_this_models_routing(reference):
    params, tokens = _seeded(reference, SMALL)
    _, counters = get_model("moe_decoder_lm", **SMALL).apply.with_counters(params, tokens)
    assert tuple(counters) == experts.COUNTERS == moe_decoder.COUNTERS
    assert 0.1 < float(counters["moe_held_pick_share"]) < 0.5  # 4 of 16 held: 0.25 if uniform
    assert 1.0 <= float(counters["moe_load_max_over_mean"]) <= SMALL["experts_held"]
    # 96 tokens x 3 picks x ~1/4 land here, four experts, one block each.
    assert float(counters["moe_block_fill"]) == pytest.approx(
        float(counters["moe_held_pick_share"]) * 96 * 3 / (4 * DEFAULT_BLOCK), rel=1e-5)


def test_block_fill_by_hand():
    """Six picks over two held experts, blocks of 4: expert 0 gets 5 rows (two blocks),
    expert 1 gets 1 (one block): 6 rows taken of 12."""
    x = jnp.ones((6, 8))
    picks = jnp.array([[0], [0], [0], [0], [0], [1]], jnp.int32)
    weights = jnp.ones((6, 1))
    w_in, w_out = jnp.ones((2, 8, 6)), jnp.ones((2, 3, 8))
    _, counted = experts.held_experts(x, picks, weights, w_in, w_out, first_expert=0, block=4,
                                      activation=experts.REGLU)
    np.testing.assert_allclose(counted, [1.0, 5 * 2 / 6, 0.5], rtol=1e-6)


def _routed_layer(block):
    """``(layer(x, router, w_in, w_out) -> [40, 32], its arguments)``: three picks of
    eight experts, the first four held, in blocks of ``block`` rows."""
    k = jax.random.split(jax.random.key(11), 4)
    args = (jax.random.normal(k[0], (40, 32)), jax.random.normal(k[1], (32, 8)),
            0.2 * jax.random.normal(k[2], (4, 32, 24)), 0.2 * jax.random.normal(k[3], (4, 12, 32)))

    def layer(x, router, w_in, w_out):
        picks, weights = experts.sigmoid_route(router, 2 * x, 3, 1.0)
        return experts.held_experts(2 * x, picks, weights, w_in, w_out, first_expert=0,
                                    block=block, activation=experts.REGLU)[0]

    return layer, args


def test_a_checkpoint_keeps_the_dispatchs_three_outputs_and_nothing_else(capsys):
    """Beside the checkpoint's inputs: ``src`` (one int32 a row of the layout: 40 tokens'
    3 picks and a block to spare an expert, in whole blocks), ``block_expert`` (one a
    block) and the trip count; not the tokens, the picks or their weights.  A plain
    checkpoint, or one that keeps the attention kernels' names alone, keeps none."""
    layer, args = _routed_layer(block=8)
    rows = 40 * 3 + 4 * 8

    def kept(policy):
        jax.ad_checkpoint.print_saved_residuals(jax.checkpoint(layer, policy=policy), *args)
        lines = capsys.readouterr().out.strip().splitlines()
        assert all("from the argument" in line for line in lines[:len(args)])
        return [line.split()[0] for line in lines[len(args):]]

    assert kept(experts.KEEP_NAMED_OUTPUTS) == [f"i32[{rows}]", f"i32[{rows // 8}]", "i32[]"]
    assert kept(None) == kept(attention.KEEP_KERNEL_OUTPUTS) == []


def test_outside_a_checkpoint_the_dispatchs_names_change_nothing(monkeypatch, equations):
    """``held_experts`` under no checkpoint: value and gradients lower to the same
    StableHLO with the names and without (a name is an equation of the jaxpr and lowers
    to nothing; ``tests/unit/ops/test_attention.py`` says why the symbols' numbers go)."""
    layer, args = _routed_layer(block=8)
    step = lambda: jax.value_and_grad(lambda *a: layer(*a).sum(), argnums=(0, 1, 2, 3))
    lowered = lambda: re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1", jax.jit(step()).lower(*args).as_text())
    names = lambda: [eqn.params["name"] for eqn in equations(step(), *args)
                     if eqn.primitive.name == "name"]
    named = lowered()
    assert tuple(names()) == experts.KEPT
    monkeypatch.setattr(experts, "checkpoint_name", lambda x, name: x)
    assert names() == [] and lowered() == named


@pytest.mark.parametrize("activation,width", [(experts.RELU2, 12), (experts.REGLU, 24)],
                         ids=["relu2", "reglu"])
def test_an_activations_backward_is_autodiffs(activation, width):
    pre = jax.random.normal(jax.random.key(5), (7, width))
    d_hidden = jax.random.normal(jax.random.key(6), (7, 12))
    hidden, pull = activation.with_grad(pre)
    want_hidden, vjp = jax.vjp(activation.apply, pre)
    np.testing.assert_array_equal(hidden, want_hidden)
    np.testing.assert_allclose(pull(d_hidden), vjp(d_hidden)[0], rtol=1e-6, atol=1e-7)


def test_the_hybrid_runs_the_shared_loop():
    """One dispatch and one loop in the zoo: ``hybrid.routed_experts`` is
    ``experts.held_experts`` with the hybrid's router and the squared ReLU."""
    kw = {"experts": 16, "first_expert": 4, "experts_held": 4, "top_k": 3, "routed_scale": 2.5}
    k = jax.random.split(jax.random.key(7), 4)
    p = {"router": jax.random.normal(k[0], (32, 16)), "w_up": 0.2 * jax.random.normal(k[1], (4, 32, 24)),
         "w_down": 0.2 * jax.random.normal(k[2], (4, 24, 32))}
    x = jax.random.normal(k[3], (40, 32))
    out, counted = hybrid.routed_experts(p, x, kw)
    picks, weights = experts.sigmoid_route(p["router"], x, kw["top_k"], kw["routed_scale"])
    want, all_counted = experts.held_experts(x, picks, weights, p["w_up"], p["w_down"], first_expert=4,
                                             activation=experts.RELU2)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(counted, all_counted[:2])
    assert hybrid.COUNTERS == experts.COUNTERS[:2]
    assert not hasattr(hybrid, "expert_blocks")


def test_factory_refuses_what_it_cannot_build():
    for bad in ({"window_layout": [0, 1]}, {"attn_heads": 3}, {"head_dim": 15}, {"window": 0},
                {"first_expert": 14}, {"rope_layout": [], "window_layout": []}):
        with pytest.raises(ValueError):
            get_model("moe_decoder_lm", **{**SMALL, **bad})


def test_trains_through_the_round_program_with_its_counters():
    model = get_model("moe_decoder_lm", **SMALL)
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
                                   *moe_decoder.COUNTERS}
    assert 0.1 < float(result.metrics["moe_held_pick_share"]) < 0.5
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), result.params, params)
    assert all(v > 0 for v in jax.tree.leaves(moved))  # every leaf learns, the router too


@pytest.fixture(scope="module")
def lowered_gradient(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("moe_decoder_lm", **SMALL)
    return jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum())).lower(params).as_text(debug_info=True)


def test_the_six_scopes_are_in_the_lowered_program(lowered_gradient):
    for scope in ("moe_router", "rope", "attention_full", "attention_window", "moe_dispatch",
                  "moe_experts"):
        assert scope in lowered_gradient, scope


@pytest.mark.parametrize("path", [
    "jvp(token_embed)/", "transpose(jvp(token_embed))/",
    "jvp(layer_scan)/squeeze", "transpose(jvp(layer_scan))/",  # the stacked leaves' slices
    "checkpoint/attention_proj/dot_general", "rematted_computation/attention_proj/dot_general",
    "jvp(lm_head)/dot_general", "transpose(jvp(lm_head))/dot_general",
])
def test_the_projections_the_lookup_and_the_head_have_scopes(lowered_gradient, path):
    assert path in lowered_gradient, path
