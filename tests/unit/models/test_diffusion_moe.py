"""The block-diffusion mixture-of-experts decoder (``models.diffusion_moe``) against its
plain reference (``benchmark/reference/sdar_moe.py``) at a small size on the CPU: the
objective it carries (each sample's weighted masked denoising loss over a doubled stream,
the noise from the step's key) and ``apply``'s view (the last block masked, one stream).

In float32 both sides compute the same real numbers, and what is left is the order of the
sums (rows laid out in blocks against dense products an expert, a fused ``W_gate | W_up``
product against two, the head in chunks against whole): 1e-5 on a loss, 1e-4 relative on a
leaf's gradient.  In bfloat16 (the cell's compute precision) every product rounds at
2**-8: a tenth of a leaf's gradient norm, which a dropped term (the ``1/t`` weight, the
mask's strict rule, positions that do not repeat) passes several times over; each of
those is also tested on its own below."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import diffusion_moe, experts, get_model
from nanofed_tpu.ops import experts as ops_experts
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig
from nanofed_tpu.trainer.local import make_grad_fn

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "block": 4, "width": 64,
    "layers": 2, "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "rope_theta": 1e6,
    "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3, "expert_width": 48,
    "eps": 1e-6,
}
#: Long enough for ``ops.attention``'s kernels (the interpreter here): a doubled stream of
#: 512 positions in tiles of 256, eight query heads a key/value head, one layer.
KERNELS = {**SMALL, "seq_len": 256, "layers": 1, "attn_heads": 8, "kv_heads": 1}
IDENTITY = lambda t: t
BF16 = lambda p: p.astype(jnp.bfloat16)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_moe_reference", REPO / "benchmark" / "reference" / "sdar_moe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(reference, kw, batch=3):
    """The reference's seeded tree with the embeddings brought down to N(0, 0.025), where
    the branches outweigh them in the stream, and norm weights that are not all 1, so
    that a norm given another's weight shows."""
    params = reference.init_params(jax.random.key(0), kw)
    params["embed"] = 0.025 * params["embed"]
    keys = iter(jax.random.split(jax.random.key(9), 16))
    params["layers"] = {name: leaf * (1 + 0.2 * jax.random.normal(next(keys), leaf.shape))
                        if name.startswith("norm") else leaf
                        for name, leaf in params["layers"].items()}
    tokens = jax.random.randint(jax.random.key(1), (batch, kw["seq_len"]), 0, kw["vocab"])
    return params, tokens


def _objective_gaps(model, reference, kw, params, tokens, cast=IDENTITY, their_key=None):
    """``(worst per-sample loss gap, the worst leaf and its relative gradient gap)`` of
    the program's objective against the reference's under the key ``jax.random.key(4)``."""
    key = jax.random.key(4)
    weight = jnp.arange(1.0, tokens.shape[0] + 1)  # every sample's loss counts, each its own
    ours = lambda p: model.apply.sample_nll(jax.tree.map(cast, p), tokens, None, rng=key)[0]
    theirs = lambda p: reference.sample_nll(p, tokens, None, key if their_key is None else their_key, kw)
    got, want = jax.jit(ours)(params), jax.jit(theirs)(params)
    assert got.dtype == want.dtype == jnp.float32 and got.shape == want.shape == tokens.shape[:1]
    grad = lambda fn: jax.jit(jax.grad(lambda p: (fn(p) * weight).sum()))(params)
    gaps = {jax.tree_util.keystr(path): float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grad(ours)),
                                    jax.tree.leaves(grad(theirs)))}
    return float(jnp.abs(got - want).max()), max(gaps.items(), key=lambda kv: kv[1])


def test_zoo_tree_is_the_references_tree(reference):
    own = jax.eval_shape(get_model("diffusion_moe_lm", **SMALL).init, jax.random.key(0))
    ours = jax.eval_shape(lambda: reference.init_params(jax.random.key(0), SMALL))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)))
    assert own["layers"]["w_gate_up"].shape == (2, 4, 64, 96)  # [layers, experts held, d, 2f]
    assert own["layers"]["wq"].shape == (2, 64, 64) and own["layers"]["wk"].shape == (2, 64, 32)
    assert own["layers"]["norm_q"].shape == own["layers"]["norm_k"].shape == (2, 16)
    assert own["layers"]["router"].shape == (2, 64, 16)


def test_zoo_and_reference_draw_their_leaves_alike(reference):
    kw = {**SMALL, "width": 128, "vocab": 512, "experts": 64}
    own = get_model("diffusion_moe_lm", **kw).init(jax.random.key(0))
    ours = reference.init_params(jax.random.key(1), kw)
    want = {"embed": 1.0, "wo": 0.01, "w_down": 0.01}  # 0.02 / sqrt(2 x 2 layers)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own), jax.tree.leaves(ours)):
        name = path[-1].key
        if name.startswith("norm"):
            assert bool((a == 1).all() and (b == 1).all()), name
        else:
            std = want.get(name, 0.02)
            assert float(a.std()) == pytest.approx(std, rel=0.1), name
            assert float(b.std()) == pytest.approx(std, rel=0.1), name


@pytest.mark.parametrize("cast,loss_limit,gradient_limit", [(IDENTITY, 1e-5, 1e-4), (BF16, 0.05, 0.1)],
                         ids=["float32", "bfloat16"])
def test_the_objectives_loss_and_gradients_match_the_reference(reference, cast, loss_limit,
                                                                gradient_limit):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("diffusion_moe_lm", **SMALL)
    loss_gap, (leaf, gap) = _objective_gaps(model, reference, SMALL, params, tokens, cast)
    assert loss_gap < loss_limit and gap < gradient_limit, (loss_gap, leaf, gap)


def test_the_same_key_gives_the_same_mask_and_another_key_fails(reference):
    """The noise is the step key's on both sides: stream, mask and levels are the
    reference's to the bit, and a reference given another key is another loss."""
    params, tokens = _seeded(reference, SMALL)
    model = get_model("diffusion_moe_lm", **SMALL)
    key = jax.random.key(4)
    stream, masked, level = diffusion_moe.noised(tokens, key, {**SMALL})
    x_t, m, t = reference.noised(tokens, key, SMALL)
    np.testing.assert_array_equal(stream, jnp.concatenate([tokens, x_t], axis=1))
    np.testing.assert_array_equal(masked, m)
    np.testing.assert_array_equal(level, t)
    assert bool((stream[:, 32:][masked] == 63).all()) and 0.2 < float(masked.mean()) < 0.8
    assert bool((level.reshape(3, 8, 4) == level.reshape(3, 8, 4)[..., :1]).all())  # one level a block
    assert float(level.min()) >= 1e-3 and float(level.max()) <= 1.0
    loss_gap, (leaf, gap) = _objective_gaps(model, reference, SMALL, params, tokens,
                                            their_key=jax.random.key(5))
    assert loss_gap > 1e-2 and gap > 1e-2, (loss_gap, leaf, gap)


def test_apply_reads_one_denoising_step_of_the_last_block(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("diffusion_moe_lm", **SMALL)
    got = jax.jit(model.apply)(params, tokens)
    want = jax.jit(lambda p: reference.log_probs(p, tokens, None, SMALL))(params)
    assert got.shape == (3, SMALL["vocab"]) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-5)
    # The last block is MASK whatever stands there: its tokens do not reach the answer.
    other = tokens.at[:, -4:].set((tokens[:, -4:] + 1) % 63)
    np.testing.assert_array_equal(jax.jit(model.apply)(params, other), got)
    assert float(jnp.abs(jax.jit(model.apply)(params, tokens.at[:, -5].add(1) % 63) - got).max()) > 1e-4
    low = jax.jit(model.apply)(jax.tree.map(BF16, params), tokens)
    assert low.dtype == jnp.float32 and float(jnp.abs(low - want).max()) < 0.1


def test_on_the_kernels_path_with_eight_heads_a_group(reference):
    """A doubled stream of 512 positions: attention runs in ``ops.attention``'s kernels
    under the block-diffusion mask (the interpreter), once forward a layer."""
    params, tokens = _seeded(reference, KERNELS, batch=1)
    model = get_model("diffusion_moe_lm", **KERNELS)
    objective = lambda p: model.apply.sample_nll(p, tokens, None, rng=jax.random.key(4))[0].sum()
    text = str(jax.make_jaxpr(jax.grad(objective))(params)) + "\n"
    assert text.count("name=causal_attention_fwd_blocks\n") == 1
    assert text.count("name=causal_attention_bwd_blocks\n") == 1
    loss_gap, (leaf, gap) = _objective_gaps(model, reference, KERNELS, params, tokens)
    assert loss_gap < 2e-5 and gap < 2e-4, (loss_gap, leaf, gap)


def test_a_noised_block_sees_itself_whole_no_later_block_and_the_clean_half_never_the_noised(reference):
    """On the layer's attention over a doubled stream of 2 x 32 positions in blocks of 4."""
    params, _ = _seeded(reference, SMALL)
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    cfg, length = dict(SMALL), SMALL["seq_len"]
    u = jax.random.normal(jax.random.key(3), (2, 2 * length, SMALL["width"]))
    pos = jnp.arange(2 * length) % length
    attend = lambda u: diffusion_moe.attention(layer, u, pos, cfg, half=length)
    base = attend(u)
    changed = lambda at: attend(u.at[:, at].add(1.0)) - base
    moved = lambda delta, rows: float(jnp.abs(delta[:, jnp.asarray(list(rows))]).max())
    noised = lambda i: length + i
    # The clean half never sees the noised one: change the whole noised half, the clean
    # half's outputs stand to the bit.
    np.testing.assert_array_equal(attend(u.at[:, length:].add(1.0))[:, :length], base[:, :length])
    # A noised block (positions 8..11 of the copy) sees itself whole: its LAST position
    # moves its FIRST ...
    assert moved(changed(noised(11)), [noised(8)]) > 1e-4
    # ... no later block and no other noised block: nothing outside the block moves.
    delta = changed(noised(9))
    assert moved(delta, [noised(i) for i in range(length) if not 8 <= i < 12]) == 0.0
    assert moved(delta, range(length)) == 0.0
    # A noised block reads the CLEAN blocks strictly before its own: clean 7 reaches noised
    # 8..11, clean 8 (its own block's clean copy) and clean 12 do not.
    assert moved(changed(7), [noised(8), noised(11)]) > 1e-4
    assert moved(changed(8), [noised(i) for i in range(8, 12)]) == 0.0
    assert moved(changed(12), [noised(i) for i in range(12)]) == 0.0
    # The clean half is causal across blocks and bidirectional inside one.
    delta = changed(9)
    assert moved(delta, [8]) > 1e-4 and moved(delta, range(8)) == 0.0 and moved(delta, [12, 31]) > 1e-4
    # The whole layer's attention is the reference's under the same mask.
    seen = reference.visible(2 * length, length, 4)
    np.testing.assert_allclose(
        base, reference._attention(layer, u, pos.astype(jnp.float32), seen, SMALL, IDENTITY), atol=1e-6)


def test_the_positions_repeat(reference):
    """Position ``L + i`` of the stream stands at text position ``i``: a noised copy that
    masks nothing, read at a position whose block holds that one position's worth of
    difference, answers as rotary positions that repeat make it, and not as positions
    that run on to ``2 L`` would."""
    params, _ = _seeded(reference, SMALL)
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    cfg, length = dict(SMALL), SMALL["seq_len"]
    u = jax.random.normal(jax.random.key(3), (1, length, SMALL["width"]))
    doubled = jnp.concatenate([u, u], axis=1)
    repeat, run_on = jnp.arange(2 * length) % length, jnp.arange(2 * length)
    seen = reference.visible(2 * length, length, 4)
    got = diffusion_moe.attention(layer, doubled, repeat, cfg, half=length)
    want = reference._attention(layer, doubled, jnp.tile(jnp.arange(length, dtype=jnp.float32), 2),
                                seen, SMALL, IDENTITY)
    np.testing.assert_allclose(got, want, atol=1e-6)
    other = diffusion_moe.attention(layer, doubled, run_on, cfg, half=length)
    np.testing.assert_allclose(other[:, :length], got[:, :length], atol=1e-6)  # the clean half's own
    assert float(jnp.abs(other[:, length:] - got[:, length:]).max()) > 1e-3
    # What the model itself hands its layers: [0 .. L-1] twice (stream_states).
    states = lambda stream: diffusion_moe.stream_states(params, stream, cfg, half=length)[0]
    tokens = jax.random.randint(jax.random.key(1), (1, length), 0, 63)
    stream = jnp.concatenate([tokens, tokens], axis=1)
    np.testing.assert_allclose(states(stream), reference.hidden_states(params, stream, length, SMALL),
                               atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """The deployment's cut at a small width: eight chips of 16 experts each, 8 picks of
    128.  The routed parts all eight shares give add up to what the uncut reference's
    experts give; with the attended stream, which every chip computes alike and is
    counted once, that is the uncut layer."""
    kw = {**SMALL, "experts": 128, "top_k": 8}
    n_experts, per_chip, length = kw["experts"], 16, SMALL["seq_len"]
    params, _ = _seeded(reference, {**kw, "experts_held": n_experts})
    whole = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    whole = {**whole, "router": 25.0 * whole["router"]}  # logits that decide picks
    x = jax.random.normal(jax.random.key(7), (2, 2 * length, kw["width"]))
    pos = jnp.arange(2 * length) % length
    seen = reference.visible(2 * length, length, 4)
    uncut_kw = {**kw, "first_expert": 0, "experts_held": n_experts}
    uncut = reference.layer(whole, x, pos, seen, uncut_kw, IDENTITY)
    attended = reference.attention_block(whole, x, pos, seen, kw, IDENTITY)
    total, landed = attended, 0.0
    for chip in range(n_experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_gate_up": whole["w_gate_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**kw, "first_expert": first, "experts_held": per_chip}
        out, counted = diffusion_moe.decoder_layer(share, x, pos, cfg, half=length)
        np.testing.assert_allclose(out, reference.layer(share, x, pos, seen, cfg, IDENTITY), atol=1e-5)
        total, landed = total + (out - attended), landed + float(counted[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(uncut - out).max()) > 1e-3  # one chip alone is a cut


def test_the_objective_counts_its_masked_share_beside_the_experts_counters(reference):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("diffusion_moe_lm", **SMALL)
    nll, hits, counters = model.apply.sample_nll(params, tokens, None, rng=jax.random.key(4))
    assert tuple(counters) == (*experts.COUNTERS, "diffusion_masked_share")
    masked = reference.noised(tokens, jax.random.key(4), SMALL)[1]
    assert float(counters["diffusion_masked_share"]) == pytest.approx(float(masked.mean()))
    assert 0.1 < float(counters["moe_held_pick_share"]) < 0.5  # 4 of 16 held: 0.25 if uniform
    assert hits.shape == (3,) and bool(((0 <= hits) & (hits <= 1)).all())
    # ``apply`` counts the experts' three too (the standard channel of a model that is read).
    assert tuple(model.apply.with_counters(params, tokens)[1]) == experts.COUNTERS
    # The share of masked positions the argmax gets right: a head that knows the answer.
    sure = {**params, "head": 50.0 * params["embed"].T, "norm_f": jnp.ones_like(params["norm_f"])}
    zero = jax.tree.map(lambda a: jnp.zeros_like(a), params["layers"])
    stays = {**sure, "layers": {**zero, **{k: params["layers"][k] for k in params["layers"] if "norm" in k}}}
    _, hits, _ = model.apply.sample_nll(stays, tokens, None, rng=jax.random.key(4))
    # With every layer adding nothing a masked position holds MASK's embedding: its argmax
    # is MASK, right only where the clean token is MASK itself.
    assert float(hits.max()) < 0.2


def test_the_loss_weighs_a_masked_position_by_one_over_its_blocks_level(reference):
    """By hand from the reference's parts: ``(1 / L) sum_i m / t * -log p`` at the noised
    half's positions, no shift; leaving the weight out or shifting the logits shows."""
    params, tokens = _seeded(reference, SMALL)
    key, length = jax.random.key(4), SMALL["seq_len"]
    x_t, m, t = reference.noised(tokens, key, SMALL)
    hidden = reference.hidden_states(params, jnp.concatenate([tokens, x_t], axis=1), length, SMALL)
    logp = reference._head(params, hidden[:, length:], SMALL, IDENTITY)
    of_token = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    by_hand = -(m * of_token / t).sum(axis=1) / length
    model = get_model("diffusion_moe_lm", **SMALL)
    got = model.apply.sample_nll(params, tokens, None, rng=key)[0]
    np.testing.assert_allclose(got, by_hand, rtol=1e-5)
    np.testing.assert_allclose(reference.sample_nll(params, tokens, None, key, SMALL), by_hand, rtol=1e-5)
    assert float(jnp.abs(-(m * of_token).sum(axis=1) / length - got).max()) > 0.1  # no 1/t
    shifted = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
    assert float(jnp.abs(-(m[:, 1:] * shifted / t[:, 1:]).sum(axis=1) / length - got).max()) > 0.1


def test_factory_refuses_what_it_cannot_build():
    for bad in ({"head_dim": 7}, {"layers": 0}, {"first_expert": 14}, {"top_k": 17}, {"attn_heads": 3},
                {"block": 3}, {"block": 64}, {"seq_len": 30}, {"block": 0}):
        with pytest.raises(ValueError):
            get_model("diffusion_moe_lm", **{**SMALL, **bad})


def test_trains_through_the_round_program_on_its_own_objective():
    """``build_round_step`` with no ``grad_fn``: the model carries its objective, the
    labels are not read, the counters reach the round's metrics."""
    model = get_model("diffusion_moe_lm", **SMALL)
    mesh = make_mesh(devices=jax.devices()[:1])
    training = TrainingConfig(batch_size=2, local_epochs=1, learning_rate=0.01)
    strategy = fedavg_strategy()
    params = model.init(jax.random.key(0))
    step = build_round_step(model.apply, training, mesh, strategy, client_chunk=1, params_like=params)
    k = jax.random.split(jax.random.key(5), 2)
    x = jax.random.randint(k[0], (2, 4, SMALL["seq_len"]), 0, SMALL["vocab"])
    labels = jax.random.randint(k[1], (2, 4), 0, SMALL["vocab"])
    run = lambda y: step(params, init_server_state(strategy, params), ClientData(x=x, y=y, mask=jnp.ones((2, 4))),
                         jnp.full((2,), 4.0), jax.random.split(jax.random.key(6), 2))
    result = run(labels)
    assert set(result.metrics) == {"loss", "accuracy", "samples", "participating_clients",
                                   *experts.COUNTERS, "diffusion_masked_share"}
    assert 0.3 < float(result.metrics["diffusion_masked_share"]) < 0.7
    assert 0.0 <= float(result.metrics["accuracy"]) <= 1.0
    # The loss of uniform guesses over 64 tokens, weighted by m / t (expectation 1): ln 64.
    assert 2.0 < float(result.metrics["loss"]) < 7.0
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), result.params, params)
    assert all(v > 0 for v in jax.tree.leaves(moved))  # every leaf learns
    other = run((labels + 1) % SMALL["vocab"])  # the labels are not read
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(other.params), jax.tree.leaves(result.params)))


@pytest.fixture(scope="module")
def lowered_step(reference):
    params, tokens = _seeded(reference, SMALL)
    grad_fn = make_grad_fn(get_model("diffusion_moe_lm", **SMALL).apply, compute_dtype="bfloat16")
    step = lambda p: grad_fn(p, tokens, jnp.zeros((3,), jnp.int32), jnp.ones((3,)), jax.random.key(0))
    return jax.jit(step).lower(params).as_text(debug_info=True)


def test_the_scopes_and_the_counter_are_in_the_lowered_step(lowered_step):
    for scope in ("diffusion_noise", "attention_block_diffusion", "attention_proj", "rope",
                  "moe_router", "moe_dispatch", "moe_experts", "layer_scan", "token_embed",
                  "lm_head", "nll_loss", "cast_params"):
        assert scope in lowered_step, scope


@pytest.mark.parametrize("path", [
    "diffusion_noise)/jit(_uniform)", "diffusion_noise)/concatenate", "diffusion_noise)/lt",
    "jvp(lm_head)/dot_general", "transpose(jvp(lm_head))/dot_general",
    "jvp(nll_loss)/", "transpose(jvp(nll_loss))/",
    "checkpoint/attention_block_diffusion/", "rematted_computation/attention_proj/dot_general",
    "checkpoint/rope/", "jvp(token_embed)/",
])
def test_the_objectives_parts_have_scopes(lowered_step, path):
    assert path in lowered_step, path
