"""The indexed-attention mixture-of-experts decoder (``models.indexed_moe``) against its
plain reference (``benchmark/reference/keye_vl2.py``) at a small size on the CPU.

In float32 both sides compute the same real numbers.  The indexer's scores are the same
products summed in another order (the program sums its heads in a fused reduction, the
reference in an ``einsum``), so two keys change places only where their scores differ in
the last bits, which no seed here does: the picks are compared exactly.  What is left
is the order of the sums elsewhere (sorted rows in blocks against dense products an
expert, blockwise softmax against whole rows): a few ulps amplified through the layers —
hence 1e-5 on log-probabilities and 1e-4 relative on a leaf's gradient, far under
anything a missing term would give: ONE key of eight picked otherwise moves a
log-probability by 1e-3 to 1e-1 here, so an indexer computed in bfloat16 and a
selection that loses a key (a recall under 1) both fail the same comparison, as two
tests below show.  In bfloat16 (the cell's compute precision) every product rounds at
2**-8, and the indexer reads bfloat16 inputs: on the seeds where the picks still agree
log-probabilities hold to 0.02 and a leaf's gradient to a tenth of its norm."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.models import decoder, experts, get_model, indexed_moe
from nanofed_tpu.ops import attention
from nanofed_tpu.ops import experts as ops_experts

#: The layout's block for experts as small as the tests': the largest row tile.
DEFAULT_BLOCK = ops_experts.TILES[0]

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "width": 64, "layers": 2, "attn_heads": 4, "kv_heads": 2,
    "head_dim": 16, "rope_theta": 1e7, "rope_sections": [2, 3, 3], "index_heads": 4,
    "index_dim": 8, "index_topk": 8, "experts": 16, "first_expert": 0, "experts_held": 4,
    "top_k": 3, "expert_width": 48, "eps": 1e-6,
}
#: Long enough for ``ops.attention``'s kernels (the interpreter here): eight query heads
#: a key/value head under a pick of 96 keys, two bands of 256 queries.
KERNELS = {**SMALL, "seq_len": 512, "attn_heads": 8, "kv_heads": 1, "index_topk": 96}
IDENTITY = lambda t: t


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "keye_vl2_reference", REPO / "benchmark" / "reference" / "keye_vl2.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(reference, kw, batch=3, seed=0):
    """The reference's seeded tree with every matrix brought to N(0, 0.02) and the
    embeddings with them: there the attention and expert branches outweigh the embeddings
    in the residual stream, so a branch computed wrongly (a key picked otherwise) shows in
    the log-probabilities and not only in its own gradient."""
    params = reference.init_params(jax.random.key(seed), kw)
    into_stream = (2 * kw["layers"]) ** 0.5
    params["embed"] = 0.02 * params["embed"]
    params["layers"] = {**params["layers"], "wo": into_stream * params["layers"]["wo"],
                        "w_down": into_stream * params["layers"]["w_down"]}
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, kw["seq_len"]), 0, kw["vocab"])
    return params, tokens


@pytest.fixture(params=[8, indexed_moe.INDEX_BAND], ids=["bands-of-8", "one-band"])
def index_band(request, monkeypatch):
    """At 8 queries a band the 32 positions are a band that keeps all it sees and three
    that select, in one group and (``BAND_GROUP`` 2) in two; at the default one band."""
    monkeypatch.setattr(indexed_moe, "INDEX_BAND", request.param)
    monkeypatch.setattr(indexed_moe, "BAND_GROUP", 2)
    return request.param


def _nll(logp, labels):
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def _gradient_gaps(model, reference, kw, params, tokens, cast=IDENTITY):
    labels = jnp.arange(tokens.shape[0]) * 7 % kw["vocab"]
    got = jax.grad(lambda p: _nll(model.apply(jax.tree.map(cast, p), tokens), labels))(params)
    want = jax.grad(lambda p: _nll(reference.log_probs(p, tokens, None, kw), labels))(params)
    return {jax.tree_util.keystr(path): (g, w) for (path, g), w in
            zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))}


def _worst_gap(gaps):
    moving = {k: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
              for k, (g, w) in gaps.items() if "index_" not in k}
    return max(moving.items(), key=lambda kv: kv[1])


def test_zoo_tree_is_the_references_tree(reference):
    own = jax.eval_shape(get_model("indexed_moe_lm", **SMALL).init, jax.random.key(0))
    ours = jax.eval_shape(lambda: reference.init_params(jax.random.key(0), SMALL))
    assert jax.tree.structure(own) == jax.tree.structure(ours)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours)))
    assert own["layers"]["w_gate_up"].shape == (2, 4, 64, 96)  # [layers, experts, d, 2f]
    assert own["layers"]["index_wq"].shape == (2, 64, 32) and own["layers"]["norm_q"].shape == (2, 16)


def test_zoo_and_reference_draw_their_leaves_alike(reference):
    kw = {**SMALL, "width": 128, "vocab": 512}
    own = get_model("indexed_moe_lm", **kw).init(jax.random.key(0))
    ours = reference.init_params(jax.random.key(1), kw)
    want = {"embed": 1.0, "wo": 0.02 / 4 ** 0.5, "w_down": 0.02 / 4 ** 0.5}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own), jax.tree.leaves(ours)):
        name = path[-1].key
        if name.startswith("norm"):
            assert bool((a == 1).all() and (b == 1).all()), name
        else:
            std = want.get(name, 0.02)
            assert float(a.std()) == pytest.approx(std, rel=0.08), name
            assert float(b.std()) == pytest.approx(std, rel=0.08), name


def test_log_probs_and_gradients_match_the_reference_in_float32(reference, index_band):
    params, tokens = _seeded(reference, SMALL)
    model = get_model("indexed_moe_lm", **SMALL)
    got = model.apply(params, tokens)
    assert got.shape == (3, SMALL["vocab"])
    np.testing.assert_allclose(got, reference.log_probs(params, tokens, None, SMALL), atol=1e-5)
    gaps = _gradient_gaps(model, reference, SMALL, params, tokens)
    leaf, gap = _worst_gap(gaps)
    assert gap < 1e-4, (leaf, gap)


def test_the_indexers_leaves_take_a_gradient_of_exactly_zero(reference):
    """The pick is a constant of the backward pass, in program and reference alike; every
    other leaf moves."""
    params, tokens = _seeded(reference, SMALL)
    gaps = _gradient_gaps(get_model("indexed_moe_lm", **SMALL), reference, SMALL, params, tokens)
    for leaf, (got, want) in gaps.items():
        if "index_" in leaf:
            assert not bool(got.any()) and not bool(want.any()), leaf
        else:
            assert bool(got.any()) and bool(want.any()), leaf
    assert sum("index_" in leaf for leaf in gaps) == 3


def test_a_selection_that_loses_a_key_fails_the_comparison(reference, monkeypatch):
    """A top-k of recall 7/8: each query's LAST kept key dropped.  The log-probabilities
    leave the reference's by a hundred times the tolerance the float32 test allows."""
    def lossy(exact):
        def pick(scores, first, topk):
            keep = exact(scores, first, topk)
            last = (jnp.cumsum(keep[:, ::-1], axis=1)[:, ::-1] == 1) & (keep == 1)
            return jnp.where(last & (keep.sum(axis=1, keepdims=True) > 1), 0, keep).astype(jnp.int8)
        return pick

    params, tokens = _seeded(reference, SMALL)
    monkeypatch.setattr(indexed_moe, "top_keys", lossy(indexed_moe.top_keys))
    got = get_model("indexed_moe_lm", **SMALL).apply(params, tokens)
    assert float(jnp.abs(got - reference.log_probs(params, tokens, None, SMALL)).max()) > 1e-3


def test_an_indexer_in_bfloat16_fails_the_comparison(reference, monkeypatch):
    """The indexer reading its inputs and its three matrices rounded to bfloat16, the rest
    of the program in float32: two keys change places (seed 0: one query's eighth key),
    and the log-probabilities leave the reference's by 0.14, ten thousand times the
    tolerance the float32 test allows."""
    exact = indexed_moe.index_keys
    low = lambda a: a.astype(jnp.bfloat16)
    monkeypatch.setattr(indexed_moe, "index_keys", lambda p, u, cfg: exact(
        {**p, **{leaf: low(p[leaf]) for leaf in ("index_wq", "index_wk", "index_w")}}, low(u), cfg))
    params, tokens = _seeded(reference, SMALL)
    got = get_model("indexed_moe_lm", **SMALL).apply(params, tokens)
    assert float(jnp.abs(got - reference.log_probs(params, tokens, None, SMALL)).max()) > 1e-2


@pytest.mark.parametrize("seed", [1, 2, 4])
def test_bfloat16_compute_stays_near_the_float32_reference(reference, seed):
    """Seeds on which the bfloat16 program's picks are the float32 reference's (read here:
    log-probabilities within 0.004, gradients within 0.02 of a leaf's norm).  On seeds 0,
    3 and 5 one key of eight changes places between bfloat16 and float32 indexer inputs
    and the same numbers read 0.12-0.14 and 0.2-0.6: at this size a pick is an eighth of a
    query's attention; at the cell's it is one key of 2048."""
    params, tokens = _seeded(reference, SMALL, seed=seed)
    model = get_model("indexed_moe_lm", **SMALL)
    cast = lambda p: p.astype(jnp.bfloat16)
    low = model.apply(jax.tree.map(cast, params), tokens)
    assert low.dtype == jnp.float32 and bool(jnp.isfinite(low).all())
    assert float(jnp.abs(low - reference.log_probs(params, tokens, None, SMALL)).max()) < 0.02
    leaf, gap = _worst_gap(_gradient_gaps(model, reference, SMALL, params, tokens, cast=cast))
    assert gap < 0.1, (leaf, gap)


def test_where_no_pick_binds_the_layer_is_plain_grouped_query_attention(reference):
    """``T <= topk``: no mask is made, the attention is ``moe_decoder``'s full causal
    grouped-query attention on the same q, k, v, and the reference (whose ``lax.top_k``
    then takes every key) agrees."""
    kw = {**SMALL, "index_topk": 32}
    params, tokens = _seeded(reference, kw)
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    u = jax.random.normal(jax.random.key(5), (2, 32, 64))
    keep, counted = indexed_moe.index_keys(layer, u, kw)
    assert keep is None and counted.tolist() == [1.0, 1.0]
    np.testing.assert_allclose(get_model("indexed_moe_lm", **kw).apply(params, tokens),
                               reference.log_probs(params, tokens, None, kw), atol=1e-5)
    # A pick of 1000 keys is the same function, and a pick of 31 is not.
    same = get_model("indexed_moe_lm", **{**kw, "index_topk": 1000}).apply(params, tokens)
    np.testing.assert_array_equal(same, get_model("indexed_moe_lm", **kw).apply(params, tokens))
    other = get_model("indexed_moe_lm", **{**kw, "index_topk": 31}).apply(params, tokens)
    assert float(jnp.abs(other - same).max()) > 1e-6
    # Plain causal grouped-query attention, spelled by the op itself.
    pos = indexed_moe.text_positions(32)
    got = indexed_moe.attention(layer, u, pos, None, kw)
    all_kept = jnp.ones((2, 32, 32), jnp.int8)
    np.testing.assert_array_equal(got, indexed_moe.attention(layer, u, pos, all_kept, kw))


def _scores(seed, n, keys, band, levels=None):
    scores = jax.random.normal(jax.random.key(seed), (n, keys, band))
    if levels:  # few distinct values: ties everywhere, zeros of both signs among them
        scores = jnp.round(scores * levels) / levels
    return scores


@pytest.mark.parametrize("levels", [None, 2, 0.4], ids=["distinct", "five-values", "mostly-zero"])
@pytest.mark.parametrize("first,topk", [(0, 5), (16, 8), (40, 8), (40, 1), (8, 40)])
def test_the_pick_is_the_references_top_k_tie_for_tie(reference, first, topk, levels):
    """``top_keys`` against ``lax.top_k`` on a band of 24 queries standing at ``first``
    over 64 keys: exactly equal, with scores that are distinct and with scores quantized
    so that every threshold has ties (equal scores go to the smaller key; -0.0 is 0.0)."""
    scores = _scores(first + topk, 2, 64, 24, levels)
    got = indexed_moe.top_keys(scores, first, topk)
    want = reference.picked(jnp.swapaxes(scores, 1, 2), first, topk)  # [n, band, keys]
    np.testing.assert_array_equal(got, jnp.swapaxes(want, 1, 2).astype(jnp.int8))
    kept = np.asarray(got.sum(axis=1))
    np.testing.assert_array_equal(kept, np.broadcast_to(
        np.minimum(topk, np.minimum(first + np.arange(24) + 1, 64)), kept.shape))


def test_the_references_pick_is_lax_top_ks_own_order(reference):
    """The reference reads a threshold off ``lax.top_k`` and fills the places left by
    position; ``lax.top_k``'s indices, which put the smaller index first among equals,
    name the same keys."""
    for levels in (None, 2, 0.4):
        scores = jnp.swapaxes(_scores(11, 2, 64, 24, levels), 1, 2)  # [n, band, keys]
        causal = jnp.arange(64)[None, :] <= 30 + jnp.arange(24)[:, None]
        clean = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
        _, keys = jax.lax.top_k(clean, 8)
        want = jax.nn.one_hot(keys, 64, dtype=jnp.bool_).any(axis=-2) & causal
        np.testing.assert_array_equal(reference.picked(scores, 30, 8), want)


def test_crafted_equal_scores_go_to_the_smaller_key():
    """Ten keys, one query at position 9, three places: scores 5 at keys 2 and 7, score 1
    at keys 0, 3, 4, 8 and 0 elsewhere.  The two fives are in; the third place goes to the
    first of the ones, key 0.  With four places, keys 0 and 3."""
    row = jnp.zeros((10,)).at[jnp.array([2, 7])].set(5.0).at[jnp.array([0, 3, 4, 8])].set(1.0)
    pick = lambda topk: np.flatnonzero(np.asarray(
        indexed_moe.top_keys(row[None, :, None], 9, topk))[0, :, 0]).tolist()
    assert pick(2) == [2, 7] and pick(3) == [0, 2, 7] and pick(4) == [0, 2, 3, 7]
    assert pick(7) == [0, 1, 2, 3, 4, 7, 8]  # the zeros' tie: key 1 before 5, 6, 9
    # Negative zero is zero: it neither loses to 0.0 nor beats it.
    signed = row.at[1].set(-0.0)
    got = indexed_moe.top_keys(signed[None, :, None], 9, 7)
    assert np.flatnonzero(np.asarray(got)[0, :, 0]).tolist() == [0, 1, 2, 3, 4, 7, 8]


def test_the_selection_is_two_loops_and_no_sort():
    """The bits' bisection (a loop of known length) and the ties' (a ``while`` whose
    condition is false from the start where no threshold has spare ties); nothing sorts."""
    jaxpr = jax.make_jaxpr(lambda s: indexed_moe.top_keys(s, 56, 8))(_scores(3, 1, 64, 8))
    names = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert names.count("scan") == 1 and names.count("while") == 1
    assert "sort" not in names and "top_k" not in names and "approx_top_k" not in names


def test_positions_with_three_components_match_the_reference(reference):
    """``pos`` [3, T] with three different components: the sections of a head's rotary
    pairs each follow their own, against the reference's rotation written pair by pair."""
    x = jax.random.normal(jax.random.key(4), (2, 6, 3, 16))
    pos = jnp.stack([jnp.arange(6.0), jnp.arange(6.0)[::-1] * 3, jnp.array([4., 4, 0, 9, 1, 2])])
    got = indexed_moe.rotate(x, pos, 1e7, (2, 3, 3))
    np.testing.assert_allclose(got, reference._rotate(x, pos, 1e7, (2, 3, 3)), atol=1e-6)
    for i, c in enumerate([0, 0, 1, 1, 1, 2, 2, 2]):  # pair i turns by pos[c] * theta^(-i/8)
        angle = pos[c] * 1e7 ** (-i / 8)
        a, b = x[0, :, 0, i], x[0, :, 0, i + 8]
        np.testing.assert_allclose(got[0, :, 0, i], a * jnp.cos(angle) - b * jnp.sin(angle), atol=1e-6)
        np.testing.assert_allclose(got[0, :, 0, i + 8], b * jnp.cos(angle) + a * jnp.sin(angle), atol=1e-6)
    # The components matter: all three set to the first is another rotation.
    assert float(jnp.abs(got - indexed_moe.rotate(x, pos[:1].repeat(3, 0), 1e7, (2, 3, 3))).max()) > 1e-3


def test_text_positions_are_the_plain_rotation(reference):
    x = jax.random.normal(jax.random.key(4), (2, 40, 3, 16)).astype(jnp.bfloat16)
    got = indexed_moe.rotate(x, indexed_moe.text_positions(40), 1e7, (2, 3, 3))
    np.testing.assert_array_equal(got, decoder.rotate(x, 1e7))
    np.testing.assert_array_equal(indexed_moe.text_positions(5), reference.text_positions(5))
    params, tokens = _seeded(reference, SMALL)
    pos = jnp.stack([jnp.arange(32.0), jnp.arange(32.0) // 2, jnp.arange(32.0) % 5])
    cfg = {**SMALL, "rope_sections": (2, 3, 3)}
    hidden, _ = indexed_moe.hidden_states(params, tokens, cfg, pos)
    np.testing.assert_allclose(hidden, reference.hidden_states(params, tokens, SMALL, pos=pos), atol=2e-5)
    text, _ = indexed_moe.hidden_states(params, tokens, cfg)
    assert float(jnp.abs(hidden - text).max()) > 1e-4


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Eight chips of sixteen experts each: the feed-forward parts all the shares give add
    up to what the uncut reference gives for the whole layer; attention under the pick
    (what every chip computes alike) is counted once, before them."""
    kw = {**SMALL, "layers": 1, "width": 32, "experts": 128, "top_k": 8, "expert_width": 16}
    d, f, n_experts, per_chip = kw["width"], kw["expert_width"], kw["experts"], 16
    k = jax.random.split(jax.random.key(3), 12)
    normal = lambda key, scale, *shape: scale * jax.random.normal(key, shape)
    whole = {
        "norm_in": jnp.ones(d), "norm_post": jnp.ones(d), "norm_q": jnp.ones(16), "norm_k": jnp.ones(16),
        "wq": normal(k[0], 0.1, d, 64), "wk": normal(k[1], 0.1, d, 32), "wv": normal(k[2], 0.1, d, 32),
        "wo": normal(k[3], 0.1, 64, d), "index_wq": normal(k[4], 0.3, d, 32),
        "index_wk": normal(k[5], 0.3, d, 8), "index_w": normal(k[6], 0.3, d, 4),
        "router": normal(k[7], 0.5, d, n_experts),
        "w_gate_up": normal(k[8], 0.2, n_experts, d, 2 * f), "w_down": normal(k[9], 0.2, n_experts, f, d),
    }
    x = jax.random.normal(k[10], (2, 32, d))
    pos = reference.text_positions(32)
    uncut = reference.layer(whole, x, pos, {**kw, "first_expert": 0, "experts_held": n_experts}, IDENTITY)
    attended = reference.attention_block(whole, x, pos, kw, IDENTITY)
    total, landed = attended, 0.0
    for chip in range(n_experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_gate_up": whole["w_gate_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**kw, "first_expert": first, "experts_held": per_chip}
        out, counted = indexed_moe.decoder_layer(share, x, pos, cfg)
        np.testing.assert_allclose(out, reference.layer(share, x, pos, cfg, IDENTITY), atol=1e-5)
        total, landed = total + (out - attended), landed + float(counted[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(total - out).max()) > 1e-2  # one chip alone is a cut


def test_the_whole_stack_is_causal(reference):
    params, tokens = _seeded(reference, SMALL)
    cfg = {**SMALL, "rope_sections": (2, 3, 3)}
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % SMALL["vocab"])
    before, _ = indexed_moe.hidden_states(params, tokens, cfg)
    after, _ = indexed_moe.hidden_states(params, changed, cfg)
    np.testing.assert_array_equal(before[:, :20], after[:, :20])
    assert float(jnp.abs(before[:, 20:] - after[:, 20:]).max()) > 1e-3


def test_on_the_kernels_path_with_groups_of_eight(reference, monkeypatch):
    """512 positions in two bands of 256: the pick of 96 keys binds, attention runs in
    ``ops.attention``'s masked kernels (the interpreter), one key/value head for eight
    query heads, and the reference agrees on log-probabilities and every gradient."""
    monkeypatch.setattr(indexed_moe, "INDEX_BAND", 256)
    params, tokens = _seeded(reference, KERNELS, batch=2)
    model = get_model("indexed_moe_lm", **KERNELS)
    text = str(jax.make_jaxpr(model.apply)(params, tokens))
    assert "name=causal_attention_fwd_keep" in text and attention.engages(512)
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens),
                               reference.log_probs(params, tokens, None, KERNELS), atol=2e-5)
    leaf, gap = _worst_gap(_gradient_gaps(model, reference, KERNELS, params, tokens))
    assert gap < 2e-4, (leaf, gap)


def test_counters_count_the_pick_and_the_routing(reference, index_band):
    params, tokens = _seeded(reference, SMALL)
    _, counters = get_model("indexed_moe_lm", **SMALL).apply.with_counters(params, tokens)
    assert tuple(counters) == indexed_moe.COUNTERS == (*experts.COUNTERS, *indexed_moe.SPARSE_COUNTERS)
    # topk (2T - topk + 1) / (T (T + 1)) at 32 positions of 8 keys, exactly.
    assert float(counters["sparse_kept_pair_share"]) == pytest.approx(8 * 57 / (32 * 33), rel=1e-6)
    bands = 32 // min(index_band, 32)
    live = float(counters["sparse_live_block_share"]) * bands * (bands + 1) / 2
    assert live == pytest.approx(round(live), abs=1e-4) and bands <= round(live) <= bands * (bands + 1) / 2
    assert 0.1 < float(counters["moe_held_pick_share"]) < 0.5  # 4 of 16 held: 0.25 if uniform
    assert float(counters["moe_block_fill"]) == pytest.approx(
        float(counters["moe_held_pick_share"]) * 96 * 3 / (4 * DEFAULT_BLOCK), rel=1e-5)


def test_live_blocks_by_hand(monkeypatch):
    """Sixteen positions in bands of 4, two keys a query, indexer scores that prefer the
    NEAREST keys: every query keeps itself and its neighbour, so only the diagonal tiles
    and the ones beside them hold a kept pair: 4 + 3 of the 10 tiles on or under the
    diagonal; kept pairs 1 + 2 * 15 of 136."""
    monkeypatch.setattr(indexed_moe, "INDEX_BAND", 4)
    monkeypatch.setattr(indexed_moe, "top_keys", lambda scores, first, topk: (
        lambda at, q: ((at <= q) & (at >= q - 1)).astype(jnp.int8))(
            jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1),
            first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)))
    cfg = {"index_heads": 2, "index_dim": 4, "index_topk": 2}
    p = {"index_wq": jnp.ones((8, 8)), "index_wk": jnp.ones((8, 4)), "index_w": jnp.ones((8, 2))}
    keep, counted = indexed_moe.index_keys(p, jnp.ones((1, 16, 8)), cfg)
    assert keep.shape == (1, 16, 16) and keep.dtype == jnp.int8
    np.testing.assert_array_equal(keep[0], np.eye(16, dtype=np.int8) + np.eye(16, k=1, dtype=np.int8))
    np.testing.assert_allclose(counted, [31 / 136, 7 / 10], rtol=1e-6)


def test_factory_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="rope_sections"):
        get_model("indexed_moe_lm", **{**SMALL, "rope_sections": [2, 3, 4]})
    with pytest.raises(ValueError, match="held experts"):
        get_model("indexed_moe_lm", **{**SMALL, "first_expert": 14})
    with pytest.raises(ValueError, match="kv_heads"):
        get_model("indexed_moe_lm", **{**SMALL, "attn_heads": 5})
