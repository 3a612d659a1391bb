"""The hybrid state-space / mixture-of-experts decoder (``models.hybrid``) against its
plain reference (``benchmark/reference/nemotron_h.py``) at a small size on the CPU, in
float32: both sides then compute the same real numbers, and what is left is the order of
the sums (chunked against step by step, sorted rows against dense products), a few ulps
amplified through nine layers — hence tolerances of 1e-5 on log-probabilities and 1e-4
relative on a leaf's gradient, far under anything a missing term would give."""

import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nanofed_tpu import nn
from nanofed_tpu.core.types import ClientData, ClientMetrics
from nanofed_tpu.models import decoder, get_model, hybrid
from nanofed_tpu.ops import attention
from nanofed_tpu.ops import experts as ops_experts
from nanofed_tpu.parallel.mesh import MODEL_AXIS, make_mesh, param_partition_spec
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.trainer import TrainingConfig

REPO = Path(__file__).resolve().parents[3]
SMALL = {
    "vocab": 64, "seq_len": 32, "width": 64, "pattern": "MEMEM*EME",
    "mamba_heads": 2, "mamba_head_dim": 16, "ssm_groups": 2, "ssm_state": 16,
    "conv_kernel": 4, "chunk": 8, "attn_heads": 4, "kv_heads": 2, "head_dim": 16,
    "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 48, "shared_width": 96, "routed_scale": 2.5, "eps": 1e-5,
}
IDENTITY = lambda t: t


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference", REPO / "benchmark" / "reference" / "nemotron_h.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def seeded(reference):
    params = reference.init_params(jax.random.key(0), SMALL)
    tokens = jax.random.randint(jax.random.key(1), (3, SMALL["seq_len"]), 0, SMALL["vocab"])
    return params, tokens


@pytest.fixture(params=[8, None], ids=["blocks-of-8", "one-block-an-expert"])
def expert_block(request, monkeypatch):
    """At 8 rows a block an expert's ~12 picks span two blocks, so the expert loop runs
    several blocks an expert; at the block the experts' shape gives every expert fits one."""
    if request.param:
        monkeypatch.setattr(ops_experts, "tile_rows", lambda d, f_in: request.param)
    return request.param


def test_zoo_tree_is_the_references_tree(reference, seeded):
    own = jax.eval_shape(get_model("hybrid_lm", **SMALL).init, jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(seeded[0])
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(seeded[0])))


def test_log_probs_match_the_reference(reference, seeded, expert_block):
    params, tokens = seeded
    got = get_model("hybrid_lm", **SMALL).apply(params, tokens)
    want = reference.log_probs(params, tokens, None, SMALL)
    assert got.shape == (3, SMALL["vocab"])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_gradients_match_the_reference_leaf_by_leaf(reference, seeded, expert_block):
    params, tokens = seeded
    model = get_model("hybrid_lm", **SMALL)
    labels = jnp.array([5, 17, 40])
    nll = lambda logp: -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    got = jax.grad(lambda p: nll(model.apply(p, tokens)))(params)
    want = jax.grad(lambda p: nll(reference.log_probs(p, tokens, None, SMALL)))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def test_gradient_is_the_same_with_the_embedding_gradient_in_bands(reference, monkeypatch):
    """At a width of two lane tiles and a budget of one, ``nn.embed_rows`` accumulates the
    table's gradient band by band: every leaf's gradient is what one band gives."""
    kw = {**SMALL, "width": 256}
    params = reference.init_params(jax.random.key(0), kw)
    tokens = jax.random.randint(jax.random.key(1), (3, kw["seq_len"]), 0, 9)
    model = get_model("hybrid_lm", **kw)
    grads = lambda: jax.grad(lambda p: model.apply(p, tokens)[:, 5].sum())(params)
    whole = grads()
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", kw["vocab"] * 128 * 4)
    assert nn.embed_bands(kw["vocab"], 256, 4) == 2
    jax.tree.map(np.testing.assert_array_equal, grads(), whole)


def _scan_inputs(t, key=2):
    k = jax.random.split(jax.random.key(key), 5)
    n, g, hg, p, s = 2, 2, 2, 4, 8
    x = jax.random.normal(k[0], (n, t, g, hg, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (n, t, g, hg)) - 1.0)
    da = -dt * jnp.exp(jax.random.normal(k[2], (g, hg)))
    b, c = jax.random.normal(k[3], (n, t, g, s)), jax.random.normal(k[4], (n, t, g, s))
    return x, dt, da, b, c


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_scan_equals_the_step_by_step_recurrence(reference, chunk):
    x, dt, da, b, c = _scan_inputs(32)
    by_chunks = lambda x, dt, da, b, c: hybrid.ssd_chunked(x, dt, da, b, c, chunk)
    by_steps = lambda x, dt, da, b, c: reference._recurrence(jnp.exp(da), x * dt[..., None], b, c, 8)
    np.testing.assert_allclose(by_chunks(x, dt, da, b, c), by_steps(x, dt, da, b, c), atol=2e-5)
    weight = jax.random.normal(jax.random.key(9), x.shape)
    grads = lambda f: jax.grad(lambda *a: (f(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(x, dt, da, b, c)
    for got, want in zip(grads(by_chunks), grads(by_steps)):
        assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5


def test_factory_refuses_a_length_that_is_not_whole_chunks():
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        get_model("hybrid_lm", **{**SMALL, "seq_len": 36})
    model = get_model("hybrid_lm", **SMALL)
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        model.apply(model.init(jax.random.key(0)), jnp.zeros((1, 12), jnp.int32))
    with pytest.raises(ValueError, match="pattern"):
        get_model("hybrid_lm", **{**SMALL, "pattern": "MEA"})


def test_the_shares_add_up_to_the_uncut_layer(reference, expert_block):
    """Four chips of four experts each: the routed parts all the shares give, plus the
    shared expert counted once, are what the uncut reference gives for the whole layer."""
    d, f, experts, per_chip = SMALL["width"], SMALL["expert_width"], SMALL["experts"], 4
    k = jax.random.split(jax.random.key(3), 6)
    whole = {
        "router": 0.5 * jax.random.normal(k[0], (d, experts)),
        "w_up": 0.2 * jax.random.normal(k[1], (experts, d, f)),
        "w_down": 0.2 * jax.random.normal(k[2], (experts, f, d)),
        "shared_up": 0.2 * jax.random.normal(k[3], (d, SMALL["shared_width"])),
        "shared_down": 0.2 * jax.random.normal(k[4], (SMALL["shared_width"], d)),
    }
    x = jax.random.normal(k[5], (2, 16, d))
    uncut = (reference.routed_experts(whole, x, SMALL, IDENTITY, 0, experts)
             + reference.shared_expert(whole, x, IDENTITY))
    total, landed = jnp.zeros_like(x), 0.0
    for chip in range(experts // per_chip):
        first = chip * per_chip
        share = {**whole, "w_up": whole["w_up"][first:first + per_chip],
                 "w_down": whole["w_down"][first:first + per_chip]}
        cfg = {**SMALL, "first_expert": first, "experts_held": per_chip}
        routed, counters = hybrid.routed_experts(share, x.reshape(-1, d), cfg)
        np.testing.assert_allclose(
            routed.reshape(x.shape),
            reference.routed_experts(share, x, SMALL, IDENTITY, first, per_chip), atol=1e-5)
        total, landed = total + routed.reshape(x.shape), landed + float(counters[0])
    assert landed == pytest.approx(1.0)  # every pick lands on exactly one chip
    np.testing.assert_allclose(total + reference.shared_expert(whole, x, IDENTITY), uncut, atol=2e-5)
    # One chip alone gives a different answer: the cut is a cut.
    assert float(jnp.abs(total - routed.reshape(x.shape)).max()) > 1e-2


def test_the_whole_stack_is_causal(seeded):
    params, tokens = seeded
    cfg = {**SMALL}
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % SMALL["vocab"])
    before, _ = hybrid.hidden_states(params, tokens, cfg)
    after, _ = hybrid.hidden_states(params, changed, cfg)
    np.testing.assert_array_equal(before[:, :20], after[:, :20])
    assert float(jnp.abs(before[:, 20:] - after[:, 20:]).max()) > 1e-3
    assert float(jnp.abs(before[:, -1] - after[:, -1]).max()) > 1e-6  # the order is carried


#: The cell's attention layer with fewer heads: four query heads a key/value head, 128 wide.
ATTENTION = {"width": 64, "attn_heads": 8, "kv_heads": 2, "head_dim": 128}


def _attention_layer(reference, t):
    """``(leaves of one attention layer, x [1, t, width], weights of a scalar loss)``."""
    params = reference.init_params(jax.random.key(0), {**SMALL, **ATTENTION, "pattern": "*"})
    p = jax.tree.map(lambda leaf: leaf[0], params["attn"])
    x = jax.random.normal(jax.random.key(4), (1, t, ATTENTION["width"]))
    return p, x, jax.random.normal(jax.random.key(5), x.shape)


def _own_layer(p, x):
    return hybrid.gqa_attention(p, x, ATTENTION)


def _value_and_gradients(layer, p, x, weigh):
    """The layer's output, and the gradients of a weighted sum of it in ``wq``, ``wk``,
    ``wv``, ``wo`` (and the norm's leaf, which the layer does not read) and in ``x``."""
    weighed = lambda p, x: (layer(p, x) * weigh).sum()
    return jax.jit(layer)(p, x), jax.jit(jax.grad(weighed, (0, 1)))(p, x)


def _the_references(reference, p, x, weigh):
    """The same of the float32 reference's banded layer."""
    return _value_and_gradients(
        lambda p, x: reference._attention(p, x, ATTENTION, IDENTITY), p, x, weigh)


def _assert_same(got, want, rtol=1e-4):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(g - w)) <= rtol * float(jnp.linalg.norm(w)), g.shape


@pytest.mark.parametrize("oracle", ["dense", "reference"])
def test_attention_on_the_kernels_is_the_dense_layer(reference, monkeypatch, kernel_calls, oracle):
    """At 512 positions the layer's attention proper is ``ops.attention``'s two kernels
    (Pallas's interpreter here), grouped: the output and the gradients of the four
    projections and of ``x`` are what ``dense_causal_attention`` gives on the same
    ``q``/``k``/``v``, and what the float32 reference's banded layer gives."""
    p, x, weigh = _attention_layer(reference, 512)
    launches = lambda: kernel_calls(jax.grad(lambda p: _own_layer(p, x).sum()), p)
    assert launches() == {"causal_attention_fwd": 1, "causal_attention_bwd": 1}
    got = _value_and_gradients(_own_layer, p, x, weigh)
    if oracle == "dense":
        monkeypatch.setattr(hybrid, "engages", lambda t: False)
        assert launches() == {}
        want = _value_and_gradients(_own_layer, p, x, weigh)
    else:
        want = _the_references(reference, p, x, weigh)
    _assert_same(got, want)


@pytest.mark.parametrize("t", [32, 640], ids=["under-512", "not-whole-blocks"])
def test_where_the_kernels_do_not_engage_the_dense_spelling_answers(reference, monkeypatch,
                                                                    kernel_calls, t):
    p, x, weigh = _attention_layer(reference, t)
    assert kernel_calls(jax.grad(lambda p: _own_layer(p, x).sum()), p) == {}
    monkeypatch.setattr(reference, "QUERY_BLOCK", 128)  # 640 is five bands of the reference's
    _assert_same(_value_and_gradients(_own_layer, p, x, weigh), _the_references(reference, p, x, weigh))


@pytest.mark.parametrize("kept,forwards", [(True, 1), (False, 2)],
                         ids=["the-models-policy", "a-plain-checkpoint"])
def test_the_lowered_training_step_holds_each_attention_kernel_once(monkeypatch, kept, forwards):
    """The TPU-platform lowering (made on the CPU) of a ``MEM*E`` hybrid's training step at
    512 positions in bfloat16: one forward and one backward kernel module for its one
    attention layer, the layer's checkpoint keeping the forward's two named outputs; a
    plain checkpoint launches the forward a second time."""
    monkeypatch.setattr(attention, "auto_interpret", lambda interpret: False)  # as on the TPU
    if not kept:
        monkeypatch.setattr(decoder, "KEEP_NAMED_OUTPUTS", None)
    model = get_model("hybrid_lm", **{**SMALL, **ATTENTION, "seq_len": 512, "pattern": "MEM*E"})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 512), jnp.int32)

    def loss(params, tokens):
        half = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16), params)
        return -model.apply(half, tokens)[:, 7].mean()

    text = jax.jit(jax.value_and_grad(loss)).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text()
    modules = {kernel: len(re.findall(rf'kernel_name = "{kernel}"', text))
               for kernel in ("causal_attention_fwd", "causal_attention_bwd")}
    assert modules == {"causal_attention_fwd": forwards, "causal_attention_bwd": 1}


def test_counters_count_the_picks_that_land_here(reference, seeded):
    params, tokens = seeded
    _, counters = get_model("hybrid_lm", **SMALL).apply.with_counters(params, tokens)
    assert set(counters) == set(hybrid.COUNTERS)
    # By hand, from the reference's gates: the share of non-zero gates among the held experts.
    hidden = params["embed"][tokens]
    shares, seen = [], {"M": 0, "E": 0, "*": 0}
    for letter in SMALL["pattern"]:
        kind, mixer = reference.MIXERS[letter]
        p = jax.tree.map(lambda leaf: leaf[seen[letter]], params[kind])
        seen[letter] += 1
        normed = reference._rms_norm(p["norm"], hidden, SMALL["eps"])
        if letter == "E":
            gate = reference.gates(p["router"], normed, SMALL)[..., :SMALL["experts_held"]]
            shares.append(float((gate > 0).sum()) / (tokens.size * SMALL["top_k"]))
        hidden = hidden + mixer(p, normed, SMALL, IDENTITY)
    assert float(counters["moe_held_pick_share"]) == pytest.approx(np.mean(shares), abs=1e-6)
    assert 1.0 <= float(counters["moe_load_max_over_mean"]) <= SMALL["experts_held"]


def test_bfloat16_forward_is_finite_and_near_float32(seeded):
    params, tokens = seeded
    model = get_model("hybrid_lm", **SMALL)
    low = model.apply(jax.tree.map(lambda p: p.astype(jnp.bfloat16), params), tokens)
    assert low.dtype == jnp.float32 and bool(jnp.isfinite(low).all())
    assert float(jnp.abs(low - model.apply(params, tokens)).max()) < 0.1


def test_stacked_expert_leaf_shards_neither_stacking_dim():
    tree = jax.eval_shape(get_model("hybrid_lm", **SMALL).init, jax.random.key(0))
    assert tree["moe"]["w_up"].shape == (4, 4, 64, 48)  # [layers, experts, d, f]
    assert param_partition_spec(tree["moe"]["w_up"].shape, 4) == P(None, None, MODEL_AXIS)
    assert param_partition_spec(tree["moe"]["w_down"].shape, 4) == P(None, None, None, MODEL_AXIS)
    assert param_partition_spec(tree["mamba"]["in_proj"].shape, 2) == P(None, None, MODEL_AXIS)


def _one_round(model, x_dtype, classes):
    mesh = make_mesh(devices=jax.devices()[:1])
    training = TrainingConfig(batch_size=2, local_epochs=1, learning_rate=0.01)
    strategy = fedavg_strategy()
    params = model.init(jax.random.key(0))
    step = build_round_step(model.apply, training, mesh, strategy, client_chunk=1, params_like=params)
    k = jax.random.split(jax.random.key(5), 2)
    shape = (2, 4, *model.input_shape)
    x = (jax.random.randint(k[0], shape, 0, classes) if x_dtype == jnp.int32
         else jax.random.normal(k[0], shape))
    data = ClientData(x=x, y=jax.random.randint(k[1], (2, 4), 0, classes), mask=jnp.ones((2, 4)))
    return step(params, init_server_state(strategy, params), data, jnp.full((2,), 4.0),
                jax.random.split(jax.random.key(6), 2))


def test_a_model_with_no_counters_yields_exactly_the_old_metrics():
    result = _one_round(get_model("linear", in_features=8, num_classes=4), jnp.float32, 4)
    assert set(result.metrics) == {"loss", "accuracy", "samples", "participating_clients"}
    assert result.client_metrics.counters == {}
    assert len(jax.tree.leaves(result.client_metrics)) == 3
    assert len(jax.tree.leaves(ClientMetrics(loss=0.0, accuracy=0.0, samples=0.0))) == 3


def test_the_expert_layers_counters_reach_the_rounds_metrics():
    result = _one_round(get_model("hybrid_lm", **SMALL), jnp.int32, SMALL["vocab"])
    assert set(result.metrics) == {"loss", "accuracy", "samples", "participating_clients",
                                   *hybrid.COUNTERS}
    share = float(result.metrics["moe_held_pick_share"])
    assert 0.1 < share < 0.5  # 4 of 16 experts held: 0.25 under uniform routing
    assert result.client_metrics.counters["moe_held_pick_share"].shape == (2,)


@pytest.fixture(scope="module")
def lowered_gradient(seeded):
    params, tokens = seeded
    model = get_model("hybrid_lm", **SMALL)
    return jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum())).lower(params).as_text(debug_info=True)


def test_the_seven_scopes_are_in_the_lowered_program(lowered_gradient):
    for scope in ("ssm_mixer", "ssm_scan", "moe_router", "moe_dispatch", "moe_experts",
                  "moe_shared", "gqa_attention"):
        assert scope in lowered_gradient, scope


@pytest.mark.parametrize("path", [
    "jvp(token_embed)/", "transpose(jvp(token_embed))/",
    "jvp(layer_scan)/squeeze", "transpose(jvp(layer_scan))/",  # the stacked leaves' slices
    "jvp(lm_head)/dot_general", "transpose(jvp(lm_head))/dot_general",
])
def test_the_lookup_and_the_head_have_scopes(lowered_gradient, path):
    assert path in lowered_gradient, path
