"""Unit tests for the causal transformer LM (``models.transformer``) and its
synthetic token-stream workload (``data.synthetic_token_streams``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu import nn
from nanofed_tpu.data import synthetic_token_streams
from nanofed_tpu.models import get_model
from nanofed_tpu.models.transformer import (
    FLAGSHIP_CONFIGS,
    apply_sequence,
    flagship,
    init_transformer,
    stack_blocks,
    transformer_param_count,
    unstack_blocks,
)

VOCAB, SEQ, WIDTH, DEPTH, HEADS = 32, 8, 16, 2, 2

#: ``apply`` runs the head on the last position's hidden state alone;
#: ``apply_sequence(...)[:, -1]`` runs it on every position and slices.  The two
#: are the same function, computed by matmuls of different shapes, so float32
#: values agree to rounding and not bit for bit: log-probs of magnitude ~4 and
#: gradients of magnitude <= 1, a few float32 ulps of each.
SLICE_ATOL = 1e-5


@pytest.fixture(scope="module")
def model():
    return get_model(
        "transformer_lm", vocab=VOCAB, seq_len=SEQ, width=WIDTH,
        depth=DEPTH, heads=HEADS,
    )


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def test_registry_and_metadata(model):
    assert model.name == "transformer_lm"
    assert model.token_stream is True
    assert model.input_shape == (SEQ,)
    assert model.num_classes == VOCAB


def test_param_count_matches_analytic(params):
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == transformer_param_count(VOCAB, SEQ, WIDTH, DEPTH)


def test_flagship_configs_build_abstract():
    # eval_shape only — the large config must never materialize in tests
    for name in FLAGSHIP_CONFIGS:
        m = flagship(name)
        abs_p = jax.eval_shape(lambda m=m: m.init(jax.random.key(0)))
        vocab, seq_len, width, depth, _ = FLAGSHIP_CONFIGS[name]
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abs_p))
        assert n == transformer_param_count(vocab, seq_len, width, depth)


def test_apply_returns_last_position_log_probs(model, params):
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (4, SEQ)), jnp.int32
    )
    logp = model.apply(params, x)
    assert logp.shape == (4, VOCAB)
    np.testing.assert_allclose(np.exp(np.asarray(logp)).sum(-1), 1.0, atol=1e-5)
    full = apply_sequence(params, x, heads=HEADS)
    np.testing.assert_allclose(
        np.asarray(full[:, -1]), np.asarray(logp), atol=SLICE_ATOL
    )


@pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
def test_apply_is_head_of_last_hidden_state(name):
    """``apply`` slices the hidden state BEFORE ln_f/head; values and every
    leaf's gradient of the masked NLL (``head`` and ``ln_f`` included) equal
    the full-sequence head sliced afterwards, in float32 to ``SLICE_ATOL``."""
    from nanofed_tpu.trainer.local import make_grad_fn

    m = get_model(
        name, vocab=VOCAB, seq_len=SEQ, width=WIDTH, depth=3, heads=HEADS
    )
    p = m.init(jax.random.key(5))
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.integers(0, VOCAB, (4, SEQ)), jnp.int32)
    y = jnp.asarray(rng.integers(0, VOCAB, (4,)), jnp.int32)
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)

    def sliced_after(q, tokens, *, train=False, rng=None):
        return apply_sequence(q, tokens, heads=HEADS)[:, -1, :]

    got, want = m.apply(p, x), sliced_after(p, x)
    assert got.dtype == jnp.float32 and got.shape == (4, VOCAB)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=SLICE_ATOL)

    g_got, _ = make_grad_fn(m.apply)(p, x, y, mask, jax.random.key(0))
    g_want, _ = make_grad_fn(sliced_after)(p, x, y, mask, jax.random.key(0))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves_with_path(g_want)
    assert [k for k, _ in got_leaves] == [k for k, _ in want_leaves]
    paths = {jax.tree_util.keystr(k) for k, _ in got_leaves}
    assert any("head" in k for k in paths) and any("ln_f" in k for k in paths)
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=SLICE_ATOL,
            err_msg=jax.tree_util.keystr(path),
        )
        # every leaf is reached: the rows dropped carried a cotangent of zero,
        # not a share of the gradient
        assert np.abs(np.asarray(b)).max() > 0.0, jax.tree_util.keystr(path)


def _largest_intermediate(jaxpr) -> int:
    """Most elements any equation of ``jaxpr`` writes, sub-jaxprs (scan and
    jit bodies, custom-derivative rules) included."""
    worst = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            worst = max(worst, int(np.prod(v.aval.shape, dtype=np.int64)))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    worst = max(worst, _largest_intermediate(sub))
    return worst


class TestHeadStaysOffTheSequence:
    """The guard that keeps the head from growing back: in the training step
    (``value_and_grad`` of ``make_grad_fn``'s masked NLL) nothing has
    ``seq_len x vocab`` elements per sequence.  Shapes chosen so that every
    legitimate intermediate is far below that: the widest are the scan's
    stacked MLP residuals (``depth x seq x 4 width`` = 4096 a sequence) and the
    head's kernel (``width x vocab`` = 31,904 in all) against
    ``seq x vocab`` = 15,952 a sequence, 63,808 in the batch of 4."""

    V, T, D, L, H, N = 997, 16, 32, 2, 2, 4

    def _step_jaxpr(self, apply_fn, params):
        from nanofed_tpu.trainer.local import make_grad_fn

        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.integers(0, self.V, (self.N, self.T)), jnp.int32)
        y = jnp.asarray(rng.integers(0, self.V, (self.N,)), jnp.int32)
        mask = jnp.ones((self.N,), jnp.float32)
        grad_fn = make_grad_fn(apply_fn, compute_dtype="bfloat16")
        return jax.make_jaxpr(grad_fn)(params, x, y, mask, jax.random.key(0)).jaxpr

    def _model(self, name):
        return get_model(
            name, vocab=self.V, seq_len=self.T, width=self.D, depth=self.L,
            heads=self.H,
        )

    @pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
    def test_no_sequence_by_vocab_intermediate(self, name):
        m = self._model(name)
        params = m.init(jax.random.key(0))
        worst = _largest_intermediate(self._step_jaxpr(m.apply, params))
        assert worst < self.N * self.T * self.V, (
            f"{name}: an intermediate of {worst} elements — the head runs over "
            f"the sequence again ({self.N} x {self.T} x {self.V} = "
            f"{self.N * self.T * self.V})"
        )

    @pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
    def test_the_walk_sees_a_full_sequence_head(self, name):
        """Control: the same walk over the step built on
        ``apply_sequence(...)[:, -1]`` finds the ``[N, T, vocab]`` tensors."""
        m = self._model(name)
        params = m.init(jax.random.key(0))

        def full_head(p, x, *, train=False, rng=None):
            return apply_sequence(p, x, heads=self.H)[:, -1, :]

        worst = _largest_intermediate(self._step_jaxpr(full_head, params))
        assert worst >= self.N * self.T * self.V


def test_the_walk_descends_into_loop_and_jit_bodies():
    """A tensor that exists only inside a ``scan`` body inside a ``jit`` is
    counted — a head wrapped in either could not hide from the guard."""

    @jax.jit
    def f(x):
        def body(c, _):
            return c + jnp.ones((7, 11, 13)).sum(), None

        return jax.lax.scan(body, x, None, length=3)[0]

    assert _largest_intermediate(jax.make_jaxpr(f)(0.0).jaxpr) == 7 * 11 * 13


def _square_intermediates(jaxpr, t) -> list[tuple]:
    """Shapes of every array some equation of ``jaxpr`` writes whose two trailing axes
    are both ``t`` — sub-jaxprs (scan and jit bodies, custom-derivative rules)
    included, a kernel's body excepted: what a ``pallas_call`` holds lives in VMEM."""
    found = []
    for eqn in jaxpr.eqns:
        found += [v.aval.shape for v in eqn.outvars
                  if getattr(v.aval, "shape", ())[-2:] == (t, t)]
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _square_intermediates(sub, t)
    return found


class TestAttentionFormFollowsTheSequence:
    """``_attention`` reads its form off the sequence length (``ops.attention.engages``):
    block by block from 512 positions in whole blocks, dense below.  No option says
    so."""

    V, D, L, H, N = 61, 32, 2, 2, 2

    def _step_jaxpr(self, name, seq_len):
        from nanofed_tpu.trainer.local import make_grad_fn

        m = get_model(name, vocab=self.V, seq_len=seq_len, width=self.D, depth=self.L,
                      heads=self.H)
        params = jax.eval_shape(m.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((self.N, seq_len), jnp.int32)
        y = jax.ShapeDtypeStruct((self.N,), jnp.int32)
        mask = jax.ShapeDtypeStruct((self.N,), jnp.float32)
        grad_fn = make_grad_fn(m.apply, compute_dtype="bfloat16")
        return jax.make_jaxpr(grad_fn)(params, x, y, mask, jax.random.key(0)).jaxpr

    @pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
    @pytest.mark.parametrize("seq_len", [512, 1024])
    def test_no_score_tensor_in_the_training_step(self, name, seq_len):
        found = _square_intermediates(self._step_jaxpr(name, seq_len), seq_len)
        assert not found, f"{name}: [.., {seq_len}, {seq_len}] arrays outside a kernel: {found}"

    @pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
    def test_the_walk_sees_a_dense_score_tensor(self, name):
        """Control: 384 positions are not whole blocks, the dense form runs, and the same
        walk finds its ``[N, H, T, T]`` scores."""
        found = _square_intermediates(self._step_jaxpr(name, 384), 384)
        assert (self.N, self.H, 384, 384) in found

    @pytest.mark.parametrize("name,traces", [("transformer_lm", DEPTH),
                                             ("transformer_lm_scan", 1)])
    @pytest.mark.parametrize("seq_len,path,other", [(SEQ, "dense", "blockwise"),
                                                    (512, "blockwise", "dense")])
    def test_counter_reads_what_was_traced(self, name, traces, seq_len, path, other):
        from nanofed_tpu.models.transformer import ATTENTION_TRACES
        from nanofed_tpu.observability.registry import get_registry

        m = get_model(name, vocab=VOCAB, seq_len=seq_len, width=WIDTH, depth=DEPTH,
                      heads=HEADS)
        counter = get_registry().counter(ATTENTION_TRACES, labels=("path",))
        before = {p: counter.value(path=p) for p in (path, other)}
        jax.eval_shape(m.apply, jax.eval_shape(m.init, jax.random.key(0)),
                       jax.ShapeDtypeStruct((2, seq_len), jnp.int32))
        # one trace a block unrolled, one for the scanned body
        assert counter.value(path=path) - before[path] == traces
        assert counter.value(path=other) == before[other]

    @pytest.mark.parametrize("seq_len", [SEQ, 512])
    def test_named_scope_is_in_the_lowered_program(self, seq_len):
        m = get_model("transformer_lm_scan", vocab=VOCAB, seq_len=seq_len, width=WIDTH,
                      depth=DEPTH, heads=HEADS)
        params = jax.eval_shape(m.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((2, seq_len), jnp.int32)
        text = jax.jit(m.apply).lower(params, x).as_text(debug_info=True)
        assert "causal_attention" in text

    @pytest.fixture(scope="class", params=["transformer_lm", "transformer_lm_scan"])
    def lowered_gradient(self, request):
        m = get_model(request.param, vocab=VOCAB, seq_len=SEQ, width=WIDTH, depth=DEPTH,
                      heads=HEADS)
        params = jax.eval_shape(m.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
        grad = jax.jit(jax.grad(lambda p, x: m.apply(p, x).sum()))
        return request.param, grad.lower(params, x).as_text(debug_info=True)

    @pytest.mark.parametrize("scope", ["token_embed", "layer_scan", "attention_proj",
                                       "mlp_block", "lm_head"])
    def test_the_block_and_the_head_have_scopes_forward_and_backward(self, lowered_gradient,
                                                                     scope):
        name, text = lowered_gradient
        if scope not in ("attention_proj", "mlp_block"):
            assert f"/jvp({scope})/" in text and f"/transpose(jvp({scope}))/" in text
        elif name == "transformer_lm_scan":  # the scanned body's paths start at the body
            assert f'"{scope}/' in text
        else:  # a block's scopes nest in the trunk's
            assert f"/jvp(layer_scan)/{scope}/" in text
            assert f"/transpose(jvp(layer_scan))/{scope}/" in text

    @pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
    def test_short_sequences_keep_their_values_bit_for_bit(self, name, monkeypatch):
        """At the file's ``SEQ`` the dense form runs and ``apply`` returns what it did
        before the blockwise form existed: the same eight lines, spelled here as they
        stood, give identical bits."""
        import math

        from nanofed_tpu import nn
        from nanofed_tpu.models import transformer

        def attention_as_it_stood(params, x, heads):
            n, t, d = x.shape
            hd = d // heads
            split = lambda y: y.reshape(n, t, heads, hd).transpose(0, 2, 1, 3)
            q = split(nn.dense(params["wq"], x))
            k = split(nn.dense(params["wk"], x))
            v = split(nn.dense(params["wv"], x))
            scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / math.sqrt(hd)
            causal = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(causal[None, None], scores, jnp.finfo(scores.dtype).min)
            att = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("nhqk,nhkd->nhqd", att, v)
            return nn.dense(params["wo"], out.transpose(0, 2, 1, 3).reshape(n, t, d))

        m = get_model(name, vocab=VOCAB, seq_len=SEQ, width=WIDTH, depth=DEPTH, heads=HEADS)
        p = m.init(jax.random.key(4))
        x = jnp.asarray(np.random.default_rng(4).integers(0, VOCAB, (3, SEQ)), jnp.int32)
        now = (np.asarray(m.apply(p, x)), np.asarray(apply_sequence(p, x, heads=HEADS)))
        monkeypatch.setattr(transformer, "_attention", attention_as_it_stood)
        then = (np.asarray(m.apply(p, x)), np.asarray(apply_sequence(p, x, heads=HEADS)))
        for a, b in zip(now, then):
            np.testing.assert_array_equal(a, b)


def _tail_as_it_stood(fc2, h):
    """The block's last line as it stood: plain autodiff of the tanh GELU and of fc2
    keeps six arrays of ``h``'s shape (``h``, its square, the tanh, the cdf, the product
    and fc2's input)."""
    from nanofed_tpu import nn

    return nn.dense(fc2, jax.nn.gelu(h))


class TestMlpTailKeepsItsInputAlone:
    """``_mlp_tail`` is ``fc2(gelu(h))`` whose backward is handed ``h`` and nothing else
    of that width: GELU, its derivative and fc2's input are recomputed from it.  The
    forward is the old line's; the gradients are plain autodiff's.  No option says so."""

    V, T, D, L, H, N = 61, 16, 32, 3, 2, 2
    LAYOUTS = ["transformer_lm", "transformer_lm_scan"]

    def _model(self, name):
        return get_model(name, vocab=self.V, seq_len=self.T, width=self.D, depth=self.L,
                         heads=self.H)

    def _batch(self):
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.integers(0, self.V, (self.N, self.T)), jnp.int32)
        y = jnp.asarray(rng.integers(0, self.V, (self.N,)), jnp.int32)
        return x, y, jnp.ones((self.N,), jnp.float32)

    @staticmethod
    def _put_the_line_back(monkeypatch):
        from nanofed_tpu.models import transformer

        monkeypatch.setattr(transformer, "_mlp_tail", _tail_as_it_stood)

    def _wide_residuals(self, name):
        """Shapes of what the training loss's VJP saves with trailing ``[T, 4 width]``."""
        from jax._src.ad_checkpoint import saved_residuals

        m = self._model(name)
        x, y, _ = self._batch()

        def loss(p):  # make_grad_fn's: bfloat16 compute on float32 parameters
            p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
            logp = m.apply(p, x, train=True).astype(jnp.float32)
            return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

        saved = saved_residuals(loss, jax.eval_shape(m.init, jax.random.key(0)))
        return [a.shape for a, _ in saved if a.shape[-2:] == (self.T, 4 * self.D)]

    @pytest.mark.parametrize("name,per_block", [
        ("transformer_lm", [(N, T, 4 * D)] * L),  # one a block
        ("transformer_lm_scan", [(L, N, T, 4 * D)]),  # one stacked scan output
    ])
    def test_one_wide_residual_a_block(self, name, per_block):
        assert self._wide_residuals(name) == per_block

    @pytest.mark.parametrize("name,count", [("transformer_lm", 6 * L),
                                            ("transformer_lm_scan", 6)])
    def test_the_count_sees_six_in_the_plain_spelling(self, name, count, monkeypatch):
        """Control: the same count over the line as it stood."""
        self._put_the_line_back(monkeypatch)
        assert len(self._wide_residuals(name)) == count

    @pytest.mark.parametrize("name", LAYOUTS)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    def test_forward_is_the_plain_spelling_bit_for_bit(self, name, dtype, monkeypatch):
        m = self._model(name)
        p = jax.tree.map(lambda a: a.astype(dtype), m.init(jax.random.key(1)))
        x, _, _ = self._batch()
        now = (m.apply(p, x), apply_sequence(p, x, heads=self.H))
        self._put_the_line_back(monkeypatch)
        then = (m.apply(p, x), apply_sequence(p, x, heads=self.H))
        for a, b in zip(now, then):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def _assert_grads_equal(self, got, want):
        """The same arithmetic, recomputed: every leaf's gradient is autodiff's own, in
        float32 and in bfloat16 alike."""
        got_leaves = jax.tree_util.tree_leaves_with_path(got)
        want_leaves = jax.tree_util.tree_leaves_with_path(want)
        assert [k for k, _ in got_leaves] == [k for k, _ in want_leaves]
        for (path, a), (_, b) in zip(got_leaves, want_leaves):
            assert np.abs(np.asarray(b)).max() > 0.0, jax.tree_util.keystr(path)
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("name", LAYOUTS)
    @pytest.mark.parametrize("compute_dtype", [None, "bfloat16"], ids=["f32", "bf16"])
    def test_gradients_are_plain_autodiffs(self, name, compute_dtype, monkeypatch):
        """Every leaf's gradient of the training loss against autodiff of the line as it
        stood; the scanned layout is ``grad`` through ``lax.scan``."""
        from nanofed_tpu.trainer.local import make_grad_fn

        m = self._model(name)
        p = m.init(jax.random.key(2))
        batch = (*self._batch(), jax.random.key(0))
        got, _ = make_grad_fn(m.apply, compute_dtype=compute_dtype)(p, *batch)
        self._put_the_line_back(monkeypatch)
        want, _ = make_grad_fn(m.apply, compute_dtype=compute_dtype)(p, *batch)
        self._assert_grads_equal(got, want)

    @pytest.mark.parametrize("name", LAYOUTS)
    def test_per_example_gradients_under_vmap(self, name, monkeypatch):
        """The private trainer's nesting: ``vmap`` over examples of ``grad``."""
        from nanofed_tpu.trainer.local import make_grad_fn

        m = self._model(name)
        p = m.init(jax.random.key(3))
        x, y, mask = self._batch()

        def per_example():
            one = lambda xi, yi, mi: make_grad_fn(m.apply)(
                p, xi[None], yi[None], mi[None], jax.random.key(0))[0]
            return jax.vmap(one)(x, y, mask)

        got = per_example()
        self._put_the_line_back(monkeypatch)
        self._assert_grads_equal(got, per_example())

    @pytest.mark.parametrize("name", LAYOUTS)
    def test_gradients_through_the_adapter_apply(self, name, monkeypatch):
        """``make_adapter_apply`` merges ``W + s A B`` and calls ``apply``: the adapters'
        gradients are autodiff's too."""
        from nanofed_tpu.adapters.lora import AdapterSpec, init_adapters, make_adapter_apply
        from nanofed_tpu.trainer.local import make_grad_fn

        m = self._model(name)
        base = m.init(jax.random.key(4))
        spec = AdapterSpec(rank=2)
        rng = np.random.default_rng(5)
        # B starts at zero, where A's gradient is zero: move every leaf off it
        adapters = jax.tree.map(
            lambda a: a + 0.01 * rng.standard_normal(a.shape).astype(np.float32),
            init_adapters(spec, base, rng=0))
        batch = (*self._batch(), jax.random.key(0))
        grads = lambda: make_grad_fn(make_adapter_apply(m.apply, spec, base))(adapters, *batch)[0]
        got = grads()
        self._put_the_line_back(monkeypatch)
        self._assert_grads_equal(got, grads())


def test_causality(params):
    """Perturbing token t must not change any position < t — the causal mask
    is load-bearing, not decorative."""
    rng = np.random.default_rng(1)
    x = rng.integers(0, VOCAB, (2, SEQ)).astype(np.int32)
    full = apply_sequence(params, jnp.asarray(x), heads=HEADS)
    for t in (SEQ - 1, SEQ // 2):
        x2 = x.copy()
        x2[:, t] = (x2[:, t] + 1) % VOCAB
        full2 = apply_sequence(params, jnp.asarray(x2), heads=HEADS)
        np.testing.assert_allclose(
            np.asarray(full[:, :t]), np.asarray(full2[:, :t]), atol=1e-6
        )
        # ...and positions >= t DO change (the perturbation is visible forward)
        assert not np.allclose(np.asarray(full[:, t:]), np.asarray(full2[:, t:]))


def test_width_must_divide_heads():
    with pytest.raises(ValueError, match="divisible"):
        get_model("transformer_lm", width=10, heads=4)


def test_token_streams_shapes_and_determinism():
    ds = synthetic_token_streams(64, vocab=VOCAB, seq_len=SEQ, seed=3)
    assert ds.x.shape == (64, SEQ) and ds.x.dtype == np.int32
    assert ds.y.shape == (64,) and ds.y.dtype == np.int32
    assert ds.x.min() >= 0 and ds.x.max() < VOCAB
    assert ds.y.min() >= 0 and ds.y.max() < VOCAB
    ds2 = synthetic_token_streams(64, vocab=VOCAB, seq_len=SEQ, seed=3)
    np.testing.assert_array_equal(ds.x, ds2.x)
    np.testing.assert_array_equal(ds.y, ds2.y)


def test_token_streams_split_discipline():
    """Different sample seeds draw different sequences from the SAME chain —
    train/test describe one language (the split rule of
    synthetic_classification, carried over)."""
    a = synthetic_token_streams(16384, vocab=8, seq_len=4, seed=0)
    b = synthetic_token_streams(16384, vocab=8, seq_len=4, seed=1)
    assert not np.array_equal(a.x, b.x)

    # The bigram distribution of both splits matches the shared chain: compare
    # empirical next-token marginals conditioned on the last token.
    def cond(ds):
        out = np.zeros((8, 8))
        for last, nxt in zip(ds.x[:, -1], ds.y):
            out[last, nxt] += 1
        return out / np.maximum(out.sum(1, keepdims=True), 1)

    assert np.abs(cond(a) - cond(b)).max() < 0.15


def test_token_streams_learnable_structure():
    """The chain is peaked: the optimal conditional entropy is well below
    log(vocab), so an LM that learns transitions shows a real loss drop."""
    ds = synthetic_token_streams(8192, vocab=16, seq_len=4, seed=0)
    # Empirical conditional entropy H(y | last token), in nats:
    joint = np.zeros((16, 16))
    for last, nxt in zip(ds.x[:, -1], ds.y):
        joint[last, nxt] += 1
    p_last = joint.sum(1) / joint.sum()
    cond = joint / np.maximum(joint.sum(1, keepdims=True), 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        h = -np.nansum(cond * np.where(cond > 0, np.log(cond), 0.0), axis=1)
    h_cond = float((p_last * h).sum())
    assert h_cond < 0.8 * np.log(16)


def test_token_streams_validation():
    with pytest.raises(ValueError):
        synthetic_token_streams(8, vocab=1)
    with pytest.raises(ValueError):
        synthetic_token_streams(8, seq_len=0)


class TestScanLayers:
    """scan_layers=True must be bit-compatible at init (same RNG splits,
    stacked) and numerically equivalent at apply (lax.scan over one block
    body instead of L unrolled blocks)."""

    @pytest.fixture(scope="class")
    def unrolled(self):
        return init_transformer(jax.random.key(7), VOCAB, SEQ, WIDTH, 3)

    @pytest.fixture(scope="class")
    def scanned(self):
        return init_transformer(
            jax.random.key(7), VOCAB, SEQ, WIDTH, 3, scan_layers=True
        )

    def test_stacked_leaves_are_exact_stacks(self, unrolled, scanned):
        for i in range(3):
            per_layer = jax.tree.map(lambda s, i=i: s[i], scanned["blocks"])
            flat_s = jax.tree.leaves(per_layer)
            flat_u = jax.tree.leaves(unrolled[f"block_{i}"])
            for s, u in zip(flat_s, flat_u):
                np.testing.assert_array_equal(np.asarray(s), np.asarray(u))

    def test_logits_parity(self, unrolled, scanned):
        x = jnp.asarray(
            np.random.default_rng(0).integers(0, VOCAB, (4, SEQ)), jnp.int32
        )
        lu = apply_sequence(unrolled, x, heads=HEADS)
        ls = apply_sequence(scanned, x, heads=HEADS)
        np.testing.assert_allclose(np.asarray(lu), np.asarray(ls), atol=1e-5)

    def test_model_apply_parity(self):
        mu = get_model(
            "transformer_lm", vocab=VOCAB, seq_len=SEQ, width=WIDTH,
            depth=3, heads=HEADS,
        )
        ms = get_model(
            "transformer_lm_scan", vocab=VOCAB, seq_len=SEQ, width=WIDTH,
            depth=3, heads=HEADS,
        )
        assert ms.name == "transformer_lm_scan"
        pu = mu.init(jax.random.key(0))
        ps = ms.init(jax.random.key(0))
        x = jnp.asarray(
            np.random.default_rng(2).integers(0, VOCAB, (4, SEQ)), jnp.int32
        )
        np.testing.assert_allclose(
            np.asarray(mu.apply(pu, x)), np.asarray(ms.apply(ps, x)), atol=1e-5
        )

    def test_stack_unstack_round_trip(self, unrolled, scanned):
        stacked = stack_blocks(unrolled)
        for s, t in zip(jax.tree.leaves(stacked), jax.tree.leaves(scanned)):
            np.testing.assert_array_equal(np.asarray(s), np.asarray(t))
        back = unstack_blocks(scanned)
        for s, t in zip(jax.tree.leaves(back), jax.tree.leaves(unrolled)):
            np.testing.assert_array_equal(np.asarray(s), np.asarray(t))

    def test_stack_blocks_requires_unrolled(self, scanned):
        with pytest.raises(ValueError, match="no block_"):
            stack_blocks(scanned)

    def test_param_count_invariant(self, scanned):
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(scanned))
        assert n == transformer_param_count(VOCAB, SEQ, WIDTH, 3)

    def test_grad_parity(self, unrolled, scanned):
        """Training trajectories match: grads through the scan equal grads
        through the unrolled loop (up to stacking)."""
        x = jnp.asarray(
            np.random.default_rng(3).integers(0, VOCAB, (4, SEQ)), jnp.int32
        )
        y = jnp.asarray(
            np.random.default_rng(4).integers(0, VOCAB, (4,)), jnp.int32
        )

        def loss(p):
            logp = apply_sequence(p, x, heads=HEADS)[:, -1]
            return -jnp.mean(logp[jnp.arange(4), y])

        gu = jax.grad(loss)(unrolled)
        gs = jax.grad(loss)(scanned)
        np.testing.assert_allclose(
            np.asarray(gu["tok_emb"]), np.asarray(gs["tok_emb"]), atol=1e-5
        )
        gu_stacked = stack_blocks({**{k: v for k, v in gu.items()
                                      if k.startswith("block_")}})
        for a, b in zip(
            jax.tree.leaves(gu_stacked["blocks"]),
            jax.tree.leaves(gs["blocks"]),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_flagship_scan_passthrough(self):
        m = flagship("tiny", scan_layers=True)
        assert m.name == "transformer_lm_scan"
        abs_p = jax.eval_shape(lambda: m.init(jax.random.key(0)))
        vocab, seq_len, width, depth, _ = FLAGSHIP_CONFIGS["tiny"]
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abs_p))
        assert n == transformer_param_count(vocab, seq_len, width, depth)
        # the stacked subtree exists with leading depth dim
        assert abs_p["blocks"]["attn"]["wq"]["kernel"].shape[0] == depth


@pytest.mark.parametrize("name", ["transformer_lm", "transformer_lm_scan"])
def test_gradient_is_the_same_with_the_embedding_gradient_in_bands(name, monkeypatch):
    """At a width of two lane tiles and a budget of one, ``nn.embed_rows`` accumulates the
    table's gradient band by band: every leaf's gradient is what one band gives."""
    m = get_model(name, vocab=VOCAB, seq_len=SEQ, width=256, depth=DEPTH, heads=HEADS)
    p = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (3, SEQ), 0, 5)  # rows drawn many times
    grads = lambda: jax.grad(lambda q: m.apply(q, tokens)[:, 5].sum())(p)
    whole = grads()
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", VOCAB * 128 * 4)
    assert nn.embed_bands(VOCAB, 256, 4) == 2
    jax.tree.map(np.testing.assert_array_equal, grads(), whole)


def test_grad_fn_keeps_integer_inputs_integer(model, params):
    """bf16 mixed precision must not cast token ids (they index the embedding
    table) — regression for the make_grad_fn dtype guard."""
    from nanofed_tpu.trainer.local import make_grad_fn

    grad_fn = make_grad_fn(model.apply, compute_dtype="bfloat16")
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (4, SEQ)), jnp.int32
    )
    y = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (4,)), jnp.int32)
    m = jnp.ones((4,), jnp.float32)
    grads, stats = grad_fn(params, x, y, m, jax.random.key(0))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    assert float(stats.count) == 4.0
