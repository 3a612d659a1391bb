"""What the six models with rematerialized layers keep for the backward pass
(``models.moe_decoder``, ``models.latent_moe``, ``models.hybrid``, ``models.indexed_moe``,
whose pick of keys is a third kind of kept output, ``models.gated_moe``, whose gate comes
after the kept output and is computed again, ``models.diffusion_moe``, whose round trains
on the objective it carries, a doubled stream under the block-diffusion mask, where
``apply`` reads one stream): every layer under
``jax.checkpoint`` with ``models.experts.KEEP_NAMED_OUTPUTS`` (``models.decoder.run_layers``,
the one place a layer is rematerialized: ``stack`` here), so the attention kernel's
output and log-sum-exp stay and the kernel is launched once a layer, and the expert
dispatch's three integer outputs stay and it runs once a layer; a plain checkpoint
(the policy taken away, as each test does for its other side) launches and dispatches
twice and computes the same bits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import decoder as stack
from nanofed_tpu.models import experts, get_model
from nanofed_tpu.ops import attention
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig

#: ``(factory, a tiny configuration)``; at 512 positions the kernels engage
#: (Pallas's interpreter here), at 32 the dense spelling answers.  One full and three
#: windowed layers with seven query heads a key/value head; one dense and two expert
#: layers with 24-wide scores over 16-wide values; two mixers, one attention layer with two
#: query heads a key/value head and two expert layers.
DECODERS = {
    "moe_decoder": ("moe_decoder_lm", {
        "vocab": 64, "seq_len": 512, "width": 64, "rope_layout": [0, 1, 1, 1],
        "window_layout": [0, 1, 1, 1], "window": 200, "attn_heads": 7, "kv_heads": 1,
        "head_dim": 16, "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 48}),
    "latent_moe": ("latent_moe_lm", {
        "vocab": 64, "seq_len": 512, "width": 64, "heads": 4, "latent_rank": 32, "nope_dim": 16,
        "rope_dim": 8, "value_dim": 16, "dense_layers": 1, "dense_width": 160, "expert_layers": 2,
        "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 24, "shared_width": 48}),
    "hybrid": ("hybrid_lm", {"vocab": 64, "seq_len": 512, "pattern": "MEM*E", "chunk": 32}),
    # Two layers whose attention runs under a pick of 96 keys, eight query heads a
    # key/value head (at 32 positions: a pick of 8, densely).
    "indexed_moe": ("indexed_moe_lm", {
        "vocab": 64, "seq_len": 512, "width": 64, "layers": 2, "attn_heads": 8, "kv_heads": 1,
        "head_dim": 16, "rope_sections": [2, 3, 3], "index_heads": 4, "index_dim": 8,
        "index_topk": 96, "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 48}),
    # A dense and three expert layers, three of them sliding under a window of 200, eight
    # query heads a key/value head, an output gate after the kernels' kept output.
    "gated_moe": ("gated_moe_lm", {
        "vocab": 64, "seq_len": 512, "width": 64, "sliding_layout": [1, 1, 0, 1], "window": 200,
        "attn_heads": 8, "kv_heads": 1, "head_dim": 16, "dense_layers": 1, "dense_width": 160,
        "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 24, "shared_width": 24}),
    # One layer under the block-diffusion mask in blocks of 4, eight query heads a key/value
    # head: ``apply`` reads one stream of 512, a round trains on the doubled one of 1024.
    "diffusion_moe": ("diffusion_moe_lm", {
        "vocab": 64, "seq_len": 512, "block": 4, "width": 64, "layers": 1, "attn_heads": 8,
        "kv_heads": 1, "head_dim": 16, "experts": 16, "experts_held": 4, "top_k": 3,
        "expert_width": 48}),
}
#: Launches a training step of each holds, forward kernels under the policy first.
LAUNCHES = {
    "moe_decoder": {"causal_attention_fwd": 1, "causal_attention_fwd_window": 3,
                    "causal_attention_bwd": 1, "causal_attention_bwd_window": 3},
    "latent_moe": {"causal_attention_fwd": 3, "causal_attention_bwd": 3},
    "hybrid": {"causal_attention_fwd": 1, "causal_attention_bwd": 1},
    "indexed_moe": {"causal_attention_fwd_keep": 2, "causal_attention_bwd_keep": 2},
    "gated_moe": {"causal_attention_fwd": 1, "causal_attention_fwd_window": 3,
                  "causal_attention_bwd": 1, "causal_attention_bwd_window": 3},
    "diffusion_moe": {"causal_attention_fwd_blocks": 1, "causal_attention_bwd_blocks": 1},
}
#: Expert layers of each: a dispatch, and so one scatter of ``src``, apiece.
EXPERT_LAYERS = {"moe_decoder": 4, "latent_moe": 2, "hybrid": 2, "indexed_moe": 2, "gated_moe": 3,
                 "diffusion_moe": 1}
#: Leaves the loss reads and no step moves: the indexer's three matrices (its pick is a
#: constant of the backward pass).
NEVER_MOVED = {"indexed_moe": 3}

#: Fewer layers of each for the tests that run a step operation by operation.
SHALLOW = {"moe_decoder": {"rope_layout": [0, 1], "window_layout": [0, 1]},
           "latent_moe": {"expert_layers": 1}, "hybrid": {"pattern": "M*E"},
           "indexed_moe": {"layers": 1}, "gated_moe": {"sliding_layout": [1, 0]}, "diffusion_moe": {}}


@pytest.fixture(params=list(DECODERS))
def decoder(request, monkeypatch):
    """``(name, build(**changes) -> (model, params, tokens [1, T]), plainly())``:
    ``plainly()`` leaves the stack a plain ``jax.checkpoint`` for the rest of the test."""
    factory, kwargs = DECODERS[request.param]

    def build(**changes):
        model = get_model(factory, **{**kwargs, **changes})
        tokens = jax.random.randint(jax.random.key(1), (1, model.input_shape[0]), 0, kwargs["vocab"])
        return model, model.init(jax.random.key(0)), tokens

    return request.param, build, lambda: monkeypatch.setattr(stack, "KEEP_NAMED_OUTPUTS", None)


def _training_step(model, tokens, cast=lambda p: p):
    """Loss and every leaf's gradient of one step on the last position's label."""
    def loss(params):
        logp = model.apply(jax.tree.map(cast, params), tokens)
        return -logp[:, 7].mean()

    return jax.value_and_grad(loss)


@pytest.fixture
def dispatches(equations):
    """``dispatches(fn, *args)``: the dispatches ``fn``'s jaxpr runs, the rematerialized
    bodies included, by the one scatter each holds (the write of ``src``, the only
    ``scatter`` equation under ``moe_dispatch``)."""
    return lambda fn, *args: sum(
        eqn.primitive.name == "scatter" and "moe_dispatch" in str(eqn.source_info.name_stack)
        for eqn in equations(fn, *args))


def test_a_training_step_launches_each_attention_kernel_once_a_layer(decoder, kernel_calls):
    name, build, plainly = decoder
    _, params, tokens = build()
    count = lambda: kernel_calls(_training_step(build()[0], tokens), params)
    assert count() == LAUNCHES[name]
    plainly()
    assert count() == {kernel: n * (2 if "fwd" in kernel else 1)
                       for kernel, n in LAUNCHES[name].items()}


def test_a_training_step_lays_the_picks_out_once_an_expert_layer(decoder, dispatches):
    """One dispatch an expert layer where the checkpoint keeps the layout, a second in
    every rematerialized body where it does not."""
    name, build, plainly = decoder
    _, params, tokens = build()
    count = lambda: dispatches(_training_step(build()[0], tokens), params)
    assert count() == EXPERT_LAYERS[name]
    plainly()
    assert count() == 2 * EXPERT_LAYERS[name]


@pytest.mark.parametrize("cast,whole", [(lambda p: p, True), (lambda p: p.astype(jnp.bfloat16), False)],
                         ids=["float32", "bfloat16"])
def test_what_the_checkpoint_keeps_changes_no_bit_of_a_training_step(decoder, cast, whole):
    """Loss and every leaf's gradient equal the plain checkpoint's exactly: the backward
    kernel reads the same two arrays and the expert loop's backward the same three
    integers, kept instead of computed again.  In bfloat16 the
    step runs operation by operation: compiled whole, XLA keeps a bfloat16 value unrounded
    inside a fusion (its excess precision), and the two programs, one forward kernel
    apart, fuse differently around the kernels."""
    name, build, plainly = decoder
    layers = SHALLOW[name]
    _, params, tokens = build(**layers)

    def step():
        fn = _training_step(build(**layers)[0], tokens, cast)
        if whole:
            return jax.jit(fn)(params)
        with jax.disable_jit():
            return fn(params)

    kept = step()
    plainly()
    plain = step()
    moved = [float(jnp.abs(g).sum()) > 0 for g in jax.tree.leaves(plain[1])]
    # One label: the last layer's experts may get none; a kind with no layer has empty leaves.
    assert sum(moved) > len(moved) // 2
    jax.tree.map(np.testing.assert_array_equal, kept, plain)


def test_under_512_positions_the_checkpoint_keeps_the_dispatch_alone(decoder, monkeypatch, equations,
                                                                     dispatches):
    """The dense spelling answers and the only names left are the dispatch's: a
    checkpoint that keeps the kernels' names alone finds nothing to keep and its step is
    the plain checkpoint's program, two dispatches an expert layer; the models' own keeps one."""
    name, build, plainly = decoder
    _, params, tokens = build(seq_len=32)
    step = lambda: _training_step(build(seq_len=32)[0], tokens)
    lowered = lambda: jax.jit(step()).lower(params).as_text()
    named = {eqn.params["name"] for eqn in equations(step(), params) if eqn.primitive.name == "name"}
    assert named == set(experts.KEPT)  # (32 positions under a pick of 96 keys: none is made)
    assert dispatches(step(), params) == EXPERT_LAYERS[name]
    monkeypatch.setattr(stack, "KEEP_NAMED_OUTPUTS", attention.KEEP_KERNEL_OUTPUTS)
    kernels_alone = lowered()
    assert dispatches(step(), params) == 2 * EXPERT_LAYERS[name]
    plainly()
    assert lowered() == kernels_alone


@pytest.fixture
def kernels_in_plain_jax(monkeypatch):
    """``ops.attention``'s two ``pallas_call``s replaced by the same functions of the same
    arguments in plain ``jax.numpy``, each one ``jit`` equation under the kernel's name.
    Pallas's interpreter cannot run on values that vary over a ``shard_map`` axis, so on
    the CPU mesh ``causal_attention`` answers densely there and nothing of its
    ``custom_vjp`` is traced; with the stand-ins it is: the forward rule and its names,
    the residuals, the backward rule around them."""

    def probabilities(q, k, lse, window, keep=None, blocks=None):
        t, hd = q.shape[1:]
        s = jnp.einsum("bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32) / hd ** 0.5
        behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        seen = (behind >= 0) & (behind < (t if window is None else window))
        if blocks is not None:  # the block-diffusion rule in the causal one's place
            seen = attention.block_diffusion_mask(t, *blocks)
        if keep is not None:  # [N, keys, queries], one mask for all a sequence's heads
            seen = seen & jnp.repeat(jnp.swapaxes(keep, 1, 2) != 0, q.shape[0] // keep.shape[0], 0)
        s = jnp.where(seen, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1) if lse is None else lse.reshape(q.shape[:2])
        return jnp.exp(s - lse[..., None]), lse

    def causal_attention_fwd(q, k, v, block, window, keep=None, blocks=None):
        group = q.shape[0] // k.shape[0]
        p, lse = probabilities(q, jnp.repeat(k, group, 0), None, window, keep, blocks)
        o = jnp.einsum("bqk,bkd->bqd", p, jnp.repeat(v, group, 0).astype(jnp.float32))
        return (jnp.swapaxes(o, 1, 2).astype(q.dtype),
                lse.reshape(q.shape[0], q.shape[1] // block, 1, block))

    def causal_attention_bwd(q, k, v, do, lse, delta, block, window, keep=None, blocks=None):
        group = q.shape[0] // k.shape[0]
        k, v = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
        f32 = lambda a: a.astype(jnp.float32)
        p, _ = probabilities(q, k, lse, window, keep, blocks)
        dp = jnp.einsum("bqd,bkd->bqk", f32(do), f32(v))
        ds = p * (dp - delta.reshape(q.shape[0], -1, 1)) / q.shape[-1] ** 0.5
        dq = jnp.einsum("bqk,bkd->bqd", ds, f32(k))
        dk, dv = jnp.einsum("bqk,bqd->bkd", ds, f32(q)), jnp.einsum("bqk,bqd->bkd", p, f32(do))
        if group == 1:  # the kernel writes a group's shares in float32 for its caller to sum
            dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)
        return jnp.swapaxes(dq, 1, 2).astype(q.dtype), dk, dv

    fwd = jax.jit(causal_attention_fwd, static_argnums=(3, 4))
    bwd = jax.jit(causal_attention_bwd, static_argnums=(6, 7))
    # A stand-in under a mask takes the masked kernel's name.
    fwd_keep = jax.jit(lambda *a: causal_attention_fwd(*a), static_argnums=(3, 4))
    bwd_keep = jax.jit(lambda *a: causal_attention_bwd(*a), static_argnums=(6, 7))
    fwd_keep.__wrapped__.__name__ = "causal_attention_fwd_keep"
    bwd_keep.__wrapped__.__name__ = "causal_attention_bwd_keep"
    # ... and one under the block-diffusion mask that branch's.
    fwd_blocks = jax.jit(lambda q, k, v, block, blocks: causal_attention_fwd(
        q, k, v, block, None, None, blocks), static_argnums=(3, 4))
    bwd_blocks = jax.jit(lambda q, k, v, do, lse, delta, block, blocks: causal_attention_bwd(
        q, k, v, do, lse, delta, block, None, None, blocks), static_argnums=(6, 7))
    fwd_blocks.__wrapped__.__name__ = "causal_attention_fwd_blocks"
    bwd_blocks.__wrapped__.__name__ = "causal_attention_bwd_blocks"

    def forward(q, k, v, block, interpret, window=None, keep=None, blocks=None):
        if blocks is not None:
            return fwd_blocks(q, k, v, block, blocks)
        return fwd(q, k, v, block, window) if keep is None else fwd_keep(q, k, v, block, window, keep)

    def backward(q, k, v, do, lse, delta, block, interpret, window=None, keep=None, blocks=None):
        if blocks is not None:
            return bwd_blocks(q, k, v, do, lse, delta, block, blocks)
        return (bwd(q, k, v, do, lse, delta, block, window) if keep is None
                else bwd_keep(q, k, v, do, lse, delta, block, window, keep))

    monkeypatch.setattr(attention, "_forward", forward)
    monkeypatch.setattr(attention, "_backward", backward)
    monkeypatch.setattr(attention, "auto_interpret", lambda interpret: False)


@pytest.mark.parametrize("client_chunk", [None, 1], ids=["vmap", "chunks-of-1"])
def test_the_policy_inside_the_round_program_on_the_cpu_mesh(decoder, client_chunk, dispatches,
                                                             kernels_in_plain_jax, kernel_calls):
    """The ``shard_map`` round over four devices, clients under ``vmap`` or one at a time,
    the local steps a scan: the names reach the layers' checkpoints through all of them
    (one forward launch a layer and one dispatch an expert layer in the round's jaxpr, two
    under a plain checkpoint), and a round leaves the parameters where the plain
    checkpoint's round leaves them."""
    name, build, plainly = decoder
    model, params, _ = build()
    layers = sum(LAUNCHES[name].values()) // 2
    suffix = {"indexed_moe": "_keep", "diffusion_moe": "_blocks"}.get(name, "")
    launches = lambda forward: {f"causal_attention_{kind}{suffix}": n for kind, n in (
        ("fwd", forward * layers), ("bwd", layers)) if n}
    mesh = make_mesh(devices=jax.devices()[:4])
    training = TrainingConfig(batch_size=1, local_epochs=1, learning_rate=0.05)
    strategy = fedavg_strategy()
    k = jax.random.split(jax.random.key(5), 2)
    data = ClientData(x=jax.random.randint(k[0], (4, 2, *model.input_shape), 0, 64),
                      y=jax.random.randint(k[1], (4, 2), 0, 64), mask=jnp.ones((4, 2)))
    args = (params, init_server_state(strategy, params), data, jnp.full((4,), 2.0),
            jax.random.split(jax.random.key(6), 4))

    def one_round():
        step = build_round_step(build()[0].apply, training, mesh, strategy,
                                client_chunk=client_chunk, params_like=params)
        return kernel_calls(step, *args), dispatches(step, *args), step(*args).params

    calls, dispatched, kept = one_round()
    assert (calls, dispatched) == (launches(1), EXPERT_LAYERS[name])
    plainly()
    calls, dispatched, plain = one_round()
    assert (calls, dispatched) == (launches(2), 2 * EXPERT_LAYERS[name])
    moved = [float(jnp.abs(a - b).max()) > 0
             for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(params))]
    assert sum(moved) >= len(moved) - 1 - NEVER_MOVED.get(name, 0)
    jax.tree.map(np.testing.assert_array_equal, kept, plain)
