"""``models.experts.dispatch``: the layout of the picks that land on held experts, held to
its specification — the picks in a stable order by expert, every expert's padded to whole
blocks, an idle expert's to one empty block — written out here with ``numpy``'s stable
sort, which the program does without.  Then the held experts' kernels
(``models.experts.expert_tiles`` over ``ops.experts``), in the interpreter, against the
loop they stand in for on the TPU (``models.experts.expert_blocks``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation.base import fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import experts, get_model
from nanofed_tpu.parallel.mesh import make_mesh
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer import TrainingConfig


def layout_by_sorting(picks, first_expert, held, block):
    """``(src, block_expert, n_blocks)`` as the specification has them."""
    n, top_k = picks.shape
    local = picks.reshape(-1) - first_expert
    key = np.where((local >= 0) & (local < held), local, held)  # held: lands elsewhere
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=held + 1)[:held]
    rows = -(-(n * min(top_k, held) + held * block) // block) * block
    src = np.full(rows, n * top_k)
    block_expert = np.full(rows // block, held - 1)  # past the blocks in use: the last expert
    row = first = 0
    for expert, count in enumerate(counts):
        src[row:row + count] = order[first:first + count]
        blocks = max(-(-count // block), 1)  # an idle expert: one empty block
        block_expert[row // block:row // block + blocks] = expert
        row, first = row + blocks * block, first + count
    return src, block_expert, row // block


def _routed(seed, n, top_k, experts_):
    """A token's picks are distinct experts, as every router of the zoo gives them."""
    scores = np.random.default_rng(seed).normal(size=(n, experts_))
    return np.argsort(-scores, axis=1)[:, :top_k].astype(np.int32)


#: ``(n, top_k, experts, held, first_expert, block)``: the four cells' routing with the
#: tokens and the block cut to CPU size, then the edges.
LAYOUTS = {
    "hybrid": (96, 6, 128, 8, 0, 24),
    "smallthinker": (128, 6, 64, 16, 0, 16),
    "moonlight": (128, 6, 64, 8, 0, 16),
    "keye": (128, 8, 128, 16, 0, 12),
    "held-further-along": (64, 3, 16, 4, 8, 8),
    "one-held": (64, 3, 8, 1, 5, 8),
    "more-picks-than-held": (48, 6, 8, 2, 3, 16),
    "a-block-of-one-row": (40, 2, 6, 3, 0, 1),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("seed", [0, 1])
def test_the_dispatch_lays_the_picks_out_as_a_stable_sort_would(layout, seed):
    n, top_k, experts_, held, first_expert, block = LAYOUTS[layout]
    picks = _routed(seed, n, top_k, experts_)
    got = jax.jit(experts.dispatch, static_argnames=("first_expert", "held", "block"))(
        picks, first_expert=first_expert, held=held, block=block)
    want = layout_by_sorting(picks, first_expert, held, block)
    for g, w in zip(got[:3], want):
        np.testing.assert_array_equal(g, w)
    assert (np.asarray(got[0]) < n * top_k).sum() == int(got[3].sum())  # rows taken: picks landed


#: Picks chosen by hand: ``(picks [n, top_k], held, first_expert, block)``.
EDGES = {
    "no-pick-lands-here": (np.full((12, 2), [9, 11], np.int32), 4, 0, 4),
    "every-pick-on-one-held-expert": (np.full((12, 1), 2, np.int32), 4, 0, 4),
    "every-pick-on-the-one-held-expert": (np.full((12, 1), 5, np.int32), 1, 5, 8),
    # Experts 0 and 1 get 8 rows each, two blocks of 4 to the row; expert 2 none.
    "counts-that-fill-their-blocks": (np.tile(np.array([[0, 1]], np.int32), (8, 1)), 3, 0, 4),
    "one-pick": (np.array([[3]], np.int32), 4, 0, 2),
}


@pytest.mark.parametrize("edge", list(EDGES))
def test_the_dispatch_at_the_edges_of_the_routing(edge):
    picks, held, first_expert, block = EDGES[edge]
    src, block_expert, n_blocks, counts, ends = experts.dispatch(
        jnp.asarray(picks), first_expert=first_expert, held=held, block=block)
    want_src, want_block_expert, want_blocks = layout_by_sorting(picks, first_expert, held, block)
    np.testing.assert_array_equal(src, want_src)
    np.testing.assert_array_equal(block_expert, want_block_expert)
    assert int(n_blocks) == want_blocks == int(ends[-1]) // block
    local = picks.reshape(-1) - first_expert
    np.testing.assert_array_equal(counts, [(local == e).sum() for e in range(held)])
    if edge == "no-pick-lands-here":
        assert want_blocks == held and (want_src == picks.size).all()
    if edge == "counts-that-fill-their-blocks":
        assert want_blocks == 5 and (want_src[:16] < picks.size).all()


def test_no_sort_under_the_dispatchs_scope(equations):
    """The picks are ranked by counting: neither ``held_experts``' jaxpr, its backward
    pass included, nor the program it lowers to holds a sort; the dispatch's one
    operation that is not dense is the scatter of ``src``."""
    k = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k[0], (40, 32))
    w_in, w_out = 0.2 * jax.random.normal(k[1], (4, 32, 24)), 0.2 * jax.random.normal(k[2], (4, 12, 32))
    picks, weights = jnp.asarray(_routed(0, 40, 3, 8)), jnp.full((40, 3), 1 / 3)

    def step(x, w_in, w_out):
        return experts.held_experts(x, picks, weights, w_in, w_out, first_expert=0, block=8,
                                    activation=experts.REGLU)[0].sum()

    step = jax.value_and_grad(step, argnums=(0, 1, 2))
    eqns = equations(step, x, w_in, w_out)
    under = [eqn.primitive.name for eqn in eqns if "moe_dispatch" in str(eqn.source_info.name_stack)]
    assert under.count("scatter") == 1 and "sort" not in under and "gather" not in under
    assert "sort" not in {eqn.primitive.name for eqn in eqns}
    lowered = jax.jit(step).lower(x, w_in, w_out).as_text()
    assert "stablehlo.scatter" in lowered and not re.search(r"stablehlo\.sort\b", lowered)


# ---------------------------------------------------------------------------
# The kernels (``expert_tiles`` over ``ops.experts``), in the interpreter, against the loop.
# ---------------------------------------------------------------------------

#: ``(n, top_k, experts, held, first_expert, d, f, activation, block)``: the five cells'
#: routing and activation with the tokens, the widths and the block cut to CPU size.
KERNEL_CELLS = {
    "hybrid": (96, 6, 128, 8, 0, 32, 24, experts.RELU2, 8),
    "smallthinker": (128, 6, 64, 16, 0, 32, 16, experts.REGLU, 8),
    "moonlight": (128, 6, 64, 8, 0, 32, 24, experts.SWIGLU, 8),
    "keye": (128, 8, 128, 16, 0, 32, 16, experts.SWIGLU, 8),
    "trinity": (128, 8, 128, 8, 0, 32, 16, experts.SWIGLU, 8),
    "one-held-further-along": (64, 3, 8, 1, 5, 32, 16, experts.SWIGLU, 8),
}
#: Picks chosen by hand, one a token, three experts held from expert 2 on, blocks of 4:
#: expert 2 gets no row (one empty block), expert 3 a single row, expert 4 thirty (eight
#: blocks), and five picks land elsewhere.
BY_HAND = np.array([[3]] + [[4]] * 30 + [[0]] * 5, np.int32)


def _operands(picks, held, d, f, activation, seed=0):
    """``(x, gate, w_in, w_out, d_out)`` in float32 for ``picks`` [n, top_k]."""
    n, top_k = picks.shape
    k = jax.random.split(jax.random.key(seed), 5)
    f_in = f if activation is experts.RELU2 else 2 * f
    return (jax.random.normal(k[0], (n, d)), jax.random.uniform(k[1], (n * top_k,)),
            0.3 * jax.random.normal(k[2], (held, d, f_in)), 0.3 * jax.random.normal(k[3], (held, f, d)),
            jax.random.normal(k[4], (n, d)))


def _value_and_gradients(spelling, picks, first_expert, held, block, activation, operands, *extra):
    """``(out, dx, d_gate, d_w_in, d_w_out)`` of one spelling of the held experts."""
    *inputs, d_out = operands
    layout = experts.dispatch(jnp.asarray(picks), first_expert=first_expert, held=held, block=block)[:3]
    out, pull = jax.vjp(lambda x, gate, w_in, w_out: spelling(
        x, gate, *layout, w_in, w_out, activation, block, *extra), *inputs)
    return (out, *pull(d_out))


def _assert_the_spellings_agree(got, want):
    for name, g, w in zip(("out", "dx", "d_gate", "d_w_in", "d_w_out"), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(jnp.abs(w).max() + 1), err_msg=name)


@pytest.mark.parametrize("cell", list(KERNEL_CELLS))
def test_the_kernels_give_the_loops_value_and_its_four_gradients(cell):
    n, top_k, experts_, held, first_expert, d, f, activation, block = KERNEL_CELLS[cell]
    picks = _routed(0, n, top_k, experts_)
    operands = _operands(picks, held, d, f, activation)
    args = (picks, first_expert, held, block, activation, operands)
    _assert_the_spellings_agree(_value_and_gradients(experts.expert_tiles, *args, True),
                                _value_and_gradients(experts.expert_blocks, *args))


@pytest.mark.parametrize("chunk", [8, 24, 56, 152, 1024])
def test_the_rows_move_in_chunks_that_need_not_divide_the_layout(chunk, monkeypatch):
    """The gathers and scatters around the kernels run over chunks of ``CHUNK`` rows of
    the rows in use; the last chunk is moved back inside the layout (128 * 6 + 16 * 8 = 896
    rows here, of which ~220 in use) and its rows that the chunk before held are not
    added twice."""
    monkeypatch.setattr(experts, "CHUNK", chunk)
    n, top_k, experts_, held, first_expert, d, f, activation, block = KERNEL_CELLS["smallthinker"]
    picks = _routed(1, n, top_k, experts_)
    operands = _operands(picks, held, d, f, activation, seed=2)
    args = (picks, first_expert, held, block, activation, operands)
    _assert_the_spellings_agree(_value_and_gradients(experts.expert_tiles, *args, True),
                                _value_and_gradients(experts.expert_blocks, *args))


@pytest.mark.parametrize("activation", [experts.RELU2, experts.REGLU, experts.SWIGLU],
                         ids=["relu2", "reglu", "swiglu"])
def test_the_kernels_with_an_idle_expert_a_single_row_and_many_blocks(activation):
    """No row, one row, eight blocks: the idle expert's two weight gradients are zeros the
    kernel wrote (its one empty block), not what happened to be there."""
    operands = _operands(BY_HAND, 3, 32, 16, activation, seed=1)
    layout = experts.dispatch(jnp.asarray(BY_HAND), first_expert=2, held=3, block=4)
    np.testing.assert_array_equal(layout[3], [0, 1, 30])
    assert int(layout[2]) == 1 + 1 + 8
    args = (BY_HAND, 2, 3, 4, activation, operands)
    got = _value_and_gradients(experts.expert_tiles, *args, True)
    _assert_the_spellings_agree(got, _value_and_gradients(experts.expert_blocks, *args))
    assert not np.asarray(got[3][0]).any() and not np.asarray(got[4][0]).any()
    assert np.asarray(got[3][1]).any() and np.asarray(got[0][0]).any()
    assert not np.asarray(got[0][31:]).any() and not np.asarray(got[2][31:]).any()  # landed elsewhere


@pytest.fixture
def kernels_in_the_interpreter(monkeypatch):
    """``held_experts`` takes the kernels here as it does on the TPU, interpreted."""
    on_the_tpu = experts.expert_tiles
    monkeypatch.setattr(experts, "kernels_run", lambda *_: True)
    monkeypatch.setattr(experts, "expert_tiles", lambda *args: on_the_tpu(*args, True))


def _layer(activation, block=8):
    """``(layer(x, router, w_in, w_out) -> [40, 32], its arguments)``: three picks of eight
    experts, four held from expert 2 on."""
    k = jax.random.split(jax.random.key(11), 4)
    f_in = 12 if activation is experts.RELU2 else 24
    args = (jax.random.normal(k[0], (40, 32)), jax.random.normal(k[1], (32, 8)),
            0.2 * jax.random.normal(k[2], (4, 32, f_in)), 0.2 * jax.random.normal(k[3], (4, 12, 32)))

    def layer(x, router, w_in, w_out):
        picks, weights = experts.sigmoid_route(router, x, 3, 1.0)
        return experts.held_experts(x, picks, weights, w_in, w_out, first_expert=2, block=block,
                                    activation=activation)[0]

    return layer, args


@pytest.mark.parametrize("wrap", ["bare", "vmap-of-one", "checkpoint", "vmap-of-one-over-checkpoint"])
def test_the_kernels_under_the_rounds_vmap_and_the_layers_checkpoint(wrap, monkeypatch, request):
    """The value and the gradients of a routed layer (the router's through the gates) with
    the kernels, under the round's 1-wide client ``vmap`` and under the layers'
    ``jax.checkpoint(policy=KEEP_NAMED_OUTPUTS)``, are the loop's."""
    layer, args = _layer(experts.SWIGLU)

    def step(*a):
        fn = jax.checkpoint(layer, policy=experts.KEEP_NAMED_OUTPUTS) if "checkpoint" in wrap else layer
        loss = lambda *b: jnp.sum(fn(*b) ** 2)
        if "vmap" in wrap:
            return jax.tree.map(lambda leaf: leaf[0], jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
                *(b[None] for b in a)))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*a)

    want = jax.jit(step)(*args)
    request.getfixturevalue("kernels_in_the_interpreter")
    got = jax.jit(step)(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(jnp.abs(w).max()))


def test_a_checkpoint_over_the_kernels_keeps_the_dispatchs_three_integers_alone(
        capsys, kernels_in_the_interpreter):
    layer, args = _layer(experts.REGLU)
    rows = 40 * 3 + 4 * 8
    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(layer, policy=experts.KEEP_NAMED_OUTPUTS), *args)
    lines = capsys.readouterr().out.strip().splitlines()
    assert all("from the argument" in line for line in lines[:len(args)])
    assert [line.split()[0] for line in lines[len(args):]] == [f"i32[{rows}]", f"i32[{rows // 8}]", "i32[]"]


def test_which_spelling_runs_follows_from_shapes_and_platform(monkeypatch):
    """Off the TPU the loop, whatever the shapes; on it the kernels where tokens and experts
    have one dtype, rows are whole sublane tiles and an expert fits VMEM with its
    accumulators."""
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cells = {"hybrid": (2688, 1856, 1856), "smallthinker": (2560, 1536, 768),
             "moonlight": (2048, 2816, 1408), "keye": (2048, 1536, 768), "trinity": (2048, 2048, 1024)}
    runs = lambda d, f_in, f, held=8, block=256, x=bf16: experts.kernels_run(
        x(8192, d), bf16(held, d, f_in), bf16(held, f, d), block)
    assert not any(runs(*shape) for shape in cells.values())  # here: the CPU
    monkeypatch.setattr(experts, "auto_interpret", lambda interpret: False)  # as on the TPU
    assert all(runs(*shape) for shape in cells.values())
    assert not runs(2048, 2040, 1020)                      # 1020 rows of w_out: no whole sublane tiles
    assert not runs(2048, 1536, 768, block=8)              # blocks that are no sublane tiles
    assert not runs(8192, 16384, 8192)                     # an expert that does not fit VMEM
    assert not runs(2048, 1536, 768, x=lambda *s: jax.ShapeDtypeStruct(s, jnp.float32))  # two dtypes


# ---------------------------------------------------------------------------
# Before the first round: what a layer more costs the lowering.
# ---------------------------------------------------------------------------

#: Decoders whose layers are a Python loop over ``leaf[i]``, by their number of expert
#: layers: SmallThinker's (the loop's rerun is dead code) and Trinity's (sandwich norms
#: keep it alive, so the forward kernel runs in a second pass).
DECODERS = {
    "moe_decoder_lm": lambda layers: {
        "vocab": 64, "seq_len": 32, "width": 64, "rope_layout": [0] * layers,
        "window_layout": [0] * layers, "attn_heads": 4, "kv_heads": 2, "head_dim": 16,
        "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 48},
    "gated_moe_lm": lambda layers: {
        "vocab": 64, "seq_len": 32, "width": 64, "sliding_layout": [1] + [0] * layers, "window": 8,
        "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "dense_layers": 1, "dense_width": 160,
        "experts": 16, "experts_held": 4, "top_k": 3, "expert_width": 32, "shared_width": 32},
}


def _kernel_modules_of_a_round_step(factory, layers):
    """How often each expert kernel's serialized module stands in the TPU-platform lowering
    of the round step (clients in chunks of one, as the cells run) of a decoder with
    ``layers`` expert layers, and how often the lowered program calls them."""
    model = get_model(factory, **DECODERS[factory](layers))
    params = jax.eval_shape(model.init, jax.random.key(0))
    strategy = fedavg_strategy()
    training = TrainingConfig(batch_size=1, local_epochs=1, learning_rate=0.05,
                              compute_dtype="bfloat16")
    step = build_round_step(model.apply, training, make_mesh(devices=jax.devices()[:1]), strategy,
                            client_chunk=1, params_like=params)
    shape = jax.ShapeDtypeStruct
    data = ClientData(x=shape((2, 2, *model.input_shape), jnp.int32), y=shape((2, 2), jnp.int32),
                      mask=shape((2, 2), jnp.float32))
    args = (params, jax.eval_shape(lambda p: init_server_state(strategy, p), params), data,
            shape((2,), jnp.float32), jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2)))
    text = step.jit_program.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    modules = {kernel: len(re.findall(rf'kernel_name = "{kernel}"', text))
               for kernel in ("expert_tiles_fwd", "expert_tiles_bwd")}
    calls = len(re.findall(r"call @expert_tiles", text))
    return modules, calls


@pytest.mark.parametrize("factory", list(DECODERS))
def test_a_layer_more_adds_no_kernel_module_to_the_lowered_round_step(factory, monkeypatch):
    """The design that keeps ``setup_s``: the kernels' entry points are module-level
    ``jax.jit`` functions, so a Python loop over the layers traces each kernel once and the
    lowered program holds its serialized module once a pass (forward, rerun where it is
    live, backward) however many layers call it.  A change that gives each layer a module
    of its own (4-6 layers x 3 sites in the cells) fails here, not in the driver's
    ``setup_s``."""
    monkeypatch.setattr(experts, "auto_interpret", lambda interpret: False)  # as on the TPU
    one, calls_of_one = _kernel_modules_of_a_round_step(factory, 1)
    three, calls_of_three = _kernel_modules_of_a_round_step(factory, 3)
    assert one == three
    assert one["expert_tiles_bwd"] == 1
    assert one["expert_tiles_fwd"] == (2 if factory == "gated_moe_lm" else 1)
    assert calls_of_three == 3 * calls_of_one and calls_of_one == sum(one.values())
