"""``models.experts.dispatch``: the layout of the picks that land on held experts, held to
its specification — the picks in a stable order by expert, every expert's padded to whole
blocks — written out here with ``numpy``'s stable sort, which the program does without."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.models import experts


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
        blocks = -(-count // block)
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
        assert want_blocks == 0 and (want_src == picks.size).all()
    if edge == "counts-that-fill-their-blocks":
        assert want_blocks == 4 and (want_src[:16] < picks.size).all()


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
