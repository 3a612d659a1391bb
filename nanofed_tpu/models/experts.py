"""The held experts of a mixture-of-experts layer: dispatch and the grouped matmul, shared
by every model of the zoo that routes (``models.hybrid``, ``models.moe_decoder``,
``models.latent_moe``, ``models.indexed_moe``, ``models.gated_moe``), the two routers
they pick with (:func:`route` over logits, :func:`sigmoid_route`) and the one check of
which experts a program holds (:func:`check_held`).

A layer is TOLD which experts it holds (``first_expert``, ``held``) of the ``experts``
its router scores.  The model routes — its own scores, its own normalisation — and hands
the picks and their weights to :func:`held_experts`: picks that land on held experts are
laid out by expert in whole blocks of ``block`` rows (``moe_dispatch``, :func:`dispatch`:
a one-hot of the picks, a prefix count along them, one scatter of ``src``) and the held
experts' MLPs run over the blocks in use (``moe_experts``): the work follows the routing,
so no capacity limit and no dropped token, and no work on blocks nobody fills.  Two
spellings of it, and :func:`kernels_run` says which runs from shapes and platform alone:
on the TPU :func:`expert_tiles`, two Pallas kernels (``ops.experts``) that keep an
expert's matrices, and in the backward pass its float32 weight gradients, in VMEM while
its blocks run; everywhere else :func:`expert_blocks`, a loop over the blocks with a
hand-written backward, which is also what the kernels are tested against.  A block's rows
follow from the experts' shape (``ops.experts.tile_rows``).  What experts held elsewhere
would add is left out: on one chip the layer runs without its exchange, and a sum over
all the shares is the uncut layer (tests).

The dispatch is integer layout work with no gradient, and the written backward of either
spelling reads its three outputs (``src``, ``block_expert``, the count of blocks in use).
Under a layer's ``jax.checkpoint`` they would be rebuilt in the backward pass: the same
prefix count and scatter of the same picks.  :func:`held_experts` names them (:data:`KEPT`,
``jax.ad_checkpoint.checkpoint_name``), and a checkpoint given
:data:`KEEP_NAMED_OUTPUTS` as its policy keeps them (``int32[rows]``, ``int32[rows //
block]`` and a scalar a layer) and runs the dispatch once a step; the models'
rematerialized layers do.  Outside such a checkpoint a name is the identity.

An expert is ``W_out act(W_in x)``; ``act`` is an :class:`Activation`, a static parameter
of both spellings and of their written backwards: :data:`RELU2` on ``[rows, f]``, :data:`REGLU`
and :data:`SWIGLU` on a fused ``[rows, 2f]`` product (``W_gate | W_up`` stored as one
``[d, 2f]`` leaf).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from nanofed_tpu.ops import attention
from nanofed_tpu.ops import experts as kernels
from nanofed_tpu.ops._common import auto_interpret

_F32 = jnp.float32

#: What :func:`held_experts` counts of one layer's routing, in this order: the share of
#: all picks that landed on held experts; the held experts' largest token count over
#: their mean (1.0 is even); rows taken over rows of the blocks the loop ran.
COUNTERS = ("moe_held_pick_share", "moe_load_max_over_mean", "moe_block_fill")
#: What :func:`expert_blocks`' backward reads of the dispatch, ``src``, ``block_expert``
#: and the trip count, by the names :func:`held_experts` gives them ...
KEPT = ("moe_dispatch_src", "moe_dispatch_block_expert", "moe_dispatch_n_blocks")
#: The name ``models.indexed_moe`` gives a layer's pick of keys, the mask its attention
#: runs under: integer work with no gradient, like the dispatch (spelled here because that
#: module imports this one).
INDEXER_KEPT = ("indexer_keep",)
#: ... and the ONE ``jax.checkpoint`` policy of the zoo's rematerialized layers: it keeps
#: exactly what carries a name, the attention kernels' output and log-sum-exp where they
#: run, the dispatch's three where an expert layer runs and the pick where an indexer
#: runs, and everything else in a layer is recomputed as under a plain checkpoint.  A
#: layer with none of them keeps nothing.
KEEP_NAMED_OUTPUTS = jax.checkpoint_policies.save_only_these_names(
    *attention.KEPT, *KEPT, *INDEXER_KEPT)


class Activation(NamedTuple):
    """An expert's activation between its two products.  ``apply(pre) -> hidden``;
    ``with_grad(pre) -> (hidden, d_hidden -> d_pre)`` for the loop's backward."""

    apply: Callable
    with_grad: Callable


def _relu2_with_grad(pre):
    kept = jax.nn.relu(pre)
    return jnp.square(kept), lambda d_hidden: d_hidden * 2 * kept


def _reglu(pre):
    f = pre.shape[-1] // 2
    return jax.nn.relu(pre[..., :f]) * pre[..., f:]


def _reglu_with_grad(pre):
    f = pre.shape[-1] // 2
    gate, up = pre[..., :f], pre[..., f:]
    kept = jax.nn.relu(gate)
    return kept * up, lambda d_hidden: jnp.concatenate(
        [jnp.where(gate > 0, d_hidden * up, 0), d_hidden * kept], axis=-1)


def _swiglu(pre):
    f = pre.shape[-1] // 2
    gate = pre[..., :f]
    return gate * jax.nn.sigmoid(gate) * pre[..., f:]


def _swiglu_with_grad(pre):
    f = pre.shape[-1] // 2
    gate, up = pre[..., :f], pre[..., f:]
    sig = jax.nn.sigmoid(gate)
    kept = gate * sig  # silu(gate); its derivative is sig + kept (1 - sig)
    return kept * up, lambda d_hidden: jnp.concatenate(
        [d_hidden * up * (sig + kept * (1 - sig)), d_hidden * kept], axis=-1)


#: ``relu(pre)^2``.
RELU2 = Activation(lambda pre: jnp.square(jax.nn.relu(pre)), _relu2_with_grad)
#: ``relu(gate) * up`` of ``pre = [gate | up]``: the product is twice the hidden width.
REGLU = Activation(_reglu, _reglu_with_grad)
#: ``silu(gate) * up`` of ``pre = [gate | up]``.
SWIGLU = Activation(_swiglu, _swiglu_with_grad)


def check_held(experts: int, first_expert: int, experts_held: int, top_k: int) -> None:
    """Refuse a model whose held experts ``first_expert .. first_expert + experts_held``
    do not lie among the ``experts`` its router scores, or that picks more than those."""
    if not (0 <= first_expert and first_expert + experts_held <= experts and top_k <= experts):
        raise ValueError("the held experts must lie among the routed ones, top_k within them")


def route(router: jax.Array, u: jax.Array, top_k: int):
    """``(picks [n, top_k] int32, weights [n, top_k] float32)`` over ALL the experts the
    router scores: logits in float32, the ``top_k`` largest, softmax over those."""
    logits = jnp.matmul(u.astype(_F32), router.astype(_F32), precision=lax.Precision.HIGHEST)
    top, picks = lax.top_k(logits, top_k)
    return picks, jax.nn.softmax(top, axis=-1)


def sigmoid_route(router, x, top_k: int, scale: float, bias=None):
    """``(picks [n, top_k] int32, weights [n, top_k] float32)`` over ALL the experts the
    router scores: sigmoid scores in float32, the ``top_k`` largest, normalised over the
    picks and scaled.  A selection ``bias`` [experts] is added to the scores for the
    PICKING alone: the weights are the picked experts' scores without it, and it takes
    no gradient (its balancing update is a rule of its own, outside the loss)."""
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(_F32), router.astype(_F32),
                                       precision=lax.Precision.HIGHEST))
    if bias is None:
        top, picks = lax.top_k(scores, top_k)
    else:
        _, picks = lax.top_k(scores + lax.stop_gradient(bias.astype(_F32)), top_k)
        top = jnp.take_along_axis(scores, picks, axis=-1)
    return picks, scale * top / (top.sum(axis=-1, keepdims=True) + 1e-20)


def _varying_like(array, *like):
    """``array`` varying over every mesh axis one of ``like`` varies over: inside
    ``shard_map`` a loop's carry has to start with the type its update will have."""
    axes = set().union(*(jax.typeof(a).vma for a in like))
    return lax.pcast(array, tuple(axes), to="varying") if axes else array


def _zeros_varying_like(shape, *like):
    """Float32 zeros so typed."""
    return _varying_like(jnp.zeros(shape, _F32), *like)


def _block_operands(b, block, x, gate, src, block_expert, w_in, w_out):
    """Block ``b``: its rows' picks and tokens, the token rows, their gates, its expert's
    two matrices.  An empty row's pick is ``n * top_k`` and its token ``n``, one past the
    end: such a row reads zeros (``mode="fill"``) and what it writes is dropped."""
    picks = lax.dynamic_slice_in_dim(src, b * block, block)
    tokens = picks // (gate.shape[0] // x.shape[0])
    expert = block_expert[b]
    return (picks, tokens, x.at[tokens].get(mode="fill", fill_value=0),
            gate.at[picks].get(mode="fill", fill_value=0), expert,
            lax.dynamic_index_in_dim(w_in, expert, keepdims=False),
            lax.dynamic_index_in_dim(w_out, expert, keepdims=False))


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def expert_blocks(x, gate, src, block_expert, n_blocks, w_in, w_out, activation, block):
    """``out[t] = sum over t's held picks of gate[pick] * W_out,e act(W_in,e x[t])``.

    ``x`` [n, d] tokens, ``gate`` [n * top_k] float32 one weight a pick (pick ``i`` is
    token ``i // top_k``'s).  The picks that landed on held experts are laid out by
    expert in whole blocks of ``block`` rows: ``src`` [rows] names each row's pick
    (``n * top_k``: empty row), block ``b`` belongs to expert ``block_expert[b]``, and
    only the first ``n_blocks`` blocks are in use.  Only they are computed: forward and
    backward are loops whose trip count is ``n_blocks``, each block gathering its own
    token rows and adding its result back to them, so blocks nobody fills cost nothing —
    which is why the backward pass is written out (a loop of unknown length has no
    automatic transpose, and the transpose of a gather over all rows is a scatter over
    all rows)."""
    def body(b, out):
        _, tokens, rows, gates, _, w_i, w_o = _block_operands(
            b, block, x, gate, src, block_expert, w_in, w_out)
        y = activation.apply(rows @ w_i) @ w_o
        return out.at[tokens].add(y * gates[:, None].astype(y.dtype), mode="drop")

    zeros = _zeros_varying_like(x.shape, x, gate, src, w_in, w_out).astype(x.dtype)
    return lax.fori_loop(0, n_blocks, body, zeros)


def _expert_blocks_fwd(x, gate, src, block_expert, n_blocks, w_in, w_out, activation, block):
    saved = (x, gate, src, block_expert, n_blocks, w_in, w_out)
    return expert_blocks(*saved, activation, block), saved


def _expert_blocks_bwd(activation, block, saved, d_out):
    x, gate, src, block_expert, n_blocks, w_in, w_out = saved

    def body(b, carry):
        dx, d_gate, d_in, d_o = carry
        picks, tokens, rows, gates, expert, w_i, w_o = _block_operands(
            b, block, x, gate, src, block_expert, w_in, w_out)
        hidden, pull = activation.with_grad(rows @ w_i)
        dy = d_out.at[tokens].get(mode="fill", fill_value=0)
        d_gate = d_gate.at[picks].set(
            jnp.sum((hidden @ w_o).astype(_F32) * dy.astype(_F32), axis=-1), mode="drop")
        dy = dy * gates[:, None].astype(dy.dtype)
        d_pre = pull(dy @ w_o.T)
        dx = dx.at[tokens].add(d_pre @ w_i.T, mode="drop")
        add = lambda acc, term: lax.dynamic_update_index_in_dim(
            acc, lax.dynamic_index_in_dim(acc, expert, keepdims=False) + term, expert, 0)
        d_in = add(d_in, jnp.matmul(rows.T, d_pre, preferred_element_type=_F32))
        d_o = add(d_o, jnp.matmul(hidden.T, dy, preferred_element_type=_F32))
        return dx, d_gate, d_in, d_o

    zeros = lambda like: _zeros_varying_like(like.shape, x, gate, src, d_out, w_in, w_out)
    start = (zeros(x).astype(x.dtype), zeros(gate), zeros(w_in), zeros(w_out))
    dx, d_gate, d_in, d_o = lax.fori_loop(0, n_blocks, body, start)
    return (dx, d_gate.astype(gate.dtype), None, None, None,
            d_in.astype(w_in.dtype), d_o.astype(w_out.dtype))


expert_blocks.defvjp(_expert_blocks_fwd, _expert_blocks_bwd)


#: Rows a step of the gathers and scatters around the kernels moves.  The layout has room
#: for every token's picks (its static ``rows``) and the routing fills a fraction of it (a
#: held expert sees 1/4 to 1/16 of its deployment load in the cells), so the rows are
#: moved in a loop over the chunks IN USE, as the kernels run the blocks in use: 1024 rows
#: is the largest block the loop's own gather and scatter-add were measured at on a v5e
#: before they fall off XLA's fast path (from 1280 rows: 2.7 times the round, PERF.md §6,
#: PR 31).
CHUNK = 1024


def _chunks_in_use(src, used, body, start):
    """``body(at, fresh, carry)`` over the chunks of the layout's first ``used`` rows:
    ``at`` is where a chunk's rows begin, moved back where the last chunk would pass the
    layout's end, and ``fresh`` [chunk] says which of its rows no earlier chunk held."""
    rows = src.shape[0]
    chunk = min(CHUNK, rows)

    def step(c, carry):
        at = jnp.minimum(c * chunk, rows - chunk)
        return body(at, at + jnp.arange(chunk, dtype=jnp.int32) >= c * chunk, carry)

    return lax.fori_loop(0, -(-used // chunk), step, start)


def _laid_out(x, gate, src, used, *by_token):
    """The layout's rows in use: ``(x's rows [rows, d], their gates [rows, 1], ...)`` and
    the rows of each further ``by_token`` array, gathered a chunk at a time.  An empty
    row's pick is ``n * top_k`` and its token ``n``, one past the end: it reads zeros, a
    gate of zero among them.  Rows past ``used`` are left as they were allocated."""
    top_k = gate.shape[0] // x.shape[0]

    def body(at, fresh, made):
        picks = lax.dynamic_slice_in_dim(src, at, fresh.shape[0])
        tokens = picks // top_k
        by = lambda a: a.at[tokens].get(mode="fill", fill_value=0)
        got = (by(x), gate.at[picks].get(mode="fill", fill_value=0)[:, None], *map(by, by_token))
        return tuple(lax.dynamic_update_slice_in_dim(m, g, at, 0) for m, g in zip(made, got))

    empty = lambda width, dtype: _varying_like(
        lax.empty((src.shape[0], width), dtype), x, gate, src, *by_token)
    start = (empty(x.shape[1], x.dtype), empty(1, gate.dtype),
             *(empty(a.shape[1], a.dtype) for a in by_token))
    return _chunks_in_use(src, used, body, start)


def _to_tokens(like, src, used, rows, top_k):
    """``rows`` [layout's rows, d] of the layout added to their tokens, ``[n, d]`` zeros
    ``like``-typed elsewhere; an empty row's is dropped."""
    def body(at, fresh, out):
        tokens = jnp.where(fresh, lax.dynamic_slice_in_dim(src, at, fresh.shape[0]) // top_k,
                           out.shape[0])
        return out.at[tokens].add(lax.dynamic_slice_in_dim(rows, at, fresh.shape[0]), mode="drop")

    return _chunks_in_use(src, used, body, like)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def expert_tiles(x, gate, src, block_expert, n_blocks, w_in, w_out, activation, block,
                 interpret=False):
    """:func:`expert_blocks`, its arguments and its result, as a grouped matmul over the
    layout's blocks (``ops.experts``): the rows in use are gathered by token (a loop over
    chunks of :data:`CHUNK` rows, no product in it), ONE kernel walks the blocks in use
    with an expert's two matrices resident in VMEM while its blocks run, and the gated
    results are added back to their tokens (a second such loop).  The backward pass
    gathers the rows and the cotangent's rows again and runs the second kernel, which
    keeps an expert's two float32 weight gradients in VMEM over its blocks and writes them
    once.  Every held expert needs a block (:func:`dispatch` gives an idle one an empty
    block)."""
    return _expert_tiles_fwd(x, gate, src, block_expert, n_blocks, w_in, w_out, activation,
                             block, interpret)[0]


def _expert_tiles_fwd(x, gate, src, block_expert, n_blocks, w_in, w_out, activation, block,
                      interpret):
    used = n_blocks * block
    rows, gates = _laid_out(x, gate, src, used)
    y = kernels.expert_tiles(rows, gates, block_expert, n_blocks.reshape(1), w_in, w_out,
                             activation=activation, tile=block, interpret=interpret)
    zeros = _zeros_varying_like(x.shape, x, gate, src, w_in, w_out).astype(x.dtype)
    return (_to_tokens(zeros, src, used, y, gate.shape[0] // x.shape[0]),
            (x, gate, src, block_expert, n_blocks, w_in, w_out))


def _expert_tiles_bwd(activation, block, interpret, saved, d_out):
    x, gate, src, block_expert, n_blocks, w_in, w_out = saved
    used = n_blocks * block
    # The written backward is traced outside the forward's ``with``: it names its scope.
    with jax.named_scope("moe_experts"):
        rows, gates, dy = _laid_out(x, gate, src, used, d_out)
        d_rows, d_gates, d_in, d_o = kernels.expert_tiles_grads(
            rows, gates, dy, block_expert, n_blocks.reshape(1), w_in, w_out,
            activation=activation, tile=block, interpret=interpret)
        zeros = lambda like: _zeros_varying_like(like.shape, x, gate, src, d_out, w_in, w_out)
        dx = _to_tokens(zeros(x).astype(x.dtype), src, used, d_rows, gate.shape[0] // x.shape[0])

        def a_chunk_of_d_gate(at, fresh, d_gate):
            picks = jnp.where(fresh, lax.dynamic_slice_in_dim(src, at, fresh.shape[0]), gate.shape[0])
            return d_gate.at[picks].set(
                lax.dynamic_slice_in_dim(d_gates[:, 0], at, fresh.shape[0]), mode="drop")

        d_gate = _chunks_in_use(src, used, a_chunk_of_d_gate, zeros(gate))
    return dx, d_gate.astype(gate.dtype), None, None, None, d_in, d_o


expert_tiles.defvjp(_expert_tiles_fwd, _expert_tiles_bwd)


def kernels_run(x, w_in, w_out, block: int) -> bool:
    """Which of the two spellings :func:`held_experts` runs, from shapes and platform
    alone: the kernels (:func:`expert_tiles`) on the TPU where tokens and experts have one
    dtype, blocks and matrices are whole sublane tiles and an expert's matrices fit VMEM
    with their accumulators (``ops.experts.engages``); the loop (:func:`expert_blocks`)
    everywhere else, off the TPU above all, where it is also what the kernels are tested
    against."""
    (d, f_in), f = w_in.shape[1:], w_out.shape[1]
    return (not auto_interpret(None) and x.dtype == w_in.dtype == w_out.dtype
            and kernels.engages(block, d, f_in, f, x.dtype))


def dispatch(picks, *, first_expert: int, held: int, block: int):
    """Where each pick that lands on experts ``first_expert .. first_expert + held`` goes
    in a layout by expert in whole blocks of ``block`` rows: ``(src [rows] int32,
    block_expert [rows // block] int32, n_blocks, counts [held], ends [held])``.
    ``counts[e]`` picks landed on expert ``e``; its rows, the count rounded up to whole
    blocks, end at ``ends[e]``, and the ``k``-th of them names its ``k``-th pick in pick
    order (pick ``i`` of ``picks`` [n, top_k] flattened); every other row holds ``n *
    top_k``, and only the first ``n_blocks`` blocks hold a pick.  ``rows`` is static, room
    for every token's ``min(top_k, held)`` picks (a token picks an expert once) and a block
    to spare an expert.

    A pick's place inside its expert is how many earlier picks chose that expert: a prefix
    count along the picks, dense and exact, where a stable sort of the keys would give the
    same order; then ONE scatter writes every pick's index to its row."""
    n, top_k = picks.shape
    local = (picks - first_expert).reshape(n * top_k)
    # [held, n * top_k], the picks along the lanes; one that lands elsewhere is in no row.
    lands = local[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None]
    ones = lands.astype(jnp.int32)
    counts = ones.sum(axis=1)
    padded = jnp.maximum(-(-counts // block), 1) * block  # an idle expert: one empty block
    ends = jnp.cumsum(padded)
    rows = n * min(top_k, held) + held * block
    rows = -(-rows // block) * block
    block_expert = jnp.clip(jnp.searchsorted(
        ends, jnp.arange(rows // block, dtype=jnp.int32) * block, side="right"),
        0, held - 1).astype(jnp.int32)
    before = jnp.cumsum(ones, axis=1) - ones
    # ``rows``, one past the end, where a pick lands elsewhere: the scatter drops it.
    dest = jnp.where(lands, (ends - padded)[:, None] + before, rows).min(axis=0)
    src = jnp.full(rows, n * top_k, jnp.int32).at[dest].set(
        jnp.arange(n * top_k, dtype=jnp.int32), mode="drop")
    return src, block_expert, ends[-1] // block, counts, ends


def held_experts(x, picks, weights, w_in, w_out, *, first_expert: int,
                 activation: Activation, block: int | None = None):
    """The held experts' part of the routed output for tokens ``x`` [n, d], and the
    layer's :data:`COUNTERS` (float32 ``[3]``).  ``picks`` [n, top_k] int32 name experts
    among ALL the router scores, ``weights`` [n, top_k] float32 what each pick's output
    is scaled by; ``w_in`` / ``w_out`` hold experts ``first_expert .. first_expert +
    w_in.shape[0]``.  ``block``, the rows an expert's picks are padded to a multiple of and
    the kernels' row tile, follows from the experts' shape (``ops.experts.tile_rows``)
    unless a test gives one."""
    n, top_k = picks.shape
    held = w_in.shape[0]
    if block is None:
        block = kernels.tile_rows(*w_in.shape[1:])
    with jax.named_scope("moe_dispatch"):
        src, block_expert, n_blocks, counts, ends = dispatch(
            picks, first_expert=first_expert, held=held, block=block)
        src, block_expert, n_blocks = map(
            checkpoint_name, (src, block_expert, n_blocks), KEPT)
    with jax.named_scope("moe_experts"):
        experts_of = expert_tiles if kernels_run(x, w_in, w_out, block) else expert_blocks
        out = experts_of(x, weights.reshape(n * top_k), src, block_expert, n_blocks,
                         w_in, w_out, activation, block)
    landed = counts.sum().astype(_F32)
    even = jnp.where(landed > 0, counts.max() * held / jnp.maximum(landed, 1.0), 1.0)
    fill = jnp.where(landed > 0, landed / jnp.maximum(ends[-1], 1).astype(_F32), 1.0)
    return out, jnp.stack([landed / (n * top_k), even, fill])
