"""Causal self-attention, block by block, as Pallas TPU kernels.

``causal_attention(q, k, v)`` over ``[N, H, T, hd]`` is ``softmax(q k^T / sqrt(hd)) v``
with position ``t`` attending to keys ``<= t``: what the dense ``einsum`` / ``where`` /
``softmax`` spelling computes, without any ``[N, H, T, T]`` array in HBM on either pass.
``v`` may have a head size of its own (``[N, H_kv, T, hd_v]``, latent attention's 192-wide
scores over 128-wide values): the output is ``hd_v`` wide and the scale stays ``q``'s.

Both kernels keep a score block *transposed*, keys down the sublanes and queries along
the lanes: the softmax's maximum and sum then run down the sublanes, vreg against vreg on
the VPU, the per-query statistics are lane-major rows that broadcast over a block as
stored, and no product needs a ``[block, block]`` transpose.  The small operands that
want the sequence along the lanes (``V`` forward, a ``K`` block backward) are turned once
a head or a grid step into VMEM scratch; the output and ``dQ`` leave the kernels as
``[hd, T]`` and are turned back by XLA, beside the head-merge transpose the model does
anyway.

Forward: one grid step a (head, query block).  The head's whole ``K`` and ``V`` stay in
VMEM; the kernel walks the key blocks left of the diagonal with an online softmax
(running maximum, running sum and output accumulator in float32) and finishes on the
diagonal block, the only one that needs the mask.  Blocks above the diagonal are never
visited.  It writes the output and one float32 log-sum-exp a query.

Backward (``jax.custom_vjp``): the saved values are ``q``, ``k``, ``v``, the output and
the log-sum-exp.  One grid step a (head, key block) recomputes each score block on or
under the diagonal from them: ``dV`` and ``dK`` accumulate in the step's registers,
``dQ`` in a float32 VMEM scratch that lives across the head's key blocks.  Five
products a block pair, against the forward's two.  Under a ``jax.checkpoint`` the five
are rebuilt in the backward pass, the last two by launching the forward kernel again;
the forward rule names those two (``jax.ad_checkpoint.checkpoint_name``), and a
checkpoint given :data:`KEEP_KERNEL_OUTPUTS` as its policy keeps them and rebuilds
``q``, ``k``, ``v`` alone: one forward launch a layer (``models.experts.KEEP_NAMED_OUTPUTS``
keeps :data:`KEPT` so).  Outside such a checkpoint a name is the identity.

Grouped queries (``k``, ``v`` of ``[N, H_kv, T, hd]``, ``H`` a multiple of ``H_kv``): query
head ``h`` reads key/value head ``h // (H / H_kv)`` through the block index alone, so no
copy of ``K`` or ``V`` is written and consecutive heads of a group find theirs in VMEM;
the backward writes each query head's float32 ``dK``, ``dV`` and XLA sums a group's.
A window (``window=W``: key ``s`` is seen from ``t`` only while ``t - W < s``): key blocks
wholly behind the window are never visited, the one or two blocks its trailing edge cuts
are masked, forward and backward (:func:`_window_steps`).  A value head of another size
(``hd_v != hd``): the forward's ``V`` scratch, accumulator and output are ``hd_v`` wide,
``q`` and ``K`` ``hd``; of the backward's five products three run over ``hd`` (scores,
``dK``, ``dQ``) and two over ``hd_v`` (``dP``, ``dV``); no column is padded.  A mask the
program computed (``keep=``: one ``int8`` a (key, query) pair of a sequence, shared by
all its heads, laid out as the kernels' score blocks are, keys down and queries along:
``keep[n, s, t]``): pair ``(t, s)`` is seen iff ``s <= t`` and ``keep[n, s, t] != 0``.
Every block pair on or under the diagonal is visited and each score block takes its
``[block, block]`` tile of the mask.  The forward's grid then puts a group's query
heads innermost, (key/value head, query block, head of the group), so the block's strip
of the mask (``[T, block]``, 4 MiB at 8192) is fetched once a key/value head and all the
group's heads use it; the backward keeps its (head, key block) order, whose resident
operands are a head's own (``q``, ``dO``, the float32 ``dQ``), and fetches the key
block's strip (``[block, T]``) a step, under the step's products.  A row that keeps no
key at all is the caller's error (its output is no softmax of anything).  A
block-diffusion mask (``blocks=(half, size)``): the sequence is a stream of one or two
halves of ``half`` positions, a clean text and then its noised copy, each cut into blocks
of ``size``; a clean query sees the clean keys of its own block and of every block before
it, a noised query the clean keys of the blocks before its own and the noised keys of
its own block alone, and no clean query sees a noised key.  That is not sub-causal
inside a block and it is computed in :func:`_scores` from the tile's own iotas, no mask
array anywhere.  With the clean half first every seen pair still lies on or under the
diagonal of the tile grid, and the kernels walk only the tiles that hold one
(:func:`_block_walk`): of a noised query tile's row the clean tiles up to its own text and
its own noised tile, never another noised tile, and of a clean query tile's row no
noised tile at all (80 of the 136 causal tile pairs at 8192 positions in two halves and
tiles of 512, 24 of them masked).  All five are static Python branches: with full heads,
no window, one head size and no mask the kernels trace to the program they were.

Precision: scores, softmax statistics and every accumulator are float32; the
probabilities (and ``dS``) are cast to the inputs' dtype for the products that consume
them, as a dense bfloat16 model's ``att`` is.  ``1/sqrt(hd)`` is folded into ``q`` where
that is exact (a power of two: head sizes 16, 64, 256), else applied to the float32
scores.

MEASURED (v5e, ``[4, 12, 1024, 64]`` bfloat16, a layer of a scanned stack, PERF.md §6,
PR 28): forward 0.21 ms, forward + backward 0.59 ms, against the dense spelling's 0.24
and 1.19 with its two ``[N, H, T, T]`` residuals a layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanofed_tpu.ops._common import auto_interpret

#: Rows of a query block and of a key block: the largest of these that divides ``T``.
#: Measured on a v5e at ``[4, 12, 1024, 64]`` bfloat16 (PERF.md §6, PR 28), forward +
#: backward a layer: 1.51 ms at 128, 0.71 at 256, 0.59 at 512 (dense: 1.19).  Small
#: blocks skip more of what lies above the diagonal (62.5% of the pairs are visited at
#: four blocks a sequence, 75% at two) and lose more to each pair's fixed cost.
BLOCKS = (512, 256)
#: Below this a ``[T, T]`` score tile is VMEM-sized traffic for XLA too.
MIN_SEQ = 512
#: A head's whole ``K`` and ``V`` sit in VMEM: compiles for a v5e through 8192 positions
#: of 128 (bfloat16), not at 16384; and through 8192 positions of 192-wide ``K`` over
#: 128-wide ``V`` under :data:`WIDE_VMEM` (the default 16 MiB and 32 MiB refuse both
#: kernels there: a 192-wide row is padded to 256 lanes).  Longer sequences want the keys
#: streamed.
MAX_SEQ = 8192
#: What the backward kernel reads of the forward kernel's own work, the output and the
#: log-sum-exp, by the names :func:`_attend_fwd` gives them (the identity wherever no
#: checkpoint asks for a name) ...
KEPT = ("causal_attention_out", "causal_attention_lse")
#: ... and the ``jax.checkpoint`` policy that keeps exactly those two: a rematerialized
#: layer that calls :func:`causal_attention` and passes this as ``policy`` recomputes
#: ``q``, ``k``, ``v`` and everything around them as a plain checkpoint does, and does
#: not launch the forward kernel a second time.  It costs one ``[N, H, T, hd_v]`` array in
#: the inputs' dtype (and ``N * H * T`` float32) a layer; where the dense spelling
#: answers nothing carries a name and nothing is kept.
KEEP_KERNEL_OUTPUTS = jax.checkpoint_policies.save_only_these_names(*KEPT)
#: Scoped VMEM the backward kernel may take where heads are grouped (a v5e has 128 MiB).
GROUPED_BWD_VMEM = 32 * 1024 * 1024
#: ... and both kernels where a score head is wider than 128.
WIDE_VMEM = 48 * 1024 * 1024

#: Masked scores: finite, so that ``exp(masked - max)`` is 0 and never ``inf - inf``.
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_F32 = jnp.float32
#: ``a @ b^T``: contract the last axes of both.
_NT = (((1,), (1,)), ((), ()))


def block_for(seq_len: int) -> int | None:
    """The block ``causal_attention`` cuts a sequence of this length into, ``None`` where
    it is not whole blocks."""
    return next((b for b in BLOCKS if seq_len % b == 0), None)


def engages(seq_len: int, half: int | None = None) -> bool:
    """Whether ``causal_attention`` takes a sequence of this length: whole blocks, and
    long enough that keeping the scores out of HBM pays, short enough for VMEM.  Under
    ``blocks=(half, size)`` each half has to be whole blocks too."""
    whole = block_for(seq_len if half is None else math.gcd(seq_len, half)) is not None
    return MIN_SEQ <= seq_len <= MAX_SEQ and whole


def _scale(hd: int) -> tuple[float, bool]:
    """``1/sqrt(hd)`` and whether multiplying a bfloat16 ``q`` by it is exact."""
    scale = 1.0 / math.sqrt(hd)
    return scale, math.frexp(scale)[0] == 0.5


def _struct(shape, dtype, *like):
    """``out_shape`` varying over every mesh axis one of ``like`` varies over: inside
    ``shard_map`` a ``pallas_call`` has to say so itself."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _window_steps(window: int, block: int) -> tuple[int, int]:
    """``(a, b)``: a query block ``i`` and a key block ``j <= i`` have every pair inside the
    window while ``i - j < a``, some pair while ``i - j < b``; from ``b`` on none."""
    return window // block, (window + block - 2) // block + 1


def _block_walk(tile, half_tiles: int, n_tiles: int):
    """``(noised, at)`` of a tile of a block-diffusion stream of ``n_tiles`` tiles whose
    halves are ``half_tiles`` each: whether it lies in the noised half (0 or 1; the
    integer 0 where the stream is one half) and the tile of the text it covers.  A QUERY
    tile visits its own tile (masked to equal blocks) if it is noised, the clean tiles
    ``0 .. at - 1`` whole, and the clean tile ``at`` masked (blocks up to its own; strictly
    before its own if it is noised).  A KEY tile is visited by its own query tile alone
    if it is noised; if it is clean, by the clean query tiles ``at`` (masked) and ``at + 1
    .. half_tiles - 1`` (whole) and the noised ones ``half_tiles + at`` (masked) and
    ``half_tiles + at + 1 ..`` (whole)."""
    if n_tiles == half_tiles:
        return 0, tile
    noised = tile // half_tiles
    return noised, tile - noised * half_tiles


def _once(flag, fn, carry):
    """``fn(carry)`` if the 0-or-1 ``flag`` (traced, or a plain integer) is 1, else
    ``carry``."""
    if isinstance(flag, int):
        return fn(carry) if flag else carry
    return lax.cond(flag == 1, fn, lambda c: c, carry)


def _scores(k, q, *, scale, fold, masked, behind=None, window=None, keep=None, within=None):
    """A score block transposed, ``[keys, queries]`` float32, from a key block and a query
    block.  ``masked`` is for the diagonal block, whose first rows share a position:
    inside it a key past its query gets ``_MASKED``.  ``behind`` (with ``window``) is how
    many positions the key block starts behind the query block: a key the window's
    length or more behind its query gets ``_MASKED`` too.  ``keep`` is the block's tile of a
    computed mask, ``[keys, queries]`` int8: a pair whose entry is 0 gets ``_MASKED``.
    ``within = (size, ahead)`` is for a tile of a block-diffusion stream whose keys and
    queries cover the same text, in blocks of ``size`` (a power of two that divides the
    tile, so a block's last index is ``index | (size - 1)``): a key is seen while its
    block ends ``ahead`` positions or more before the query's does (0: its own block and
    those before it; ``size``: those before it alone), or, ``ahead`` None, in the query's
    own block alone."""
    s = lax.dot_general(k, q, _NT, preferred_element_type=_F32)
    if not fold:
        s = s * scale
    if within is not None:
        size, ahead = within
        k_end = lax.broadcasted_iota(jnp.int32, s.shape, 0) | (size - 1)
        q_end = lax.broadcasted_iota(jnp.int32, s.shape, 1) | (size - 1)
        return jnp.where(k_end == q_end if ahead is None else k_end + ahead <= q_end, s, _MASKED)
    if keep is not None:
        kept = keep.astype(jnp.int32) != 0
        if masked:
            kept &= (lax.broadcasted_iota(jnp.int32, s.shape, 0)
                     <= lax.broadcasted_iota(jnp.int32, s.shape, 1))
        return jnp.where(kept, s, _MASKED)
    if masked or behind is not None:
        ki = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qi = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if masked:
            seen = ki <= qi
            if behind is not None:
                seen &= qi - ki < window
        else:
            seen = qi - ki < window - behind
        s = jnp.where(seen, s, _MASKED)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block, scale, fold, window=None, keep=False,
                blocks=None):
    keep_ref, (o_ref, lse_ref, vt_ref) = (rest[0], rest[1:]) if keep else (None, rest)
    i = pl.program_id(1)

    # Once a key/value head: V with the sequence along the lanes.  Under a computed mask
    # the grid's innermost axis walks the group's query heads, which share the scratch.
    @pl.when((i == 0) & (pl.program_id(2) == 0) if keep else i == 0)
    def _():
        vt_ref[...] = v_ref[...].T

    q = q_ref[...]
    if fold:
        q = q * scale
    hd_v = vt_ref.shape[0]

    def step(j, carry, masked, behind=None, within=None):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        # Key down the sublanes, query along the lanes: the softmax's reductions run
        # down the sublanes, vreg against vreg.
        s = _scores(k_ref[rows, :], q, scale=scale, fold=fold, masked=masked,
                    behind=behind, window=window, within=within,
                    keep=None if keep_ref is None else keep_ref[rows, :])
        m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=0, keepdims=True)
        vt = vt_ref[:, rows]
        acc = alpha * acc + jnp.dot(vt, p.astype(vt.dtype), preferred_element_type=_F32)
        return m_new, l, acc

    carry = (jnp.full((1, block), _MASKED, _F32), jnp.zeros((1, block), _F32),
             jnp.zeros((hd_v, block), _F32))
    if blocks is not None:
        # A noised query tile starts on its own tile, where every row sees its block;
        # then the clean tiles before its text, then the clean tile of its text.
        half_tiles, n_tiles, size = blocks
        noised, at = _block_walk(i, half_tiles, n_tiles)
        carry = _once(noised, lambda c: step(i, c, masked=True, within=(size, None)), carry)
        carry = lax.fori_loop(0, at, lambda j, c: step(j, c, masked=False), carry)
        m, l, acc = step(at, carry, masked=True, within=(size, noised * size))
    elif window is None:
        carry = lax.fori_loop(0, i, lambda j, c: step(j, c, masked=False), carry)
        m, l, acc = step(i, carry, masked=True)
    else:
        # Key blocks the window's trailing edge cuts, then those wholly inside it, then
        # the diagonal (which the window cuts too where it is shorter than a block).
        a, b = _window_steps(window, block)
        first = jnp.maximum(i - b + 1, 0)
        whole = jnp.clip(i - a + 1, first, i)
        carry = lax.fori_loop(
            first, whole, lambda j, c: step(j, c, masked=False, behind=(i - j) * block), carry)
        carry = lax.fori_loop(whole, i, lambda j, c: step(j, c, masked=False), carry)
        m, l, acc = step(i, carry, masked=True, behind=0 if a == 0 else None)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, block, scale, fold,
                window=None, keep=False, blocks=None):
    keep_ref, rest = (rest[0], rest[1:]) if keep else (None, rest)
    dq_ref, dk_ref, dv_ref, dq_acc, kt_ref = rest
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[...]
    v = v_ref[...]
    kt_ref[...] = k.T  # K's block with its rows along the lanes, for dQ^T = K^T dS^T

    def pair(i, carry, masked, behind=None, within=None):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        q = q_ref[rows, :]
        if fold:
            q = q * scale
        do = do_ref[rows, :]
        s = _scores(k, q, scale=scale, fold=fold, masked=masked, behind=behind, window=window,
                    within=within, keep=None if keep_ref is None else keep_ref[:, rows])
        p = jnp.exp(s - lse_ref[i])
        dp = lax.dot_general(v, do, _NT, preferred_element_type=_F32)
        ds = (p * (dp - delta_ref[i])).astype(q.dtype)
        dv = dv + jnp.dot(p.astype(do.dtype), do, preferred_element_type=_F32)
        dk = dk + jnp.dot(ds, q, preferred_element_type=_F32)
        dq_acc[:, rows] += jnp.dot(kt_ref[...], ds, preferred_element_type=_F32)
        return dk, dv

    zeros = jnp.zeros(k.shape, _F32)
    start = (zeros, zeros if v.shape == k.shape else jnp.zeros(v.shape, _F32))
    if blocks is not None:
        # The forward's walk seen from the key tile: a noised one meets its own query
        # tile alone; a clean one the clean query tiles from its own on and, in a stream
        # of two halves, the noised ones from its text's on.  The loops of the kind of
        # tile this one is not run from a start past their end.
        half_tiles, n_tiles, size = blocks
        noised, at = _block_walk(j, half_tiles, n_tiles)
        clean = 1 - noised
        whole = lambda i, c: pair(i, c, masked=False)
        carry = _once(noised, lambda c: pair(j, c, masked=True, within=(size, None)), start)
        carry = _once(clean, lambda c: pair(j, c, masked=True, within=(size, 0)), carry)
        carry = lax.fori_loop(j + 1, clean * half_tiles, whole, carry)
        if n_tiles > half_tiles:
            partner = half_tiles + at
            carry = _once(clean, lambda c: pair(partner, c, masked=True, within=(size, size)), carry)
            carry = lax.fori_loop(partner + 1, clean * n_tiles, whole, carry)
        dk, dv = carry
    elif window is None:
        carry = pair(j, start, masked=True)
        dk, dv = lax.fori_loop(j + 1, n_blocks, lambda i, c: pair(i, c, masked=False), carry)
    else:
        # The forward's three kinds of block pair, seen from the key block.
        a, b = _window_steps(window, block)
        carry = pair(j, start, masked=True, behind=0 if a == 0 else None)
        cut = jnp.clip(j + a, j + 1, n_blocks)
        carry = lax.fori_loop(j + 1, cut, lambda i, c: pair(i, c, masked=False), carry)
        dk, dv = lax.fori_loop(
            cut, jnp.minimum(j + b, n_blocks),
            lambda i, c: pair(i, c, masked=False, behind=(i - j) * block), carry)
    dk_ref[...] = (dk if fold else dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == n_blocks - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _kernel_options(q, k, window, keep=None, blocks=None, block=None):
    """``(query heads a key/value head, the kernels' window or mask argument, what such a
    kernel's name ends in)``: static, and ``(1, {}, "")`` for full heads, no window and
    no computed mask, which leaves the calls as they were."""
    group = q.shape[0] // k.shape[0]
    if keep is not None:
        return group, {"keep": True}, "_keep"
    if blocks is not None:  # (tiles a half, tiles of the stream, positions a block)
        return group, {"blocks": (blocks[0] // block, q.shape[1] // block, blocks[1])}, "_blocks"
    return group, ({} if window is None else {"window": window}), "" if window is None else "_window"


def _vmem(hd: int, group: int, backward: bool, keep: bool = False) -> dict:
    """The kernels' scoped-VMEM limit where the default 16 MiB does not hold a head: a
    head's ``q``, ``dO`` and float32 ``dQ`` fill it at 8192 positions of 128, and a
    group's float32 ``dK``, ``dV`` blocks pass it by 1.25 MiB; score heads wider than a
    lane tile (192: rows padded to 256 lanes) pass it on both passes, and so does a
    computed mask's strip (4 MiB at 8192 positions, twice for the pipeline)."""
    if hd > 128 or keep:
        return {"vmem_limit_bytes": WIDE_VMEM}
    return {"vmem_limit_bytes": GROUPED_BWD_VMEM} if backward and group != 1 else {}


def _forward(q, k, v, block, interpret, window=None, keep=None, blocks=None):
    """``q`` [B, T, hd], ``k`` [B / group, T, hd], ``v`` [B / group, T, hd_v], ``keep``
    [N, T, T] int8 or None -> output *transposed* [B, hd_v, T], log-sum-exp
    [B, T/block, 1, block]."""
    b, t, hd = q.shape
    hd_v = v.shape[-1]
    n_blocks = t // block
    scale, fold = _scale(hd)
    group, masking, kind = _kernel_options(q, k, window, keep, blocks, block)
    if keep is None:
        grid, semantics = (b, n_blocks), ("parallel", "arbitrary")
        head_of = lambda h, i: h  # the query head of a grid step
        kv_of = (lambda h, i: h) if group == 1 else (lambda h, i: h // group)
        operands, extra = (q, k, v), []
    else:
        # (key/value head, query block, head of the group): the mask's strip follows the
        # first two, so the pipeline fetches it once for the group's heads.
        grid, semantics = (b // group, n_blocks, group), ("parallel", "arbitrary", "arbitrary")
        head_of = lambda h, i, g: h * group + g
        kv_of = lambda h, i, g: h
        kv_a_sequence = (b // group) // keep.shape[0]
        operands = (q, k, v, keep)
        extra = [pl.BlockSpec((None, t, block), lambda h, i, g: (h // kv_a_sequence, 0, i))]
    at = lambda *ids: ids[1]  # the query block of a grid step
    head = lambda width: pl.BlockSpec((None, t, width), lambda *ids: (kv_of(*ids), 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, scale=scale, fold=fold, **masking),
        grid=grid,
        in_specs=[pl.BlockSpec((None, block, hd), lambda *ids: (head_of(*ids), at(*ids), 0)),
                  head(hd), head(hd_v), *extra],
        out_specs=[pl.BlockSpec((None, hd_v, block), lambda *ids: (head_of(*ids), 0, at(*ids))),
                   pl.BlockSpec((None, None, 1, block), lambda *ids: (head_of(*ids), at(*ids), 0, 0))],
        out_shape=[_struct((b, hd_v, t), q.dtype, *operands),
                   _struct((b, n_blocks, 1, block), _F32, *operands)],
        scratch_shapes=[pltpu.VMEM((hd_v, t), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, **_vmem(hd, group, backward=False, keep=keep is not None)),
        interpret=interpret,
        name="causal_attention_fwd" + kind,
    )(*operands)


def _backward(q, k, v, do, lse, delta, block, interpret, window=None, keep=None, blocks=None):
    """Gradients: ``dq`` *transposed* [B, hd, T]; ``dk`` [B, T, hd], ``dv`` [B, T, hd_v] —
    one a QUERY head, in float32, where heads are grouped: the caller sums a group's."""
    b, t, hd = q.shape
    hd_v = v.shape[-1]
    n_blocks = t // block
    scale, fold = _scale(hd)
    group, masking, kind = _kernel_options(q, k, window, keep, blocks, block)
    head = lambda width: pl.BlockSpec((None, t, width), lambda h, j: (h, 0, 0))
    rows = lambda width: pl.BlockSpec((None, block, width), lambda h, j: (h, j, 0))
    kv_rows = rows if group == 1 else lambda width: pl.BlockSpec(
        (None, block, width), lambda h, j: (h // group, j, 0))
    stats = pl.BlockSpec((None, n_blocks, 1, block), lambda h, j: (h, 0, 0, 0))
    operands = (q, k, v, do, lse, delta)
    extra = []
    if keep is not None:  # the key block's strip of the mask, its sequence's
        heads = b // keep.shape[0]
        operands = (*operands, keep)
        extra = [pl.BlockSpec((None, block, t), lambda h, j: (h // heads, j, 0))]
    dk_dtype, dv_dtype = (k.dtype, v.dtype) if group == 1 else (_F32, _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, scale=scale, fold=fold, **masking),
        grid=(b, n_blocks),
        in_specs=[head(hd), kv_rows(hd), kv_rows(hd_v), head(hd_v), stats, stats, *extra],
        out_specs=[pl.BlockSpec((None, hd, t), lambda h, j: (h, 0, 0)), rows(hd), rows(hd_v)],
        out_shape=[_struct((b, hd, t), q.dtype, *operands), _struct(q.shape, dk_dtype, *operands),
                   _struct(do.shape, dv_dtype, *operands)],
        scratch_shapes=[pltpu.VMEM((hd, t), _F32), pltpu.VMEM((hd, block), k.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            **_vmem(hd, group, backward=True, keep=keep is not None)),
        interpret=interpret,
        name="causal_attention_bwd" + kind,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, keep, block, interpret, window, blocks):
    return _attend_fwd(q, k, v, keep, block, interpret, window, blocks)[0]


def _attend_fwd(q, k, v, keep, block, interpret, window, blocks):
    o_t, lse = _forward(q, k, v, block, interpret, window, keep, blocks)
    o, lse = map(checkpoint_name, (jnp.swapaxes(o_t, 1, 2), lse), KEPT)
    return o, (q, k, v, keep, o, lse)


def _attend_bwd(block, interpret, window, blocks, saved, do):
    q, k, v, keep, o, lse = saved
    delta = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1).reshape(lse.shape)
    dq_t, dk, dv = _backward(q, k, v, do, lse, delta, block, interpret, window, keep, blocks)
    if k.shape != q.shape:  # a group's query heads each wrote their own share
        shared = lambda d, like: d.reshape(like.shape[0], -1, *like.shape[1:]).sum(1).astype(like.dtype)
        dk, dv = shared(dk, k), shared(dv, v)
    return jnp.swapaxes(dq_t, 1, 2), dk, dv, None  # a mask takes no gradient


_attend.defvjp(_attend_fwd, _attend_bwd)


def block_diffusion_mask(seq_len: int, half: int, size: int) -> jax.Array:
    """``bool [T, T]``, queries down and keys along: the pairs a stream of ``T`` positions
    sees under ``blocks=(half, size)``, from the four rules as they are stated.  Position
    ``j`` is clean while ``j < half`` and noised from there on, covers text position ``j
    % half``, and lies in block ``(j % half) // size``."""
    at = jnp.arange(seq_len)
    noised, of = at >= half, (at % half) // size
    q_noised, k_noised, q_of, k_of = noised[:, None], noised[None, :], of[:, None], of[None, :]
    return ((~q_noised & ~k_noised & (k_of <= q_of))  # clean reads clean: up to its own block
            | (q_noised & ~k_noised & (k_of < q_of))  # noised reads clean: before its own
            | (q_noised & k_noised & (k_of == q_of)))  # noised reads noised: its own alone


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           window: int | None = None,
                           keep: jax.Array | None = None,
                           blocks: tuple[int, int] | None = None) -> jax.Array:
    """The same function spelled densely, in the inputs' dtype throughout: ``[N, H, T, T]``
    scores, mask, softmax.  What short sequences run, and what the kernels are tested
    against.  Grouped ``k``/``v`` (``[N, H_kv, T, hd]``) are repeated to the query heads;
    ``v``'s head size may differ from ``q``'s and ``k``'s (the output takes it); ``keep``
    ``[N, T, T]`` (keys down, queries along, as :func:`causal_attention` takes it) masks
    every head of its sequence alike; ``blocks=(half, size)`` takes the causal rule's
    place (:func:`block_diffusion_mask`), any ``size``."""
    t, hd = q.shape[-2:]
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(a, q.shape[1] // a.shape[1], axis=1) for a in (k, v))
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / math.sqrt(hd)
    # Causal mask: position q attends to keys <= q only.  Additive -inf keeps the
    # softmax exact for the allowed band.
    causal = jnp.tril(jnp.ones((t, t), bool)) if blocks is None else block_diffusion_mask(t, *blocks)
    if window is not None:  # ... and to keys less than ``window`` positions behind it
        causal &= ~jnp.tril(jnp.ones((t, t), bool), -window)
    seen = causal[None, None]
    if keep is not None:  # ... and to the keys the program's own mask lets through
        seen = seen & (jnp.swapaxes(keep, 1, 2) != 0)[:, None]
    scores = jnp.where(seen, scores, jnp.finfo(scores.dtype).min)
    att = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", att, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int | None = None,
    keep: jax.Array | None = None,
    blocks: tuple[int, int] | None = None,
    block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal attention ``[N, H, T, hd] x 3 -> [N, H, T, hd]``, scale ``1/sqrt(hd)``,
    differentiable in all three; ``T`` whole blocks of ``block`` (default:
    :func:`block_for`).  ``k`` and ``v`` may hold fewer heads, ``[N, H_kv, T, hd]`` with
    ``H`` a multiple of ``H_kv``: query head ``h`` reads head ``h // (H / H_kv)``.  ``v``
    may have another head size, ``[N, H_kv, T, hd_v]``: the output is ``[N, H, T, hd_v]``.
    With ``window``, position ``t`` attends to keys ``t - window < s <= t`` only.  With
    ``keep`` ``[N, T, T]`` (``int8``; keys down, queries along: ``keep[n, s, t]``), position
    ``t`` of sequence ``n`` attends, in every head, to the keys ``s <= t`` with ``keep[n,
    s, t] != 0`` only; each ``t`` has to keep a key.  It is a constant of the backward pass.
    With ``blocks=(half, size)`` the causal rule gives way to the block-diffusion one
    (:func:`block_diffusion_mask`): ``T`` is ``half`` or twice it, ``size`` a power of two
    that divides the kernels' block, and the default block is the largest that divides
    ``half``.

    Off the TPU the kernels run in Pallas's interpreter, which cannot evaluate a kernel
    on values that vary over a ``shard_map`` axis under its varying-axes check (the
    kernel's own constants do not vary); there, and only there, the dense spelling
    answers."""
    n, h, t, hd = q.shape
    h_kv, hd_v = k.shape[1], v.shape[-1]
    if (k.shape != (n, h_kv, t, hd) or v.shape != (n, h_kv, t, hd_v) or h_kv == 0 or h % h_kv
            or hd_v == 0):
        raise ValueError("k must have q's shape and v k's, but for heads that divide q's and "
                         f"v's own head size: {q.shape}, {k.shape}, {v.shape}")
    if window is not None and window < 1:
        raise ValueError(f"window={window}: a position sees itself at least")
    if keep is not None and (window is not None or keep.shape != (n, t, t)):
        raise ValueError(f"keep is one [N, T, T] mask a sequence, {(n, t, t)} here, and takes "
                         f"the window's place: {keep.shape}, window={window}")
    if blocks is not None:
        half, size = blocks
        if window is not None or keep is not None or t not in (half, 2 * half):
            raise ValueError(f"blocks={blocks} stands alone, over one or two halves: T={t}, "
                             f"window={window}, keep given: {keep is not None}")
        if size < 1 or size & (size - 1):
            raise ValueError(f"blocks={blocks}: the kernels take blocks of a power of two")
    block = block_for(t if blocks is None else blocks[0]) if block is None else block
    if block is None or t % block or block % 128 or (blocks and (half % block or block % size)):
        raise ValueError(f"T={t} is not whole blocks of {block or BLOCKS} (multiples of 128"
                         + (f", which {blocks} cut into whole halves and blocks)" if blocks else ")"))
    if window is not None and window >= t:
        window = None  # no key is that far behind
    if keep is not None:
        keep = lax.stop_gradient(keep.astype(jnp.int8))
    interpret = auto_interpret(interpret)
    if interpret and any(jax.typeof(a).vma for a in (q, k, v)):
        return dense_causal_attention(q, k, v, window=window, keep=keep, blocks=blocks)
    flat = lambda a: a.reshape(-1, t, a.shape[-1])
    out = _attend(flat(q), flat(k), flat(v), keep, block, interpret, window, blocks)
    return out.reshape(n, h, t, hd_v)
