"""Mixture-of-experts decoder whose attention reads, for each query, only the keys a
learned indexer picks (the ``KeyeVL2`` language model's layout: ``sa_config`` with
``indexer_num_heads``, ``indexer_head_dim``, one indexer key head, ``topk``; grouped-query
attention under the pick, ``mrope_section`` rotary positions, softmax-routed gated
experts with no shared one).

A layer, ``x`` [N, T, d] (pre-norm residual, no bias anywhere; all layers alike)::

    u        = RMSNorm_in(x)
    q, k, v  = u W_q [T,H,hd], u W_k [T,H_kv,hd], u W_v [T,H_kv,hd]
    q, k     = RMSNorm_q(q), RMSNorm_k(k)          per head, over the hd dimensions
    q, k     = rotate(q, pos), rotate(k, pos)      rotate-half, all hd dimensions; frequency
                                                   pair i takes its position from the component
                                                   of pos [3, T] its section names; text: all t
    qI, kI, w = u W_qI [T,J,dI], u W_kI [T,dI], u W_w [T,J]    the indexer: float32, HIGHEST
    I[t,s]   = sum_j w[t,j] * relu(qI[t,j] . kI[s])             s <= t
    S_t      = the topk keys s <= t of largest I[t,s], equal scores to the smaller s;
               every s <= t while t < topk
    a_t      = softmax over s in S_t of (q_t . k_s / sqrt(hd)) v_s     query head h reads
                                        head h // (H / H_kv); ONE S_t for all the heads
    x'       = x + a W_o
    h        = RMSNorm_post(x')
    picks, g = top_k(h W_r), softmax over the picked logits
    out      = x' + sum over held picks e of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head.  ``S_t`` is a constant of the backward pass: the
pick is a mask of integers, so ``W_qI``, ``W_kI`` and ``W_w`` take a gradient of exactly
zero (the loss that would train them, a divergence against the attention's own
distribution, is not built: the indexer is read, not trained).  Like ``moe_decoder_lm`` it
drops into the standard federated pipeline: ``apply`` returns next-token
log-probabilities at the LAST position (``[N, vocab]``), the layers are stacked on a
leading axis, and every layer is rematerialized (``jax.checkpoint``) but for what
carries a name (``models.experts.KEEP_NAMED_OUTPUTS``): the attention kernels' output
and log-sum-exp, the expert dispatch's integer layout, and **the pick** (:data:`KEPT`:
one ``int8 [N, T, T]`` mask a layer, 64 MiB at 8192 positions), so the indexer's scores
and the selection run once a step.

**The pick** is made in bands of :data:`INDEX_BAND` queries, keys down and queries along
as ``ops.attention``'s score blocks are: a band's scores ``[keys <= the band's last
query, band]`` (no ``[J, T, T]`` array exists), then an exact selection of each query's
``topk`` largest by bisection over the bits of the float (:func:`top_keys`: four-way,
sixteen passes over the band, each one fused compare-and-count; no sort), the tie rule by
a second bisection over the key's position that runs only where a threshold's ties
outnumber the places left.  Bands whose every query has at most ``topk`` keys behind it
are causal and compute nothing.  **Attention** then runs in ``ops.attention``'s kernels
under that mask (``keep=``) wherever the sequence is whole blocks of at least
``MIN_SEQ`` positions, and densely below that (tests); where ``T <= topk`` no pick can
bind and the layer is plain causal grouped-query attention, statically.

**Experts**: the layer is TOLD which experts it holds (``first_expert``,
``experts_held``); dispatch and the block loop are ``models.experts``', with the
SiLU-gated activation on a fused ``[d, 2 f]`` leaf; the router is
``experts.route``.  The layer reports :data:`COUNTERS` through
``apply.with_counters``: the experts' three and the pick's two.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.models.decoder import (
    language_model, pair_frequencies, rms_norm, run_layers, turn_pairs)
from nanofed_tpu.models.experts import COUNTERS as EXPERT_COUNTERS
from nanofed_tpu.models.experts import INDEXER_KEPT, SWIGLU, check_held, held_experts, route
from nanofed_tpu.nn import embed_rows
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention, engages

#: Queries a band of the indexer holds: the configuration's ``q_chunk_size``, and the
#: attention kernels' block at the cell's length, so that :data:`SPARSE_COUNTERS`' live
#: blocks are the kernels' own.  A band's scores are ``[J, keys, band]`` float32 before
#: the sum over the indexer's heads: 256 MiB at 16 heads of 8192 keys.
INDEX_BAND = 512
#: Bands that share one loop body, and with it the body's key count (the group's last
#: band's): every band its own body would stop each at its own last key (an eighth less
#: indexer work at 16 bands) for four times the program; all twelve selecting bands in
#: one body under all 8192 keys cost a round 0.124 s (2.848 s against 2.724, PR 40).
BAND_GROUP = 4
#: Bits a pass of the selection settles: a pass counts, for each query, the keys at or
#: above each of ``2**RADIX_BITS - 1`` thresholds in one read of the band.  Compiled for a
#: v5e the band's keys (16 MiB at 8192) sit in the chip's fast memory, so a pass costs its
#: compares, and those add up to ``32 / RADIX_BITS * (2**RADIX_BITS - 1)`` a key: 32, 48,
#: 120 at one, two, four bits; each pass has its own fixed cost too.  Measured at the
#: cell (PR 40): a round takes 2.732 / 2.724 / 2.823 s at one / two / four bits.
RADIX_BITS = 2
#: The pick's name under a layer's checkpoint (``models.experts.KEEP_NAMED_OUTPUTS``).
KEPT = INDEXER_KEPT
#: What the pick counts of a layer, beside the experts' three: kept pairs over causal
#: pairs (``topk (2T - topk + 1) / (T (T + 1))``: 0.4375 at 8192 of 2048; 1 where the pick
#: does not bind), and, of the (query band, key band) pairs on or under the diagonal, the
#: share holding at least one kept pair: what a kernel that skipped blocks could save.
SPARSE_COUNTERS = ("sparse_kept_pair_share", "sparse_live_block_share")
COUNTERS = (*EXPERT_COUNTERS, *SPARSE_COUNTERS)

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def init_indexed_moe(rng: PRNGKey, *, vocab, width, layers, attn_heads, kv_heads, head_dim,
                     index_heads, index_dim, experts, experts_held, expert_width, **_) -> Params:
    """N(0, 1) embeddings; N(0, 0.02) head and matrices, the indexer's among them, the two
    projections into the residual stream N(0, 0.02 / sqrt(2 layers)); norms 1
    (``moe_decoder.init_moe_decoder`` says why the embeddings dominate)."""
    n = layers
    k = jax.random.split(rng, 12)
    normal = lambda key, *shape, std=0.02: std * jax.random.normal(key, shape, _F32)
    ones = lambda *shape: jnp.ones(shape, _F32)
    into_stream = 0.02 / math.sqrt(2 * n)
    return {
        "embed": normal(k[0], vocab, width, std=1.0),
        "head": normal(k[1], width, vocab),
        "norm_f": ones(width),
        "layers": {
            "norm_in": ones(n, width),
            "wq": normal(k[2], n, width, attn_heads * head_dim),
            "wk": normal(k[3], n, width, kv_heads * head_dim),
            "wv": normal(k[4], n, width, kv_heads * head_dim),
            "norm_q": ones(n, head_dim),
            "norm_k": ones(n, head_dim),
            "wo": normal(k[5], n, attn_heads * head_dim, width, std=into_stream),
            "index_wq": normal(k[6], n, width, index_heads * index_dim),
            "index_wk": normal(k[7], n, width, index_dim),
            "index_w": normal(k[8], n, width, index_heads),
            "norm_post": ones(n, width),
            "router": normal(k[9], n, width, experts),
            "w_gate_up": normal(k[10], n, experts_held, width, 2 * expert_width),
            "w_down": normal(k[11], n, experts_held, expert_width, width, std=into_stream),
        },
    }


def text_positions(seq_len: int) -> jax.Array:
    """``pos`` [3, T] of a text sequence: all three components the token's index."""
    return jnp.broadcast_to(jnp.arange(seq_len, dtype=_F32), (3, seq_len))


def rotate(x: jax.Array, pos: jax.Array, theta: float, sections) -> jax.Array:
    """Sectioned rotary positions on ``x`` [N, T, heads, hd]: dimension ``i`` pairs with
    ``i + hd/2`` (``decoder.turn_pairs``) and pair ``i`` turns by ``pos[c(i), t] *
    theta^(-2i/hd)``, ``c(i)`` the component whose section of the ``hd/2`` pairs holds
    ``i`` (``sections`` = 16, 24, 24: pairs 0-15 component 0, 16-39 component 1, 40-63
    component 2).  Float32 angles; with :func:`text_positions` this is ``decoder.rotate``."""
    freq = pair_frequencies(x.shape[-1] // 2, theta)
    component = np.repeat(np.arange(len(sections)), sections)
    return turn_pairs(x, pos.astype(_F32)[component, :].T * freq[None, :])  # angle [T, half]


def _count(flags) -> list[jax.Array]:
    """For each ``[N, keys, band]`` boolean of ``flags``, how many keys a query has set,
    ``int32 [N, 1, band]``; all in ONE reduction, so the band is read once."""
    ones = [f.astype(jnp.int32) for f in flags]
    sums = lax.reduce(ones, [jnp.int32(0)] * len(ones),
                      lambda acc, x: tuple(a + b for a, b in zip(acc, x)), (1,))
    return [s[:, None, :] for s in sums]


def top_keys(scores: jax.Array, first: int, topk: int) -> jax.Array:
    """The pick of one band: ``int8 [N, keys, band]``, 1 where key ``s`` is among the
    ``topk`` largest ``scores[n, s, q]`` over the keys ``s <= first + q`` (the band's
    query ``q`` stands at position ``first + q``), equal scores going to the smaller
    ``s``; every such key where there are at most ``topk``.  Exact, and no sort: a
    float's bits, turned so that unsigned order is the float's, are settled
    :data:`RADIX_BITS` at a time from the top, each pass counting the keys at or above
    every candidate threshold; the threshold reached is the ``topk``-th largest score
    itself.  Ties at it beyond the places left are cut by position, by a bisection that
    runs no pass where no query has such ties."""
    n, keys, band = scores.shape
    at = lax.broadcasted_iota(jnp.int32, (1, keys, band), 1)
    valid = at <= first + lax.broadcasted_iota(jnp.int32, (1, keys, band), 2)
    bits = lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), jnp.int32)  # -0.0 is 0.0
    turned = lax.bitcast_convert_type(bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32)
    # Unsigned order is the float's; 0 is under every score's key and marks the future.
    key = jnp.where(valid, turned ^ jnp.uint32(0x80000000), jnp.uint32(0))
    digits = jnp.arange(1, 2 ** RADIX_BITS, dtype=jnp.uint32)

    def settle(step, threshold):  # the next RADIX_BITS bits of every query's threshold
        shift = jnp.asarray(32 - RADIX_BITS * (step + 1), jnp.uint32)
        counts = _count([key >= (threshold | (d << shift)) for d in digits])
        digit = sum((c >= topk).astype(jnp.uint32) for c in counts)  # counts fall with d
        return threshold | (digit << shift)

    # (zeros typed as the keys are: inside ``shard_map`` a carry starts as it will go on)
    threshold = lax.fori_loop(0, 32 // RADIX_BITS, settle, key[:, :1] & jnp.uint32(0))
    above, ties = key > threshold, (key == threshold) & valid
    n_above, n_ties = _count([above, ties])
    places = topk - n_above  # >= 1: the threshold is the topk-th largest key itself

    def narrow(bounds):
        low, high = bounds  # ties at positions <= low are too few, <= high enough
        mid = (low + high) >> 1
        enough = _count([ties & (at <= mid)])[0] >= places
        return jnp.where(enough, low, mid), jnp.where(enough, mid, high)

    open_ = lambda bounds: jnp.any((n_ties > places) & (bounds[1] - bounds[0] > 1))
    start = (n_ties * 0 - 1, n_ties * 0 + (keys - 1))
    _, last = lax.while_loop(open_, narrow, start)
    return (above | (ties & (at <= last))).astype(jnp.int8)


def index_keys(p: Params, u: jax.Array, cfg: dict):
    """``(keep, counters)``: the layer's pick as ``ops.attention`` takes it (``int8 [N, T,
    T]``, keys down and queries along; ``None`` where ``T <= topk``: no pick binds) and
    its :data:`SPARSE_COUNTERS` (float32 ``[2]``), from the normed ``u`` [N, T, d]."""
    n, t, _ = u.shape
    heads, dim, topk = cfg["index_heads"], cfg["index_dim"], cfg["index_topk"]
    if t <= topk:
        return None, jnp.ones((len(SPARSE_COUNTERS),), _F32)
    band = min(INDEX_BAND, t)
    if t % band:
        raise ValueError(f"T={t} is not whole bands of {band} queries")
    with jax.named_scope("indexer_proj"):
        project = lambda w: jnp.matmul(u.astype(_F32), w.astype(_F32), precision=_HIGHEST)
        q_index = project(p["index_wq"]).reshape(n, t, heads, dim)
        k_index, weight = project(p["index_wk"]), project(p["index_w"])
    bands = t // band
    free = min(topk // band, bands)  # bands whose queries keep every key they see

    def one_band(args):
        q_band, w_band, first = args  # [N, band, J, dim], [N, band, J], the first position
        with jax.named_scope("indexer_scores"):
            dots = jnp.einsum("nsd,nqjd->njsq", k_band, q_band, precision=_HIGHEST)
            scores = (jax.nn.relu(dots) * w_band.transpose(0, 2, 1)[:, :, None, :]).sum(axis=1)
        with jax.named_scope("indexer_select"):
            strip = top_keys(scores, first, topk)
            tiles = strip.reshape(n, strip.shape[1] // band, band * band)
            return strip, strip.sum(dtype=jnp.int32), tiles.any(axis=-1).sum(dtype=jnp.int32)

    banded = lambda a, lo, hi: jnp.moveaxis(
        a[:, lo * band:hi * band].reshape(n, hi - lo, band, *a.shape[2:]), 1, 0)
    strips = []
    if free:  # key s <= query q, for the first free * band queries: no score is needed
        with jax.named_scope("indexer_select"):
            strips.append(jnp.broadcast_to(jnp.triu(jnp.ones((t, free * band), jnp.int8)),
                                           (n, t, free * band)))
    kept, live = n * (free * band) * (free * band + 1) // 2, n * free * (free + 1) // 2
    # Bands go BAND_GROUP at a time through one loop body, under the group's last key.
    for lo in range(free, bands, BAND_GROUP):
        hi = min(lo + BAND_GROUP, bands)
        k_band = k_index[:, :hi * band]
        group, group_kept, group_live = lax.map(one_band, (
            banded(q_index, lo, hi), banded(weight, lo, hi), jnp.arange(lo, hi) * band))
        with jax.named_scope("indexer_select"):
            group = jnp.moveaxis(group, 0, 2).reshape(n, hi * band, (hi - lo) * band)
            strips.append(jnp.pad(group, ((0, 0), (0, t - hi * band), (0, 0))))
            kept, live = kept + group_kept.sum(), live + group_live.sum()
    with jax.named_scope("indexer_select"):
        keep = checkpoint_name(lax.stop_gradient(jnp.concatenate(strips, axis=2)), KEPT[0])
        counted = jnp.stack([jnp.asarray(kept, _F32) / (n * t * (t + 1) // 2),
                             jnp.asarray(live, _F32) / (n * bands * (bands + 1) // 2)])
    return keep, counted


def attention(p: Params, u: jax.Array, pos: jax.Array, keep, cfg: dict) -> jax.Array:
    """Grouped-query causal attention over the normed ``u`` [N, T, d] under the pick
    ``keep``, its output projection included."""
    n, t, _ = u.shape
    hq, hkv, hd = cfg["attn_heads"], cfg["kv_heads"], cfg["head_dim"]
    with jax.named_scope("attention_proj"):
        q = rms_norm(p["norm_q"], (u @ p["wq"]).reshape(n, t, hq, hd), cfg["eps"])
        k = rms_norm(p["norm_k"], (u @ p["wk"]).reshape(n, t, hkv, hd), cfg["eps"])
        v = (u @ p["wv"]).reshape(n, t, hkv, hd)
    with jax.named_scope("rope"):
        q, k = (rotate(a, pos, cfg["rope_theta"], cfg["rope_sections"]) for a in (q, k))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    with jax.named_scope("attention_indexed"):
        attend = causal_attention if engages(t) else dense_causal_attention
        out = attend(q, k, v, keep=keep)
    with jax.named_scope("attention_proj"):
        return out.transpose(0, 2, 1, 3).reshape(n, t, hq * hd) @ p["wo"]


def decoder_layer(p: Params, x: jax.Array, pos: jax.Array, cfg: dict):
    """``(the layer's output [N, T, d], its counters)``."""
    n, t, d = x.shape
    u = rms_norm(p["norm_in"], x, cfg["eps"])
    keep, picked = index_keys(p, u, cfg)
    x = x + attention(p, u, pos, keep, cfg)
    h = rms_norm(p["norm_post"], x, cfg["eps"])
    with jax.named_scope("moe_router"):
        picks, weights = route(p["router"], h.reshape(n * t, d), cfg["top_k"])
    routed, counted = held_experts(
        h.reshape(n * t, d), picks, weights, p["w_gate_up"], p["w_down"],
        first_expert=cfg["first_expert"], activation=SWIGLU)
    return x + routed.reshape(n, t, d), jnp.concatenate([counted, picked])


def hidden_states(params: Params, tokens: jax.Array, cfg: dict, pos: jax.Array | None = None):
    """``([N, T, width]`` after the last layer, counters summed over the layers);
    ``pos`` [3, T] defaults to a text sequence's."""
    x = embed_rows(params["embed"], tokens.astype(jnp.int32))
    pos = text_positions(tokens.shape[1]) if pos is None else pos
    layer = partial(decoder_layer, cfg=cfg)  # all layers alike: one trace
    plan = [(layer, params["layers"], index) for index in range(cfg["layers"])]
    return run_layers(x, plan, len(COUNTERS), pos)


@register_model("indexed_moe_lm")
def indexed_moe_lm(
    vocab: int = 256,
    seq_len: int = 32,
    width: int = 64,
    layers: int = 2,
    attn_heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    rope_theta: float = 1e7,
    rope_sections: tuple[int, ...] = (2, 3, 3),
    index_heads: int = 4,
    index_dim: int = 8,
    index_topk: int = 8,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 48,
    eps: float = 1e-6,
) -> Model:
    """The decoder as a zoo entry (defaults are test-sized).  ``rope_sections`` says how
    many of a head's ``head_dim / 2`` rotary pairs follow each of the three position
    components; ``index_topk`` is the keys a query keeps; ``experts`` is what the router
    scores, ``first_expert`` and ``experts_held`` say which of them this program holds."""
    cfg = dict(locals())
    cfg["rope_sections"] = tuple(rope_sections)
    if layers < 1 or attn_heads % kv_heads or head_dim % 2 or index_topk < 1:
        raise ValueError("layers >= 1, attn_heads must divide into kv_heads, head_dim in two, "
                         "index_topk >= 1")
    if len(rope_sections) != 3 or sum(rope_sections) != head_dim // 2:
        raise ValueError(f"rope_sections {rope_sections}: three counts that add up to "
                         f"head_dim / 2 = {head_dim // 2}")
    check_held(experts, first_expert, experts_held, top_k)
    return language_model("indexed_moe_lm", cfg, init_indexed_moe, hidden_states, COUNTERS,
                          layers)
