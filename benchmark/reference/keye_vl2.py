"""Plain reference of the decoder the zoo calls ``indexed_moe_lm``: a ``KeyeVL2`` language
model's layers, each grouped-query attention that reads only the keys a learned indexer
picks for the query, followed by sparse gated experts.

A layer, ``x`` [T, d] (pre-norm residual stack, no bias; all layers alike)::

    u        = RMSNorm_in(x)
    q, k, v  = u W_q [T,H,hd], u W_k [T,H_kv,hd], u W_v [T,H_kv,hd]
    q, k     = RMSNorm_q(q), RMSNorm_k(k)        per head, over the hd dimensions
    q, k     = rotate(q, pos), rotate(k, pos)    rotate-half over all hd dimensions, theta;
                                                 frequency pair i reads component 0 of pos
                                                 [3, T] (i < 16), 1 (i < 40) or 2 (mrope_section
                                                 16/24/24); text: all three are t
    qI, kI, w = u W_qI [T,J,dI], u W_kI [T,dI], u W_w [T,J]      float32: never rounded
    I[t,s]   = sum_j w[t,j] * relu(qI[t,j] . kI[s])               s <= t
    S_t      = the topk keys s <= t of largest I[t,s], equal scores to the smaller s;
               every s <= t while t < topk
    a_t      = softmax over s in S_t of (q_t . k_s / sqrt(hd)) v_s   query head h reads head
                                                 h // (H / H_kv); one S_t for all heads
    x'       = x + a W_o
    h        = RMSNorm_post(x')
    r        = h W_r                             float32
    picks    = top_k(r); g = softmax(r[picks])
    out      = x' + sum over e in picks that are HELD of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head; log-probabilities of the next token at the LAST
position only, which is where the repo's token-stream pipeline puts the loss.  ``S_t`` is
a constant of the backward pass: the three indexer matrices take a gradient of exactly
zero (the alignment loss that trains an indexer is not built).  Written for reading,
not speed:

* the pick is a dense boolean mask from ``lax.top_k`` over a whole row of indexer scores
  (its ``topk``-th value, the keys above it, its equals by position), one band of
  ``QUERY_BAND`` queries at a time (the program bisects the floats' bits and never sorts);
* causal order and the pick are one mask over whole score rows (the program's kernels
  walk blocks);
* the rotation is written out on the two halves of a head;
* every held expert's product is computed densely over all tokens and weighted by a
  one-hot product of the router's picks, zero where the expert was not picked;
* the router scores all ``experts``; only experts ``first_expert .. first_expert +
  experts_held`` live here, and what the absent ones would add is left out (the guide's
  expert-parallel cut).

The leaves are the program's, so ``W_gate | W_up`` of an expert come as one ``[d, 2f]``
matrix and are split here.  Layers are stacked on a leading axis and run under
``lax.scan`` (all alike; their gradients then come out stacked, where a loop over
``leaf[i]`` held several whole-tree copies to stack them).  Each layer is
rematerialized, attention and the indexer go by bands of ``QUERY_BAND`` queries (so that
no ``[heads, T, T]`` array exists) and the experts one at a time under ``lax.scan``, each
rematerialized too and summed over chunks of ``EXPERT_CHUNK`` hidden units, so that a
float32 round at the published widths fits one chip.
Imports nothing of the program.

``q`` rounds a matmul operand to the precision under test and returns float32.  The
router and the indexer are float32 in the configuration's stated precision, so they are
not rounded.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TOKEN_STREAM = True
QUERY_BAND = 256
#: Hidden units of an expert a step of its sum: ``W_down (silu(W_gate h) * (W_up h))`` is
#: summed over column chunks, the same sum.  What it buys is memory, by what the TPU's
#: compiler then decides: with an expert's 768 units in one product it keeps the stacked
#: expert leaves in a transposed layout through the local fit and copies each whole, twice
#: (10.52 GiB of temporaries for a round; 8.27 in two chunks, read from the round compiled
#: for a described v5e; 8.4 GiB are free when the benchmark runs the reference).
EXPERT_CHUNK = 384
HIGHEST = lax.Precision.HIGHEST


def init_params(key, kw):
    """Weights from the seed: N(0, 1) embeddings; N(0, 0.02) head and matrices, the
    indexer's three among them; the two projections into the residual stream (``wo``,
    ``w_down``) N(0, 0.02 / sqrt(2 layers)); norms 1 (``smallthinker.py`` says why the
    embeddings dominate the stream)."""
    d, vocab, n = kw["width"], kw["vocab"], kw["layers"]
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    held, f = kw["experts_held"], kw["expert_width"]
    k = jax.random.split(key, 12)
    normal = lambda kk, *shape, std=0.02: std * jax.random.normal(kk, shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    into_stream = 0.02 / math.sqrt(2 * n)
    return {
        "embed": normal(k[0], vocab, d, std=1.0),
        "head": normal(k[1], d, vocab),
        "norm_f": ones(d),
        "layers": {
            "norm_in": ones(n, d),
            "wq": normal(k[2], n, d, hq * hd),
            "wk": normal(k[3], n, d, hkv * hd),
            "wv": normal(k[4], n, d, hkv * hd),
            "norm_q": ones(n, hd),
            "norm_k": ones(n, hd),
            "wo": normal(k[5], n, hq * hd, d, std=into_stream),
            "index_wq": normal(k[6], n, d, kw["index_heads"] * kw["index_dim"]),
            "index_wk": normal(k[7], n, d, kw["index_dim"]),
            "index_w": normal(k[8], n, d, kw["index_heads"]),
            "norm_post": ones(n, d),
            "router": normal(k[9], n, d, kw["experts"]),
            "w_gate_up": normal(k[10], n, held, d, 2 * f),
            "w_down": normal(k[11], n, held, f, d, std=into_stream),
        },
    }


def _rms_norm(weight, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def text_positions(t):
    """``pos`` [3, T] of a text sequence: every component the token's index."""
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.float32), (3, t))


def _rotate(x, pos, theta, sections):
    """``x`` [B, T, heads, hd]: the pair (``i``, ``i + hd/2``) at position ``t`` turned by
    ``pos[c(i), t] * theta ** (-2 i / hd)``, ``c(i)`` the section that holds pair ``i``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    component = np.concatenate([np.full(count, c) for c, count in enumerate(sections)])
    angle = jnp.stack([pos[c].astype(jnp.float32) * inv_freq[i]
                       for i, c in enumerate(component)], axis=-1)  # [T, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def indexer_scores(p, u, kw):
    """``I`` [B, T, T] float32 (queries down, keys along), the future NOT yet masked.  Only
    the tests call this on a whole sequence; :func:`_attention` computes it a band at a
    time."""
    return _band_scores(*_indexer_projections(p, u, kw), slice(None))


def _indexer_projections(p, u, kw):
    n, t, _ = u.shape
    project = lambda w: jnp.matmul(u.astype(jnp.float32), w, precision=HIGHEST)
    return (project(p["index_wq"]).reshape(n, t, kw["index_heads"], kw["index_dim"]),
            project(p["index_wk"]), project(p["index_w"]))


def _band_scores(q_index, k_index, weight, rows):
    dots = jnp.einsum("bqjd,bsd->bqjs", q_index[:, rows], k_index, precision=HIGHEST)
    return jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(dots), weight[:, rows], precision=HIGHEST)


def picked(scores, first, topk):
    """The pick of a band of queries as a boolean ``[B, band, T]``: True at the ``topk``
    keys ``s <= first + q`` of largest ``scores[b, q, s]``, equal scores going to the
    smaller ``s``; at every such key where there are fewer.  The ``topk``-th largest score
    of a query is read off ``lax.top_k``; every key above it is in, and the places left go
    to the keys that equal it in order of position."""
    band, t = scores.shape[-2:]
    causal = jnp.arange(t)[None, :] <= first + jnp.arange(band)[:, None]
    if topk >= t:
        return jnp.broadcast_to(causal, scores.shape)
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 and 0.0 are one score
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = lax.top_k(scores, topk)[0][..., -1:]
    above, equal = scores > kth, scores == kth
    places = topk - above.sum(axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= places))) & causal


def _attention(p, u, pos, kw, q):
    n, t, _ = u.shape
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    qh = (q(u) @ q(p["wq"])).reshape(n, t, hq, hd)
    kh = (q(u) @ q(p["wk"])).reshape(n, t, hkv, hd)
    vh = (q(u) @ q(p["wv"])).reshape(n, t, hkv, hd)
    qh, kh = _rms_norm(p["norm_q"], qh, kw["eps"]), _rms_norm(p["norm_k"], kh, kw["eps"])
    qh = _rotate(qh, pos, kw["rope_theta"], kw["rope_sections"])
    kh = _rotate(kh, pos, kw["rope_theta"], kw["rope_sections"])
    q_index, k_index, weight = _indexer_projections(p, u, kw)
    band = min(QUERY_BAND, t)

    @jax.checkpoint
    def one_band(args):
        q_band, q_index_band, weight_band, first = args
        index = _band_scores(q_index_band, k_index, weight_band, slice(None))
        seen = lax.stop_gradient(picked(index, first, kw["index_topk"]))  # [B, band, T]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q(q_band), q(kh)) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", q(att), q(vh))

    banded = lambda a: jnp.moveaxis(a.reshape(n, t // band, band, *a.shape[2:]), 1, 0)
    bands = banded(qh.reshape(n, t, hkv, hq // hkv, hd))
    out = lax.map(one_band, (bands, banded(q_index), banded(weight), jnp.arange(t // band) * band))
    return q(jnp.moveaxis(out, 0, 1).reshape(n, t, hq * hd)) @ q(p["wo"])


def gates(router, h, kw):
    """``[..., experts]``: the weight each expert's output gets, zero where not picked:
    float32 logits, the ``top_k`` largest, softmax over those (``norm_topk_prob``: the
    softmax over all experts renormalised over the picks is the same numbers)."""
    logits = jnp.matmul(h.astype(jnp.float32), router, precision=HIGHEST)
    top, picks = lax.top_k(logits, kw["top_k"])
    weight = jax.nn.softmax(top, axis=-1)
    return (jax.nn.one_hot(picks, kw["experts"], dtype=jnp.float32) * weight[..., None]).sum(axis=-2)


def routed_experts(p, h, gate, kw, q, first, held):
    """The part of the layer's feed-forward that experts ``first .. first + held`` give
    for ``h``, under ``gate`` [..., experts]; ``p["w_gate_up"]`` / ``p["w_down"]`` hold
    exactly those."""
    f = kw["expert_width"]

    chunk = EXPERT_CHUNK if f % EXPERT_CHUNK == 0 else f
    columns = lambda w: jnp.moveaxis(w.reshape(w.shape[0], f // chunk, chunk), 1, 0)

    @jax.checkpoint
    def one_chunk(out, weights):  # a chunk of the expert's hidden units
        w_gate, w_up, w_out = weights
        return out + q(jax.nn.silu(q(h) @ q(w_gate)) * (q(h) @ q(w_up))) @ q(w_out), None

    @jax.checkpoint
    def one_expert(out, expert):
        w_gate_up, w_down, weight = expert
        chunks = (columns(w_gate_up[:, :f]), columns(w_gate_up[:, f:]),
                  w_down.reshape(f // chunk, chunk, w_down.shape[1]))
        return out + weight[..., None] * lax.scan(one_chunk, jnp.zeros_like(h), chunks)[0], None

    held_gate = jnp.moveaxis(gate[..., first:first + held], -1, 0)
    out, _ = lax.scan(one_expert, jnp.zeros_like(h), (p["w_gate_up"], p["w_down"], held_gate))
    return out


def attention_block(p, x, pos, kw, q):
    """``x' = x + a W_o``: what every chip that shares the layer computes alike."""
    return x + _attention(p, _rms_norm(p["norm_in"], x, kw["eps"]), pos, kw, q)


def layer(p, x, pos, kw, q):
    x = attention_block(p, x, pos, kw, q)
    h = _rms_norm(p["norm_post"], x, kw["eps"])
    gate = gates(p["router"], h, kw)
    return x + routed_experts(p, h, gate, kw, q, kw["first_expert"], kw["experts_held"])


def hidden_states(params, tokens, kw, q=lambda t: t, pos=None):
    """``[N, T, width]`` after the last layer, before the final norm; ``pos`` [3, T]
    defaults to a text sequence's."""
    x = params["embed"][tokens]
    pos = text_positions(tokens.shape[1]) if pos is None else pos
    one = jax.checkpoint(lambda x, p: layer(p, x, pos, kw, q))  # each layer rematerialized
    return lax.scan(lambda x, p: (one(x, p), None), x, params["layers"])[0]


def log_probs(params, tokens, key, kw, q=lambda t: t, pos=None):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    x = hidden_states(params, tokens, kw, q, pos)[:, -1, :]
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))
