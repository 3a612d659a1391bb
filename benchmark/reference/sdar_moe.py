"""Plain reference of the decoder the zoo calls ``diffusion_moe_lm``: an ``sdar_moe``
language model (a Qwen3-MoE backbone: grouped-query attention with per-head q/k norms and
rotary positions over softmax-routed gated experts, no shared one) trained and read by
diffusion over blocks of ``B`` tokens.

A layer, ``x`` [S, d] over ``S`` stream positions, position ``j`` standing at text position
``pos[j]`` (pre-norm residual stack, no bias; all layers alike)::

    u        = RMSNorm_in(x)
    q, k, v  = u W_q [S,H,hd], u W_k [S,H_kv,hd], u W_v [S,H_kv,hd]
    q, k     = RMSNorm_q(q), RMSNorm_k(k)        per head, over the hd dimensions
    q, k     = rotate(q, pos), rotate(k, pos)    rotate-half over all hd dimensions, theta
    a        = softmax(q k^T / sqrt(hd) + M) v   query head h reads head h // (H / H_kv)
    x'       = x + a W_o
    h        = RMSNorm_post(x')
    r        = h W_r                             float32
    picks    = top_k(r); g = softmax(r[picks])
    out      = x' + sum over e in picks that are HELD of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head.  Training (:func:`sample_nll`; ``L`` the
sequence's length, ``b(i) = i // B``), all draws from the step ``key``::

    k_t, k_m = split(key)
    t[n, c]  = eps + (1 - eps) U(k_t)[n, c]          one noise level a block c, eps = 1e-3
    m[n, i]  = U(k_m)[n, i] < t[n, b(i)]
    x_t      = where(m, MASK, x_0)                   MASK the vocabulary's last id
    stream   = [x_0 ; x_t],  S = 2 L,  pos = [0 .. L-1, 0 .. L-1]
    M        : query j sees key s iff  both clean: b(s) <= b(j);  j noised, s clean:
               b(s) < b(j);  both noised: b(s) = b(j);  j clean, s noised: never
    loss[n]  = (1 / L) sum_i m[n, i] / t[n, b(i)] * -log p(x_0[n, i] | stream)[L + i]

Reading (:func:`log_probs`, what the program's ``apply`` returns): one stream of ``L``
whose last block is MASK, the clean rule alone, log-probabilities at the last position.

Departures from the published description, each also under ``assumed`` in the
configuration's file: the block length, the noise schedule (``t`` uniform a block with
the ``1/t`` weight of a linear schedule), the floor of ``t``, no shift of the logits, and
MASK as the last id of the held vocabulary slice are NOT in the published keys; of the
seeded weights MASK's embedding row is chosen among N(0, 1) draws (:func:`mask_row`); the
router scores all ``experts`` but only experts ``first_expert .. first_expert +
experts_held`` live here, and what the absent ones would add is left out (the guide's
expert-parallel cut).  Written for reading, not speed:

* the mask is ONE dense boolean ``[S, S]`` built from the four rules as stated, and
  attention goes over it in rematerialized bands of ``QUERY_BAND`` queries (the program's
  kernels compute it tile by tile from positions and skip the tiles it empties);
* the rotation is written out on the two halves of a head;
* every held expert's product is computed densely over all tokens and weighted by a
  one-hot product of the router's picks, zero where the expert was not picked;
* the head runs over the noised half in rematerialized chunks of ``HEAD_CHUNK``
  positions, each giving its part of every sample's loss (the same sum; what it buys is
  that no ``[L, vocab]`` array and cotangent are held, so a float32 round fits one chip).

The leaves are the program's, so ``W_gate | W_up`` of an expert come as one ``[d, 2f]``
matrix and are split here.  Layers are stacked on a leading axis and run under
``lax.scan``, each rematerialized.  Imports nothing of the program.

``q`` rounds a matmul operand to the precision under test and returns float32.  The
router is float32 in the configuration's stated precision, so it is not rounded.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

TOKEN_STREAM = True
QUERY_BAND = 256
HEAD_CHUNK = 512
#: Hidden units of an expert a step of its sum (``keye_vl2.py`` says what it buys).
EXPERT_CHUNK = 384
#: The least noise level of a block (``assumed`` in the configuration's file).
NOISE_FLOOR = 1e-3
#: Rows drawn for MASK's embedding, of which :func:`mask_row` takes the first that fits,
#: and the least gap it asks between what a layer picks and what it leaves out (logits'
#: standard deviation is 0.9 at the cell's width; a masked position's context moves them by 0.03).
MASK_CANDIDATES, MASK_MARGIN = 65536, 0.15
HIGHEST = lax.Precision.HIGHEST


def init_params(key, kw):
    """Weights from the seed: N(0, 1) embeddings, MASK's row chosen among such draws
    (:func:`mask_row`); N(0, 0.02) head and matrices; the two projections into the
    residual stream (``wo``, ``w_down``) N(0, 0.02 / sqrt(2 layers)); norms 1
    (``smallthinker.py`` says why the embeddings dominate the stream)."""
    d, vocab, n = kw["width"], kw["vocab"], kw["layers"]
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    held, f = kw["experts_held"], kw["expert_width"]
    k = jax.random.split(key, 9)
    normal = lambda kk, *shape, std=0.02: std * jax.random.normal(kk, shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    into_stream = 0.02 / math.sqrt(2 * n)
    router = normal(k[6], n, d, kw["experts"])
    embed = normal(k[0], vocab, d, std=1.0)
    return {
        "embed": embed.at[mask_id(kw)].set(mask_row(jax.random.fold_in(k[0], 1), router, kw)),
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
            "norm_post": ones(n, d),
            "router": router,
            "w_gate_up": normal(k[7], n, held, d, 2 * f),
            "w_down": normal(k[8], n, held, f, d, std=into_stream),
        },
    }


def mask_row(key, router, kw):
    """MASK's embedding row: N(0, 1) like every row, and the first of
    ``MASK_CANDIDATES`` such draws whose picks, in EVERY layer, land on the experts held
    here as often as a chip's share of them is (``top_k * experts_held / experts``,
    rounded: one of eight at 16 of 128), by ``MASK_MARGIN``: the held experts it picks
    beat the best expert elsewhere it leaves out by that much, and the experts elsewhere
    it picks beat the best held one it leaves out, so that the little a masked
    position's context adds to its state does not move the count.  Why: at N(0, 1) rows
    a token's own embedding dominates the stream (which keeps the other tokens' routing
    spread), so every masked position, a quarter of a training step's stream, picks the
    SAME experts in every layer, 8 of 128; how many of those this chip's 16 hold would
    be the seed's luck (mean 1, sd 0.91 a layer, the same in every step of a round), the
    rows the held experts get would follow the seed by 9% and a round's time by 1-2.5%
    (PERF.md section 6, PR 49).  The deployment's mean, one pick of MASK's eight a chip,
    is what a cut chip should see.  A row's direction is what a norm hands the router
    (norm weights start at 1)."""
    top_k, first, held = kw["top_k"], kw["first_expert"], kw["experts_held"]
    share = round(top_k * held / kw["experts"])
    rows = jax.random.normal(key, (MASK_CANDIDATES, kw["width"]), jnp.float32)
    unit = rows * lax.rsqrt(jnp.mean(rows * rows, axis=-1, keepdims=True))
    logits = jnp.einsum("cd,lde->cle", unit, router, precision=HIGHEST)
    here = (jnp.arange(kw["experts"]) >= first) & (jnp.arange(kw["experts"]) < first + held)
    ranked = lambda keep, n: lax.top_k(jnp.where(keep, logits, -jnp.inf), n + 1)[0]
    of_here, elsewhere = ranked(here, share), ranked(~here, top_k - share)
    # [..., -2] is the weakest expert of a side that is picked, [..., -1] its best left out.
    fits = ((of_here[..., -2] > elsewhere[..., -1] + MASK_MARGIN)
            & (elsewhere[..., -2] > of_here[..., -1] + MASK_MARGIN))
    return rows[jnp.argmax(fits.all(axis=-1))]  # (the first row if none fits)


def mask_id(kw):
    """The token a noised position reads: the last id of the vocabulary held."""
    return kw["vocab"] - 1


def _rms_norm(weight, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, pos, theta):
    """``x`` [B, S, heads, hd]: the pair (``i``, ``i + hd/2``) at stream position ``j``
    turned by ``pos[j] * theta ** (-2 i / hd)``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [S, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def visible(stream_len, half, block):
    """``M`` as a boolean ``[S, S]``, queries down and keys along: stream position ``j`` is
    clean while ``j < half`` and noised from there on, and its block is ``(j mod half) //
    block``."""
    at = jnp.arange(stream_len)
    is_noised, of = at >= half, (at % half) // block
    qn, kn, qb, kb = is_noised[:, None], is_noised[None, :], of[:, None], of[None, :]
    both_clean = ~qn & ~kn & (kb <= qb)
    noised_reads_clean = qn & ~kn & (kb < qb)
    both_noised = qn & kn & (kb == qb)
    return both_clean | noised_reads_clean | both_noised  # a clean query reads no noised key


def _attention(p, u, pos, seen, kw, q):
    n, s, _ = u.shape
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    qh = (q(u) @ q(p["wq"])).reshape(n, s, hq, hd)
    kh = (q(u) @ q(p["wk"])).reshape(n, s, hkv, hd)
    vh = (q(u) @ q(p["wv"])).reshape(n, s, hkv, hd)
    qh, kh = _rms_norm(p["norm_q"], qh, kw["eps"]), _rms_norm(p["norm_k"], kh, kw["eps"])
    qh, kh = _rotate(qh, pos, kw["rope_theta"]), _rotate(kh, pos, kw["rope_theta"])
    band = min(QUERY_BAND, s)

    @jax.checkpoint
    def one_band(args):
        q_band, seen_band = args  # [B, band, H_kv, group, hd], [band, S]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q(q_band), q(kh)) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(seen_band[None, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", q(att), q(vh))

    bands = jnp.moveaxis(qh.reshape(n, s // band, band, hkv, hq // hkv, hd), 1, 0)
    out = lax.map(one_band, (bands, seen.reshape(s // band, band, s)))
    return q(jnp.moveaxis(out, 0, 1).reshape(n, s, hq * hd)) @ q(p["wo"])


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


def attention_block(p, x, pos, seen, kw, q):
    """``x' = x + a W_o``: what every chip that shares the layer computes alike."""
    return x + _attention(p, _rms_norm(p["norm_in"], x, kw["eps"]), pos, seen, kw, q)


def layer(p, x, pos, seen, kw, q):
    x = attention_block(p, x, pos, seen, kw, q)
    h = _rms_norm(p["norm_post"], x, kw["eps"])
    gate = gates(p["router"], h, kw)
    return x + routed_experts(p, h, gate, kw, q, kw["first_expert"], kw["experts_held"])


def hidden_states(params, stream, half, kw, q=lambda t: t):
    """``[N, S, width]`` after the last layer, before the final norm, of a stream of one
    half (``S = half``) or of a clean half and its noised copy (``S = 2 half``)."""
    s = stream.shape[1]
    x = params["embed"][stream]
    pos = jnp.tile(jnp.arange(half, dtype=jnp.float32), s // half)
    seen = visible(s, half, kw["block"])
    one = jax.checkpoint(lambda x, p: layer(p, x, pos, seen, kw, q))  # each layer rematerialized
    return lax.scan(lambda x, p: (one(x, p), None), x, params["layers"])[0]


def noised(tokens, key, kw):
    """``(x_t, m, t at every position)`` of a batch of sequences ``[N, L]``."""
    n, length = tokens.shape
    block = kw["block"]
    key_t, key_m = jax.random.split(key)
    t = NOISE_FLOOR + (1.0 - NOISE_FLOOR) * jax.random.uniform(
        key_t, (n, length // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    m = jax.random.uniform(key_m, (n, length), jnp.float32) < t
    return jnp.where(m, mask_id(kw), tokens), m, t


def _head(params, x, kw, q):
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))


def sample_nll(params, xb, yb, key, kw, q=lambda t: t):
    """``[N]`` float32: each sequence's weighted masked denoising loss.  ``yb`` is not
    read: the targets are the sequence's own tokens."""
    del yb
    tokens = xb.astype(jnp.int32)
    n, length = tokens.shape
    x_t, m, t = noised(tokens, key, kw)
    hidden = hidden_states(params, jnp.concatenate([tokens, x_t], axis=1), length, kw, q)
    chunk = min(HEAD_CHUNK, length)

    @jax.checkpoint
    def one_chunk(args):
        h, target, weight = args  # [N, chunk, d], [N, chunk], [N, chunk]
        logp = _head(params, h, kw, q)
        return -(jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0] * weight).sum(axis=1)

    chunked = lambda a: jnp.moveaxis(a.reshape(n, length // chunk, chunk, *a.shape[2:]), 1, 0)
    weight = jnp.where(m, 1.0 / t, 0.0)
    parts = lax.map(one_chunk, (chunked(hidden[:, length:]), chunked(tokens), chunked(weight)))
    return (parts.sum(axis=0) / length).astype(jnp.float32)


def log_probs(params, tokens, key, kw, q=lambda t: t):
    """``[N, vocab]``: one denoising step of generating the last block, read at the last
    position.  ``key`` is unused: nothing is drawn."""
    del key
    tokens = tokens.astype(jnp.int32)
    length = tokens.shape[1]
    stream = tokens.at[:, length - kw["block"]:].set(mask_id(kw))
    return _head(params, hidden_states(params, stream, length, kw, q)[:, -1, :], kw, q)
