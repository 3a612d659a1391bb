"""Plain reference of the decoder the zoo calls ``moe_decoder_lm``: a ``smallthinker``
model's layers, each grouped-query attention followed by sparse gated experts, the
attention's kind given a layer by ``rope_layout`` and ``window_layout``.

A layer, ``x`` [T, d] (pre-norm residual stack, no bias)::

    u     = RMSNorm_in(x)
    r     = u W_r                       float32; the router reads the layer's normed
    picks = top_k(r); g = softmax(r[picks])       input, BEFORE attention
    q, k, v = u W_q, u W_k, u W_v       [T,H,hd], [T,H_kv,hd] x 2; rotated (theta, all hd
                                        dimensions, rotate-half pairs) where rope_layout[l]
    a_t   = softmax_s(q_t . k_s / sqrt(hd)) v_s  over s <= t, and t - window < s where
                                        window_layout[l]; query head h reads head h // (H/H_kv)
    x'    = x + a W_o
    h     = RMSNorm_post(x')
    out   = x' + sum over e in picks that are HELD of g_e W_down,e (relu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head; log-probabilities of the next token at the LAST
position only, which is where the repo's token-stream pipeline puts the loss.  Written
for reading, not speed:

* the window and the causal order are one dense boolean mask over whole score rows (the
  program never visits key blocks behind the window);
* the rotation is written out on the two halves of a head;
* every held expert's product is computed densely over all tokens and weighted by a
  one-hot product of the router's picks, zero where the expert was not picked (the
  program sorts by expert);
* the router scores all ``experts``; only experts ``first_expert .. first_expert +
  experts_held`` live here, and what the absent ones would add is left out (the guide's
  expert-parallel cut).

The leaves are the program's, so ``W_gate | W_up`` of an expert come as one ``[d, 2f]``
matrix and are split here.  Layers are stacked on a leading axis.  Each layer is
rematerialized, attention goes by bands of ``QUERY_BAND`` queries (so that no ``[heads,
T, T]`` array exists: at 8192 positions of 28 heads it would be 7.5 GB) and the experts
one at a time under ``lax.scan``, each rematerialized too, so that a float32 round at
the published widths fits one chip.  Imports nothing of the program.

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
HIGHEST = lax.Precision.HIGHEST


def init_params(key, kw):
    """Weights from the seed: N(0, 1) embeddings; N(0, 0.02) head and matrices, the two
    projections into the residual stream (``wo``, ``w_down``) N(0, 0.02 / sqrt(2 layers));
    norms 1.  With every leaf at 0.02 the residual stream of random tokens is soon one
    common vector (uniform attention averages the tokens' own parts away and keeps what
    they share, sixty-fold a layer) and every token picks the same experts: embeddings
    that dominate the stream, as a trained model's do in its first layers, keep the picks
    spread as a balanced router's are (configuration file, ``assumed.initialisation``)."""
    d, vocab, n = kw["width"], kw["vocab"], len(kw["rope_layout"])
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    held, f = kw["experts_held"], kw["expert_width"]
    k = jax.random.split(key, 9)
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
            "wo": normal(k[5], n, hq * hd, d, std=into_stream),
            "norm_post": ones(n, d),
            "router": normal(k[6], n, d, kw["experts"]),
            "w_gate_up": normal(k[7], n, held, d, 2 * f),
            "w_down": normal(k[8], n, held, f, d, std=into_stream),
        },
    }


def _rms_norm(weight, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, theta):
    """``x`` [B, T, heads, hd]: the pair (``i``, ``i + hd/2``) at position ``t`` turned
    by the angle ``t * theta ** (-2 i / hd)``."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def _attention(p, u, kw, q, rope, window):
    n, t, _ = u.shape
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    qh = (q(u) @ q(p["wq"])).reshape(n, t, hq, hd)
    kh = (q(u) @ q(p["wk"])).reshape(n, t, hkv, hd)
    vh = (q(u) @ q(p["wv"])).reshape(n, t, hkv, hd)
    if rope:
        qh, kh = _rotate(qh, kw["rope_theta"]), _rotate(kh, kw["rope_theta"])
    band = min(QUERY_BAND, t)

    @jax.checkpoint
    def one_band(args):
        q_band, first = args  # [B, band, H_kv, group, hd], the band's first position
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q(q_band), q(kh)) / math.sqrt(hd)
        at, key = first + jnp.arange(band)[:, None], jnp.arange(t)[None, :]
        seen = key <= at
        if window is not None:
            seen = seen & (at - key < window)
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", q(att), q(vh))

    bands = jnp.moveaxis(qh.reshape(n, t // band, band, hkv, hq // hkv, hd), 1, 0)
    out = lax.map(one_band, (bands, jnp.arange(t // band) * band))
    return q(jnp.moveaxis(out, 0, 1).reshape(n, t, hq * hd)) @ q(p["wo"])


def gates(router, u, kw):
    """``[..., experts]``: the weight each expert's output gets, zero where not picked:
    float32 logits, the ``top_k`` largest, softmax over those
    (``moe_primary_router_apply_softmax``; ``norm_topk_prob`` then changes nothing)."""
    logits = jnp.matmul(u.astype(jnp.float32), router, precision=HIGHEST)
    top, picks = lax.top_k(logits, kw["top_k"])
    weight = jax.nn.softmax(top, axis=-1)
    return (jax.nn.one_hot(picks, kw["experts"], dtype=jnp.float32) * weight[..., None]).sum(axis=-2)


def routed_experts(p, h, gate, kw, q, first, held):
    """The part of the layer's feed-forward that experts ``first .. first + held`` give
    for ``h``, under ``gate`` [..., experts]; ``p["w_gate_up"]`` / ``p["w_down"]`` hold
    exactly those."""
    f = kw["expert_width"]

    @jax.checkpoint
    def one_expert(out, expert):
        w_gate_up, w_down, weight = expert
        w_gate, w_up = w_gate_up[:, :f], w_gate_up[:, f:]
        hidden = jax.nn.relu(q(h) @ q(w_gate)) * (q(h) @ q(w_up))
        return out + weight[..., None] * (q(hidden) @ q(w_down)), None

    held_gate = jnp.moveaxis(gate[..., first:first + held], -1, 0)
    out, _ = lax.scan(one_expert, jnp.zeros_like(h), (p["w_gate_up"], p["w_down"], held_gate))
    return out


def layer(p, x, kw, q, rope, window):
    u = _rms_norm(p["norm_in"], x, kw["eps"])
    gate = gates(p["router"], u, kw)  # before attention, from the layer's normed input
    x = x + _attention(p, u, kw, q, rope, window)
    h = _rms_norm(p["norm_post"], x, kw["eps"])
    return x + routed_experts(p, h, gate, kw, q, kw["first_expert"], kw["experts_held"])


def hidden_states(params, tokens, kw, q=lambda t: t):
    """``[N, T, width]`` after the last layer, before the final norm."""
    x = params["embed"][tokens]
    for index, (rope, windowed) in enumerate(zip(kw["rope_layout"], kw["window_layout"])):
        p = jax.tree.map(lambda leaf: leaf[index], params["layers"])
        window = kw["window"] if windowed else None
        x = jax.checkpoint(
            lambda p, x, rope=bool(rope), window=window: layer(p, x, kw, q, rope, window))(p, x)
    return x


def log_probs(params, tokens, key, kw, q=lambda t: t):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    x = hidden_states(params, tokens, kw, q)[:, -1, :]
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))
