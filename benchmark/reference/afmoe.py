"""Plain reference of the decoder the zoo calls ``gated_moe_lm``: an ``afmoe`` model's
layers: gated grouped-query attention, sliding with rotary positions or full with none,
every branch normed going in and coming out; a gated MLP in the leading dense layers,
sigmoid-routed experts with a selection bias beside a shared expert in the rest.

A layer, ``x`` [T, d] (no bias anywhere; every norm an RMSNorm with its own weight)::

    x0      = embed[tokens] * sqrt(d)                                   mup_enabled
    u       = Norm_in(x)
    q, k, v = u W_q [T,H,hd], u W_k [T,H_kv,hd], u W_v [T,H_kv,hd]
    g       = u W_g [T,H,hd]                 the output gate, one value a head dimension
    q, k    = Norm_q(q), Norm_k(k)           per head, over the hd dimensions, BEFORE any rotation
    q, k    = rotate(q), rotate(k)           ONLY where sliding_layout[l] (theta, all hd
                                             dimensions, rotate-half pairs)
    a_t     = softmax_s(q_t . k_s / sqrt(hd)) v_s   over s <= t, and t - window < s where
                                             sliding_layout[l]; query head h reads head h // (H/H_kv)
    x'      = x + Norm_post_attn((a * sigmoid(g)) W_o)
    h       = Norm_pre_mlp(x')
    dense layer:   m = W_down (silu(W_gate h) * (W_up h))
    expert layer:  p      = sigmoid(h W_r)                  float32
                   picks  = top_k(p + b)                    b: the selection bias
                   w      = scale * p[picks] / (sum p[picks] + 1e-20)      WITHOUT b
                   m      = S_down (silu(S_gate h) * (S_up h))
                            + sum over e in picks that are HELD of w_e W_down,e (silu(W_gate,e h) * (W_up,e h))
    out     = x' + Norm_post_mlp(m)

then a final RMSNorm and an untied head; log-probabilities of the next token at the LAST
position only, which is where the repo's token-stream pipeline puts the loss.  Departures
from the published description (the configuration's file, ``assumed``): the bias's
balancing update (``load_balance_coeff``) is not part of this function, so the loss is
the cross-entropy alone and ``b`` gets no gradient because ``top_k``'s indices carry
none; ``n_group`` = ``topk_group`` = 1, so the group limit is the identity; only experts
``first_expert .. first_expert + experts_held`` live here, and what the absent ones would
add is left out BEFORE the branch's out-norm (where a deployment's exchange would sum the
shares); the vocabulary is the slice held.  Written for reading, not speed:

* the window and the causal order are one dense boolean mask over whole score rows (the
  program never visits key blocks behind the window);
* the rotation is written out on the two halves of a head;
* every held expert's product is computed densely over all tokens and weighted by a
  one-hot product of the router's picks, zero where the expert was not picked (the
  program lays the picks out by expert);
* the gate is a product and a sigmoid of its own beside ``q``, ``k``, ``v``.

The leaves are the program's, so ``W_gate | W_up`` come as one ``[d, 2f]`` matrix and are
split here.  Layers of a kind are stacked on a leading axis; the forward pass walks the
layout and takes each layer's slice.  Each layer is rematerialized, attention goes by
bands of ``QUERY_BAND`` queries, each rematerialized (so that no ``[heads, T, T]`` array
exists: at 8192 positions of 32 heads it would be 8.6 GB), the experts one at a time
under ``lax.scan``, and every gated MLP summed over column chunks of an expert's width,
each chunk rematerialized (the dense layer's ``[8192, 6144]`` gate, up and hidden arrays,
forward and backward, would be 1.2 GB): a float32 round at the published widths then fits
one chip beside the copies of the parameters the federated reference holds.  Imports
nothing of the program.

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
#: Hidden units of a gated MLP computed at once: a routed expert's width.
MLP_CHUNK = 1024
HIGHEST = lax.Precision.HIGHEST


def init_params(key, kw):
    """Weights from the seed: N(0, 1) embeddings (the forward pass scales them by
    ``sqrt(d)``: a token's own part enters the stream at 45, every branch adds a root mean
    square of 1 through its out-norm, so the token's part outweighs the ten branches to the
    last layer and the picks stay spread over the experts, as ``reference/smallthinker.py``
    says); N(0, 0.02) head and every matrix, the projections into the stream among them
    (the out-norm takes their scale away); norms 1; the selection bias N(0, 0.005) as
    ``reference/deepseek_v3.py`` draws it (configuration file, ``assumed.initialisation``)."""
    d, vocab = kw["width"], kw["vocab"]
    hd, qd, kvd = kw["head_dim"], kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    n_d = kw["dense_layers"]
    n_e = len(kw["sliding_layout"]) - n_d
    normal = lambda kk, *shape, std=0.02: std * jax.random.normal(kk, shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def attention(kk, n):
        k = jax.random.split(kk, 5)
        return {
            "norm_in": ones(n, d),
            "wq": normal(k[0], n, d, qd),
            "wk": normal(k[1], n, d, kvd),
            "wv": normal(k[2], n, d, kvd),
            "wg": normal(k[3], n, d, qd),
            "norm_q": ones(n, hd),
            "norm_k": ones(n, hd),
            "wo": normal(k[4], n, qd, d),
            "norm_post_attn": ones(n, d),
            "norm_pre_mlp": ones(n, d),
            "norm_post_mlp": ones(n, d),
        }

    k = jax.random.split(key, 12)
    return {
        "embed": normal(k[0], vocab, d, std=1.0),
        "head": normal(k[1], d, vocab),
        "norm_f": ones(d),
        "dense": {
            **attention(k[2], n_d),
            "w_gate_up": normal(k[3], n_d, d, 2 * kw["dense_width"]),
            "w_down": normal(k[4], n_d, kw["dense_width"], d),
        },
        "moe": {
            **attention(k[5], n_e),
            "router": normal(k[6], n_e, d, kw["experts"]),
            "router_bias": normal(k[7], n_e, kw["experts"], std=0.005),
            "shared_gate_up": normal(k[8], n_e, d, 2 * kw["shared_width"]),
            "shared_down": normal(k[9], n_e, kw["shared_width"], d),
            "w_gate_up": normal(k[10], n_e, kw["experts_held"], d, 2 * kw["expert_width"]),
            "w_down": normal(k[11], n_e, kw["experts_held"], kw["expert_width"], d),
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


def attended(p, u, kw, q, sliding):
    """``a`` [B, T, H * hd]: what attention gives for the normed ``u``, before the gate."""
    n, t, _ = u.shape
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    qh = _rms_norm(p["norm_q"], (q(u) @ q(p["wq"])).reshape(n, t, hq, hd), kw["eps"])
    kh = _rms_norm(p["norm_k"], (q(u) @ q(p["wk"])).reshape(n, t, hkv, hd), kw["eps"])
    vh = (q(u) @ q(p["wv"])).reshape(n, t, hkv, hd)
    if sliding:  # a full layer has no positional term
        qh, kh = _rotate(qh, kw["rope_theta"]), _rotate(kh, kw["rope_theta"])
    band = min(QUERY_BAND, t)

    @jax.checkpoint
    def one_band(args):
        q_band, first = args  # [B, band, H_kv, group, hd], the band's first position
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q(q_band), q(kh)) / math.sqrt(hd)
        at, key = first + jnp.arange(band)[:, None], jnp.arange(t)[None, :]
        seen = key <= at
        if sliding:
            seen = seen & (at - key < kw["window"])
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", q(att), q(vh))

    bands = jnp.moveaxis(qh.reshape(n, t // band, band, hkv, hq // hkv, hd), 1, 0)
    out = lax.map(one_band, (bands, jnp.arange(t // band) * band))
    return jnp.moveaxis(out, 0, 1).reshape(n, t, hq * hd)


def attention_branch(p, u, kw, q, sliding):
    """``(a * sigmoid(u W_g)) W_o``: the attention branch from its normed input to what its
    out-norm reads."""
    gate = jax.nn.sigmoid(q(u) @ q(p["wg"]))
    return q(attended(p, u, kw, q, sliding) * gate) @ q(p["wo"])


def _gated_mlp(w_gate_up, w_down, h, q):
    """``W_down (silu(W_gate h) * (W_up h))``, summed over column chunks of ``MLP_CHUNK``
    hidden units, each rematerialized: the same sum, and the dense layer's gate, up and
    hidden arrays never exist whole."""
    f, d = w_down.shape
    chunk = MLP_CHUNK if f % MLP_CHUNK == 0 else f
    columns = lambda w: jnp.moveaxis(w.reshape(d, f // chunk, chunk), 1, 0)

    def one_chunk(w_gate, w_up, w_out):
        return q(jax.nn.silu(q(h) @ q(w_gate)) * (q(h) @ q(w_up))) @ q(w_out)

    chunks = (columns(w_gate_up[:, :f]), columns(w_gate_up[:, f:]), w_down.reshape(f // chunk, chunk, d))
    return lax.scan(jax.checkpoint(lambda out, weights: (out + one_chunk(*weights), None)),
                    jnp.zeros_like(h), chunks)[0]


def gates(router, bias, h, kw):
    """``[..., experts]``: the weight each expert's output gets, zero where not picked:
    float32 sigmoid scores (``score_func``), the ``top_k`` largest of score PLUS bias, the
    picked SCORES normalised (``route_norm``) and scaled (``route_scale``)."""
    scores = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router, precision=HIGHEST))
    _, picks = lax.top_k(scores + bias, kw["top_k"])
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weight = kw["routed_scale"] * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return (jax.nn.one_hot(picks, kw["experts"], dtype=jnp.float32) * weight[..., None]).sum(axis=-2)


def routed_experts(p, h, gate, kw, q, first, held):
    """The part of the layer's routed sum that experts ``first .. first + held`` give for
    ``h``, under ``gate`` [..., experts]; ``p["w_gate_up"]`` / ``p["w_down"]`` hold
    exactly those."""

    def one_expert(out, expert):
        w_gate_up, w_down, weight = expert
        return out + weight[..., None] * _gated_mlp(w_gate_up, w_down, h, q), None

    held_gate = jnp.moveaxis(gate[..., first:first + held], -1, 0)
    out, _ = lax.scan(one_expert, jnp.zeros_like(h), (p["w_gate_up"], p["w_down"], held_gate))
    return out


def shared_expert(p, h, q):
    return _gated_mlp(p["shared_gate_up"], p["shared_down"], h, q)


def feed_forward(p, h, kw, q, dense):
    """``m``: what the second branch makes of the normed ``h``, before its out-norm: the
    dense MLP, or the shared expert plus the held experts' part of the routed sum."""
    if dense:
        return _gated_mlp(p["w_gate_up"], p["w_down"], h, q)
    gate = gates(p["router"], p["router_bias"], h, kw)
    return shared_expert(p, h, q) + routed_experts(p, h, gate, kw, q, kw["first_expert"], kw["experts_held"])


def layer(p, x, kw, q, dense, sliding):
    eps = kw["eps"]
    branch = attention_branch(p, _rms_norm(p["norm_in"], x, eps), kw, q, sliding)
    x = x + _rms_norm(p["norm_post_attn"], branch, eps)
    m = feed_forward(p, _rms_norm(p["norm_pre_mlp"], x, eps), kw, q, dense)
    return x + _rms_norm(p["norm_post_mlp"], m, eps)


def hidden_states(params, tokens, kw, q=lambda t: t):
    """``[N, T, width]`` after the last layer, before the final norm."""
    x = params["embed"][tokens] * math.sqrt(kw["width"])
    for index, sliding in enumerate(kw["sliding_layout"]):
        dense = index < kw["dense_layers"]
        kind, at = ("dense", index) if dense else ("moe", index - kw["dense_layers"])
        p = jax.tree.map(lambda leaf: leaf[at], params[kind])
        x = jax.checkpoint(
            lambda p, x, dense=dense, sliding=bool(sliding): layer(p, x, kw, q, dense, sliding))(p, x)
    return x


def log_probs(params, tokens, key, kw, q=lambda t: t):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    x = hidden_states(params, tokens, kw, q)[:, -1, :]
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))
