"""Plain reference of the decoder the zoo calls ``latent_moe_lm``: a ``deepseek_v3``
model's layers — latent attention (MLA) in every layer, a gated MLP in the leading dense
layers, sigmoid-routed experts with a selection bias beside shared experts in the rest.

A layer, ``x`` [T, d] (pre-norm residual stack, no bias on any projection)::

    u        = RMSNorm_in(x)
    q        = u W_q                     [T,H,nope+rope] = q_nope | q_pe
    c | k_pe = u W_kv_a                  [T,rank] | [T,rope]    ONE rotary key for all heads
    kv       = RMSNorm_kv(c) W_kv_b      [T,H,nope+value] = k_nope | v
    q_pe, k_pe = rotate(q_pe, k_pe; theta, the rope dimensions, float32 angles)
    s_ths    = (q_nope_th . k_nope_sh + q_pe_th . k_pe_s) / sqrt(nope + rope)      s <= t
    a_th     = sum_s softmax_s(s_ths) v_sh
    x'       = x + a W_o
    h        = RMSNorm_post(x')
    dense layer:   out = x' + W_down (silu(W_gate h) * (W_up h))
    expert layer:  p      = sigmoid(h W_r)                  float32
                   picks  = top_k(p + b)                    b: the selection bias
                   g      = scale * p[picks] / (sum p[picks] + 1e-20)      WITHOUT b
                   routed = sum over e in picks that are HELD of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))
                   shared = S_down (silu(S_gate h) * (S_up h))
                   out    = x' + routed + shared

then a final RMSNorm and an untied head; log-probabilities of the next token at the LAST
position only, which is where the repo's token-stream pipeline puts the loss.  The
bias's balancing update and the sequence-wise auxiliary loss are not part of this
function (the configuration's file, ``assumed``): the loss is the cross-entropy alone,
and ``b`` gets no gradient because ``top_k``'s indices carry none.  ``n_group`` =
``topk_group`` = 1: the group limit is the identity.  Written for reading, not speed:

* the latent, its norm and the keys are explicit: every head's key is the
  concatenation of its own ``k_nope`` and a copy of the one rotated ``k_pe``;
* the causal order is one dense boolean mask over whole score rows;
* the rotation is written out on the two halves of the rope dimensions (the
  rotate-half pairing; the published code pairs stored columns ``2i, 2i+1``, the same
  function under a fixed permutation of ``W_q``'s and ``W_kv_a``'s rotary columns);
* every held expert's product is computed densely over all tokens and weighted by a
  one-hot product of the router's picks, zero where the expert was not picked;
* the router scores all ``experts``; only experts ``first_expert .. first_expert +
  experts_held`` live here, and what the absent ones would add is left out.

The leaves are the program's, so ``W_gate | W_up`` come as one ``[d, 2f]`` matrix and are
split here.  Layers of a kind are stacked on a leading axis and run under ``lax.scan``
(a Python loop over ``leaf[index]`` costs the float32 round 1.2 GiB more: each layer's
gradient is padded to the stacked leaf before the sum).  Each layer is rematerialized,
attention goes by bands of ``QUERY_BAND`` queries, each rematerialized
(so that no ``[heads, T, T]`` array exists: at 8192 positions of 16 heads it would be 4.3
GB), the experts one at a time under ``lax.scan``, and every gated MLP summed over
column chunks of an expert's width, each chunk rematerialized (the dense layer's ``[8192,
11264]`` gate, up and hidden arrays, forward and backward, would be 2 GB): a float32
round at the published widths then fits one chip beside the copies of the parameters
the federated reference holds.  Imports nothing of the program.

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
#: Hidden units of a gated MLP computed at once (a routed expert's width): the dense
#: layer's ``[8192, 11264]`` gate, up and hidden arrays, forward and backward in float32,
#: would be 2 GB beside five copies of the parameters.
MLP_CHUNK = 1408
HIGHEST = lax.Precision.HIGHEST


def init_params(key, kw):
    """Weights from the seed: N(0, 1) embeddings; N(0, 0.02) head and matrices, every
    projection into the residual stream (``wo``, the dense, shared and routed ``w_down``)
    N(0, 0.02 / sqrt(2 layers)); norms 1 (``reference/smallthinker.py`` says why the
    embeddings dominate: the picks stay spread over the experts).  The selection bias N(0,
    0.005): a bias of zero would hide a program that leaves it out, one of 0.02 unbalances
    the held experts' loads by itself (configuration file, ``assumed.selection_bias``)."""
    d, vocab, h = kw["width"], kw["vocab"], kw["heads"]
    rank, nope, rope, value = kw["latent_rank"], kw["nope_dim"], kw["rope_dim"], kw["value_dim"]
    n_d, n_e = kw["dense_layers"], kw["expert_layers"]
    into_stream = 0.02 / math.sqrt(2 * (n_d + n_e))
    normal = lambda kk, *shape, std=0.02: std * jax.random.normal(kk, shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def attention(kk, n):
        k = jax.random.split(kk, 4)
        return {
            "norm_in": ones(n, d),
            "wq": normal(k[0], n, d, h * (nope + rope)),
            "wkv_a": normal(k[1], n, d, rank + rope),
            "norm_kv": ones(n, rank),
            "wkv_b": normal(k[2], n, rank, h * (nope + value)),
            "wo": normal(k[3], n, h * value, d, std=into_stream),
            "norm_post": ones(n, d),
        }

    k = jax.random.split(key, 12)
    return {
        "embed": normal(k[0], vocab, d, std=1.0),
        "head": normal(k[1], d, vocab),
        "norm_f": ones(d),
        "dense": {
            **attention(k[2], n_d),
            "w_gate_up": normal(k[3], n_d, d, 2 * kw["dense_width"]),
            "w_down": normal(k[4], n_d, kw["dense_width"], d, std=into_stream),
        },
        "moe": {
            **attention(k[5], n_e),
            "router": normal(k[6], n_e, d, kw["experts"]),
            "router_bias": normal(k[7], n_e, kw["experts"], std=0.005),
            "shared_gate_up": normal(k[8], n_e, d, 2 * kw["shared_width"]),
            "shared_down": normal(k[9], n_e, kw["shared_width"], d, std=into_stream),
            "w_gate_up": normal(k[10], n_e, kw["experts_held"], d, 2 * kw["expert_width"]),
            "w_down": normal(k[11], n_e, kw["experts_held"], kw["expert_width"], d, std=into_stream),
        },
    }


def _rms_norm(weight, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, theta):
    """``x`` [B, T, heads, r]: the pair (``i``, ``i + r/2``) at position ``t`` turned by
    the angle ``t * theta ** (-2 i / r)``."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def keys_and_values(p, u, kw, q):
    """``(keys [B,T,H,nope+rope], values [B,T,H,value])`` from the normed ``u``: the
    latent, its norm, the up-projection, and the one rotated ``k_pe`` copied to every head."""
    n, t, _ = u.shape
    h, rank, nope, rope = kw["heads"], kw["latent_rank"], kw["nope_dim"], kw["rope_dim"]
    down = q(u) @ q(p["wkv_a"])
    latent, k_pe = down[..., :rank], down[..., rank:]
    kv = (q(_rms_norm(p["norm_kv"], latent, kw["eps"])) @ q(p["wkv_b"])).reshape(n, t, h, -1)
    k_pe = _rotate(k_pe[:, :, None, :], kw["rope_theta"])
    keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (n, t, h, rope))], axis=-1)
    return keys, kv[..., nope:]


def _attention(p, u, kw, q):
    n, t, _ = u.shape
    h, nope, rope = kw["heads"], kw["nope_dim"], kw["rope_dim"]
    qh = (q(u) @ q(p["wq"])).reshape(n, t, h, nope + rope)
    qh = jnp.concatenate([qh[..., :nope], _rotate(qh[..., nope:], kw["rope_theta"])], axis=-1)
    keys, values = keys_and_values(p, u, kw, q)
    band = min(QUERY_BAND, t)

    @jax.checkpoint
    def one_band(args):
        q_band, first = args  # [B, band, H, nope+rope], the band's first position
        scores = jnp.einsum("bqhd,bshd->bhqs", q(q_band), q(keys)) / math.sqrt(nope + rope)
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(band)[:, None]
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", q(att), q(values))

    bands = jnp.moveaxis(qh.reshape(n, t // band, band, h, nope + rope), 1, 0)
    out = lax.map(one_band, (bands, jnp.arange(t // band) * band))
    return q(jnp.moveaxis(out, 0, 1).reshape(n, t, h * kw["value_dim"])) @ q(p["wo"])


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
    return lax.scan(jax.checkpoint(lambda out, weights: (out + one_chunk(*weights), None)), jnp.zeros_like(h), chunks)[0]


def gates(router, bias, h, kw):
    """``[..., experts]``: the weight each expert's output gets, zero where not picked:
    float32 sigmoid scores, the ``top_k`` largest of score PLUS bias, the picked SCORES
    normalised (``norm_topk_prob``) and scaled (``routed_scaling_factor``)."""
    scores = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router, precision=HIGHEST))
    _, picks = lax.top_k(scores + bias, kw["top_k"])
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weight = kw["routed_scale"] * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return (jax.nn.one_hot(picks, kw["experts"], dtype=jnp.float32) * weight[..., None]).sum(axis=-2)


def routed_experts(p, h, gate, kw, q, first, held):
    """The part of the layer's routed output that experts ``first .. first + held`` give
    for ``h``, under ``gate`` [..., experts]; ``p["w_gate_up"]`` / ``p["w_down"]`` hold
    exactly those."""

    def one_expert(out, expert):
        w_gate_up, w_down, weight = expert
        return out + weight[..., None] * _gated_mlp(w_gate_up, w_down, h, q), None

    held_gate = jnp.moveaxis(gate[..., first:first + held], -1, 0)
    out, _ = lax.scan(one_expert, jnp.zeros_like(h), (p["w_gate_up"], p["w_down"], held_gate))
    return out


def feed_forward(p, x, kw, q, dense):
    """What the layer adds to ``x`` after attention: the dense MLP, or the held experts'
    routed part plus the shared experts."""
    h = _rms_norm(p["norm_post"], x, kw["eps"])
    if dense:
        return _gated_mlp(p["w_gate_up"], p["w_down"], h, q)
    gate = gates(p["router"], p["router_bias"], h, kw)
    routed = routed_experts(p, h, gate, kw, q, kw["first_expert"], kw["experts_held"])
    return routed + _gated_mlp(p["shared_gate_up"], p["shared_down"], h, q)


def layer(p, x, kw, q, dense):
    x = x + _attention(p, _rms_norm(p["norm_in"], x, kw["eps"]), kw, q)
    return x + feed_forward(p, x, kw, q, dense)


def hidden_states(params, tokens, kw, q=lambda t: t):
    """``[N, T, width]`` after the last layer, before the final norm."""
    x = params["embed"][tokens]
    for kind, count in (("dense", kw["dense_layers"]), ("moe", kw["expert_layers"])):
        if count:  # the layers of a kind in turn, each rematerialized
            one = jax.checkpoint(lambda x, p, dense=kind == "dense": layer(p, x, kw, q, dense))
            x, _ = lax.scan(lambda x, p: (one(x, p), None), x, params[kind])
    return x


def log_probs(params, tokens, key, kw, q=lambda t: t):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    x = hidden_states(params, tokens, kw, q)[:, -1, :]
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))
