"""Decoder whose attention is gated and whose branches are normed on both sides, sliding
layers with rotary positions beside full layers with none, over sigmoid-routed experts
with a shared one, its first layers dense (the ``afmoe`` layout: ``layer_types``,
``sliding_window``, ``num_dense_layers``, ``score_func`` ``sigmoid``, ``route_norm``,
``route_scale``, ``num_shared_experts``, ``mup_enabled``).

The zoo's first decoder ASSEMBLED from shared parts: ``decoder.rotate`` and the
window of ``ops.attention``'s kernels, ``experts.sigmoid_route`` with its selection bias,
``experts.held_experts`` with :data:`SWIGLU`, ``decoder.gated_mlp`` for the dense
layer and the shared expert, ``decoder.rms_norm`` (per head on ``q`` and ``k``, as
``indexed_moe`` uses it).  What it adds is the order they come in.  A layer, ``x`` [N, T,
d] (no bias anywhere; every norm an RMSNorm with a weight of its own)::

    x0      = embed[tokens] * sqrt(d)
    u       = Norm_in(x)
    q, k, v = u W_q [T,H,hd], u W_k [T,H_kv,hd], u W_v [T,H_kv,hd]
    g       = u W_g [T,H,hd]                        the output gate: one value a head dimension
    q, k    = Norm_q(q), Norm_k(k)                  per head, over the hd dimensions
    q, k    = rotate(q), rotate(k)                  in a SLIDING layer alone
    a       = causal attention(q, k, v)             keys t - window < s <= t in a sliding
                                                    layer, every s <= t in a full one
    x'      = x + Norm_post_attn((a * sigmoid(g)) W_o)
    h       = Norm_pre_mlp(x')
    dense layer:   m = W_down (silu(W_gate h) * (W_up h))
    expert layer:  p = sigmoid(h W_r); picks = top_k(p + b); w = scale p[picks] / sum p[picks]
                   m = S_down (silu(S_gate h) * (S_up h))
                       + sum over HELD picks e of w_e W_down,e (silu(W_gate,e h) * (W_up,e h))
    out     = x' + Norm_post_mlp(m)

then a final RMSNorm and an untied head.  The residual stream is never what a product
reads or writes: each branch is normed going in AND coming out (four norms a layer beside
the two per-head ones).  ``b`` is the router's selection bias: it moves the picks and not
the weights and takes no gradient (``experts.sigmoid_route``); the rule that would update
it is not built.  Like ``latent_moe_lm`` it drops into the standard federated pipeline:
``apply`` returns next-token log-probabilities at the LAST position (``[N, vocab]``); the
dense layers' leaves are stacked on a leading axis under ``params["dense"]``, the expert
layers' under ``params["moe"]``, and every layer is rematerialized (``jax.checkpoint``)
but for what carries a name (``models.experts.KEEP_NAMED_OUTPUTS``): the attention
kernels' output and log-sum-exp and an expert layer's dispatch layout.  The gate stands
between the kernels' kept output and ``W_o``, so the backward pass computes its product
and its sigmoid a second time and launches no kernel for it.

**Attention** runs block by block in ``ops.attention``'s kernels wherever the sequence
is whole blocks of at least ``MIN_SEQ`` positions, grouped heads reading their key/value
head in place and key blocks behind a window never visited, and densely below that
(tests).

**Experts**: the layer is TOLD which experts it holds (``first_expert``,
``experts_held``), routes over all of them and adds the shared expert once; dispatch and
the block loop are ``models.experts``'.  The expert layers report :data:`COUNTERS`
through ``apply.with_counters``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.models.decoder import gated_mlp, language_model, rms_norm, rotate, run_layers
from nanofed_tpu.models.experts import COUNTERS, SWIGLU, check_held, held_experts, sigmoid_route
from nanofed_tpu.nn import embed_rows
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention, engages

_F32 = jnp.float32


def init_gated_moe(rng: PRNGKey, *, vocab, width, sliding_layout, attn_heads, kv_heads,
                   head_dim, dense_layers, dense_width, experts, experts_held, expert_width,
                   shared_width, **_) -> Params:
    """N(0, 1) embeddings, N(0, 0.02) head and every matrix, norms 1, the selection bias
    N(0, 0.005) (``latent_moe.init_latent_moe`` says why).  ``apply`` scales the
    embeddings by ``sqrt(width)`` and every branch leaves its out-norm at a root mean
    square of 1, so a token's own part (45 at 2048) outweighs the branches' sum to the last
    layer and the routing stays spread over the experts, as ``moe_decoder.init_moe_decoder``
    says.  Measured at the cell's size (PR 43, PERF.md section 6): the held experts'
    fullest over their mean reads 1.10-1.20 a layer at 1, 1.06-1.24 at 0.5, 1.07-1.49 at
    0.25, 1.7-4.1 at ``1 / sqrt(width)``.  A branch's out-norm takes the scale of its last
    matrix away, so no projection into the stream is drawn smaller than the others."""
    n_d, n_e = dense_layers, len(sliding_layout) - dense_layers
    q, kv = attn_heads * head_dim, kv_heads * head_dim
    normal = lambda key, *shape, std=0.02: std * jax.random.normal(key, shape, _F32)
    ones = lambda *shape: jnp.ones(shape, _F32)

    def attention(key, n):
        k = jax.random.split(key, 5)
        return {
            "norm_in": ones(n, width),
            "wq": normal(k[0], n, width, q),
            "wk": normal(k[1], n, width, kv),
            "wv": normal(k[2], n, width, kv),
            "wg": normal(k[3], n, width, q),
            "norm_q": ones(n, head_dim),
            "norm_k": ones(n, head_dim),
            "wo": normal(k[4], n, q, width),
            "norm_post_attn": ones(n, width),
            "norm_pre_mlp": ones(n, width),
            "norm_post_mlp": ones(n, width),
        }

    k = jax.random.split(rng, 12)
    return {
        "embed": normal(k[0], vocab, width, std=1.0),
        "head": normal(k[1], width, vocab),
        "norm_f": ones(width),
        "dense": {
            **attention(k[2], n_d),
            "w_gate_up": normal(k[3], n_d, width, 2 * dense_width),
            "w_down": normal(k[4], n_d, dense_width, width),
        },
        "moe": {
            **attention(k[5], n_e),
            "router": normal(k[6], n_e, width, experts),
            "router_bias": normal(k[7], n_e, experts, std=0.005),
            "shared_gate_up": normal(k[8], n_e, width, 2 * shared_width),
            "shared_down": normal(k[9], n_e, shared_width, width),
            "w_gate_up": normal(k[10], n_e, experts_held, width, 2 * expert_width),
            "w_down": normal(k[11], n_e, experts_held, expert_width, width),
        },
    }


def gated_attention(p: Params, u: jax.Array, cfg: dict, *, sliding: bool) -> jax.Array:
    """Grouped-query causal attention over the normed ``u`` [N, T, d], gated head
    dimension by head dimension before its output projection: rotary positions under a
    window in a ``sliding`` layer, neither in a full one."""
    n, t, _ = u.shape
    hq, hkv, hd = cfg["attn_heads"], cfg["kv_heads"], cfg["head_dim"]
    with jax.named_scope("attention_proj"):
        q = rms_norm(p["norm_q"], (u @ p["wq"]).reshape(n, t, hq, hd), cfg["eps"])
        k = rms_norm(p["norm_k"], (u @ p["wk"]).reshape(n, t, hkv, hd), cfg["eps"])
        v = (u @ p["wv"]).reshape(n, t, hkv, hd)
    if sliding:
        with jax.named_scope("rope"):
            q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    with jax.named_scope("attention_window" if sliding else "attention_full"):
        attend = causal_attention if engages(t) else dense_causal_attention
        out = attend(q, k, v, window=cfg["window"] if sliding else None)
    out = out.transpose(0, 2, 1, 3).reshape(n, t, hq * hd)
    with jax.named_scope("attention_gate"):
        out = (out * jax.nn.sigmoid((u @ p["wg"]).astype(_F32))).astype(out.dtype)
    with jax.named_scope("attention_proj"):
        return out @ p["wo"]


def feed_forward(p: Params, h: jax.Array, cfg: dict, *, dense: bool):
    """``(m [N, T, d], counters)``: what the layer's second branch makes of the normed
    ``h``, BEFORE its out-norm: the dense MLP, or the shared expert plus the held experts'
    part of the routed sum.  The shares of a layer add up here, where a deployment's
    exchange would sum them; the out-norm then reads the sum."""
    n, t, d = h.shape
    if dense:
        with jax.named_scope("dense_mlp"):
            return gated_mlp(p["w_gate_up"], p["w_down"], h), jnp.zeros((len(COUNTERS),), _F32)
    tokens = h.reshape(n * t, d)
    with jax.named_scope("moe_router"):
        picks, weights = sigmoid_route(p["router"], tokens, cfg["top_k"], cfg["routed_scale"],
                                       bias=p["router_bias"])
    routed, counted = held_experts(
        tokens, picks, weights, p["w_gate_up"], p["w_down"],
        first_expert=cfg["first_expert"], activation=SWIGLU)
    with jax.named_scope("moe_shared"):
        shared = gated_mlp(p["shared_gate_up"], p["shared_down"], tokens)
    return (routed + shared).reshape(n, t, d), counted


def decoder_layer(p: Params, x: jax.Array, cfg: dict, *, dense: bool, sliding: bool):
    """``(the layer's output [N, T, d], its counters)``; a dense layer counts nothing."""
    eps = cfg["eps"]
    attended = gated_attention(p, rms_norm(p["norm_in"], x, eps), cfg, sliding=sliding)
    x = x + rms_norm(p["norm_post_attn"], attended, eps)
    m, counted = feed_forward(p, rms_norm(p["norm_pre_mlp"], x, eps), cfg, dense=dense)
    return x + rms_norm(p["norm_post_mlp"], m, eps), counted


def hidden_states(params: Params, tokens: jax.Array, cfg: dict):
    """``([N, T, width]`` after the last layer, counters summed over the expert layers)."""
    rows = embed_rows(params["embed"], tokens.astype(jnp.int32))
    with jax.named_scope("token_embed"):
        x = (rows.astype(_F32) * math.sqrt(cfg["width"])).astype(rows.dtype)
    plan = []
    for index, sliding in enumerate(cfg["sliding_layout"]):
        dense = index < cfg["dense_layers"]
        kind, at = ("dense", index) if dense else ("moe", index - cfg["dense_layers"])
        plan.append((partial(decoder_layer, cfg=cfg, dense=dense, sliding=bool(sliding)),
                     params[kind], at))
    return run_layers(x, plan, len(COUNTERS))


@register_model("gated_moe_lm")
def gated_moe_lm(
    vocab: int = 256,
    seq_len: int = 32,
    width: int = 64,
    sliding_layout: tuple[int, ...] = (1, 1, 0, 1),
    window: int = 8,
    rope_theta: float = 10000.0,
    attn_heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    dense_layers: int = 1,
    dense_width: int = 160,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 24,
    shared_width: int = 24,
    routed_scale: float = 2.826,
    eps: float = 1e-5,
) -> Model:
    """The decoder as a zoo entry (defaults are test-sized).  ``sliding_layout`` has one
    flag a layer, 1 a sliding layer (rotary positions under ``window``), 0 a full one
    (neither); the first ``dense_layers`` of them are dense, the rest expert layers.
    ``experts`` is what the router scores, ``first_expert`` and ``experts_held`` say which
    of them this program holds (all: ``0`` and ``experts``); ``shared_width`` is the
    shared experts' summed width."""
    cfg = dict(locals())
    cfg["sliding_layout"] = tuple(sliding_layout)
    if not sliding_layout or not 0 <= dense_layers <= len(sliding_layout):
        raise ValueError("sliding_layout: one flag a layer; dense_layers count the first of them")
    if attn_heads % kv_heads or head_dim % 2 or window < 1:
        raise ValueError("attn_heads must divide into kv_heads, head_dim in two, window >= 1")
    check_held(experts, first_expert, experts_held, top_k)
    return language_model("gated_moe_lm", cfg, init_gated_moe, hidden_states, COUNTERS,
                          len(sliding_layout) - dense_layers)
