"""Decoder with latent attention over sigmoid-routed experts beside shared experts, its
first layers dense (the ``deepseek_v3`` layout: ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``first_k_dense_replace``, ``n_shared_experts``,
``topk_method`` ``noaux_tc``).

A layer, ``x`` [N, T, d] (pre-norm residual, no bias on any projection)::

    u          = RMSNorm_in(x)
    q          = u W_q                      [T, H, nope + rope] = q_nope | q_pe
    c | k_pe   = u W_kv_a                   [T, rank] | [T, rope]: ONE rotary key for all heads
    kv         = RMSNorm_kv(c) W_kv_b       [T, H, nope + value] = k_nope | v
    q_pe, k_pe = rotate(q_pe), rotate(k_pe)          the rope dimensions alone
    a          = attention(q_nope | q_pe, k_nope | k_pe, v) / sqrt(nope + rope), causal
    x'         = x + a W_o
    h          = RMSNorm_post(x')
    dense layer:   out = x' + W_down (silu(W_gate h) * (W_up h))
    expert layer:  p = sigmoid(h W_r); picks = top_k(p + b); g = scale p[picks] / sum p[picks]
                   out = x' + sum over HELD picks e of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))
                            + S_down (silu(S_gate h) * (S_up h))

then a final RMSNorm and an untied head.  ``b`` is the router's selection bias: a leaf of
the tree that moves the picks and not the weights and takes no gradient
(``experts.sigmoid_route``); the rule that would update it, and the sequence-wise
auxiliary loss, are not built.  Like ``moe_decoder_lm`` it drops into the standard
federated pipeline: ``apply`` returns next-token log-probabilities at the LAST position
(``[N, vocab]``); the dense layers' leaves are stacked on a leading axis under
``params["dense"]``, the expert layers' under ``params["moe"]``, and every layer is
rematerialized (``jax.checkpoint``) but for what carries a name
(``models.experts.KEEP_NAMED_OUTPUTS``): the attention kernels' output and log-sum-exp
(one ``[N, heads, T, value]`` array a layer beside the layer's input) and, in an expert
layer, the dispatch's integer layout (``src``, ``block_expert``, the trip count: under
0.3 MB), so the backward pass neither launches the forward kernel nor lays the picks
out again.

**Attention** runs block by block in ``ops.attention``'s kernels wherever the sequence
is whole blocks of at least ``MIN_SEQ`` positions, with score heads of ``nope + rope``
and value heads of ``value`` dimensions, and densely below that (tests).  The program
hands them ``k = k_nope | broadcast(k_pe)`` written out a head; autodiff sums the rotary
part of ``dK`` over the heads.  Rotary positions are ``decoder.rotate``'s (the
rotate-half pairing, float32 angles) on the ``rope`` dimensions.

**Experts**: the layer is TOLD which experts it holds (``first_expert``,
``experts_held``); dispatch and the block loop are ``models.experts``', here with the
SiLU-gated activation on a fused ``[d, 2 f]`` leaf.  The shared experts and the dense
layer's MLP are one function of a width (``decoder.gated_mlp``).  The expert layers report
:data:`COUNTERS` through ``apply.with_counters``.
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


def init_latent_moe(rng: PRNGKey, *, vocab, width, heads, latent_rank, nope_dim, rope_dim,
                    value_dim, dense_layers, dense_width, expert_layers, experts,
                    experts_held, expert_width, shared_width, **_) -> Params:
    """N(0, 1) embeddings; N(0, 0.02) head and matrices, every projection into the residual
    stream (``wo``, the dense, shared and routed ``w_down``) N(0, 0.02 / sqrt(2 layers));
    norms 1 (``moe_decoder.init_moe_decoder`` says why the embeddings dominate).  The
    selection bias N(0, 0.005): not zero, so that a program that leaves it out shows, and
    small beside the sigmoid scores' spread (0.2), so that it does not unbalance the
    experts' loads itself (at 0.02 the busiest held expert saw 1.3 to 1.8 times the mean)."""
    into_stream = 0.02 / math.sqrt(2 * (dense_layers + expert_layers))
    normal = lambda key, *shape, std=0.02: std * jax.random.normal(key, shape, _F32)
    ones = lambda *shape: jnp.ones(shape, _F32)

    def attention(key, n):
        k = jax.random.split(key, 4)
        return {
            "norm_in": ones(n, width),
            "wq": normal(k[0], n, width, heads * (nope_dim + rope_dim)),
            "wkv_a": normal(k[1], n, width, latent_rank + rope_dim),
            "norm_kv": ones(n, latent_rank),
            "wkv_b": normal(k[2], n, latent_rank, heads * (nope_dim + value_dim)),
            "wo": normal(k[3], n, heads * value_dim, width, std=into_stream),
            "norm_post": ones(n, width),
        }

    k = jax.random.split(rng, 12)
    n_e = expert_layers
    return {
        "embed": normal(k[0], vocab, width, std=1.0),
        "head": normal(k[1], width, vocab),
        "norm_f": ones(width),
        "dense": {
            **attention(k[2], dense_layers),
            "w_gate_up": normal(k[3], dense_layers, width, 2 * dense_width),
            "w_down": normal(k[4], dense_layers, dense_width, width, std=into_stream),
        },
        "moe": {
            **attention(k[5], n_e),
            "router": normal(k[6], n_e, width, experts),
            "router_bias": normal(k[7], n_e, experts, std=0.005),
            "shared_gate_up": normal(k[8], n_e, width, 2 * shared_width),
            "shared_down": normal(k[9], n_e, shared_width, width, std=into_stream),
            "w_gate_up": normal(k[10], n_e, experts_held, width, 2 * expert_width),
            "w_down": normal(k[11], n_e, experts_held, expert_width, width, std=into_stream),
        },
    }


def latent_attention(p: Params, u: jax.Array, cfg: dict) -> jax.Array:
    """Causal latent attention over the normed ``u`` [N, T, d], its output projection
    included: keys and values come up from one ``latent_rank``-wide normed latent, and
    one rotary key of ``rope_dim`` dimensions serves every head."""
    n, t, _ = u.shape
    h, rank = cfg["heads"], cfg["latent_rank"]
    nope, rope, value = cfg["nope_dim"], cfg["rope_dim"], cfg["value_dim"]
    with jax.named_scope("mla_q"):
        q = (u @ p["wq"]).reshape(n, t, h, nope + rope)
    with jax.named_scope("mla_kv_down"):
        down = u @ p["wkv_a"]
        latent, k_pe = down[..., :rank], down[..., rank:]
    with jax.named_scope("mla_kv_up"):
        kv = (rms_norm(p["norm_kv"], latent, cfg["eps"]) @ p["wkv_b"]).reshape(n, t, h, nope + value)
    with jax.named_scope("mla_rope"):
        q_pe = rotate(q[..., nope:], cfg["rope_theta"])
        k_pe = rotate(k_pe[:, :, None, :], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (n, t, h, rope))], axis=-1)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., nope:]))
    with jax.named_scope("mla_attention"):
        attend = causal_attention if engages(t) else dense_causal_attention
        out = attend(q, k, v)
    with jax.named_scope("attention_proj"):
        return out.transpose(0, 2, 1, 3).reshape(n, t, h * value) @ p["wo"]


def decoder_layer(p: Params, x: jax.Array, cfg: dict, *, dense: bool):
    """``(the layer's output [N, T, d], its counters)``; a dense layer counts nothing."""
    n, t, d = x.shape
    x = x + latent_attention(p, rms_norm(p["norm_in"], x, cfg["eps"]), cfg)
    h = rms_norm(p["norm_post"], x, cfg["eps"])
    if dense:
        with jax.named_scope("dense_mlp"):
            return x + gated_mlp(p["w_gate_up"], p["w_down"], h), jnp.zeros((len(COUNTERS),), _F32)
    tokens = h.reshape(n * t, d)
    with jax.named_scope("moe_router"):
        picks, weights = sigmoid_route(p["router"], tokens, cfg["top_k"], cfg["routed_scale"],
                                       bias=p["router_bias"])
    routed, counted = held_experts(
        tokens, picks, weights, p["w_gate_up"], p["w_down"],
        first_expert=cfg["first_expert"], activation=SWIGLU)
    with jax.named_scope("moe_shared"):
        shared = gated_mlp(p["shared_gate_up"], p["shared_down"], tokens)
    return x + (routed + shared).reshape(n, t, d), counted


def hidden_states(params: Params, tokens: jax.Array, cfg: dict):
    """``([N, T, width]`` after the last layer, counters summed over the expert layers)."""
    x = embed_rows(params["embed"], tokens.astype(jnp.int32))
    plan = []
    for kind, count in (("dense", cfg["dense_layers"]), ("moe", cfg["expert_layers"])):
        layer = partial(decoder_layer, cfg=cfg, dense=kind == "dense")  # one trace a kind
        plan += [(layer, params[kind], index) for index in range(count)]
    return run_layers(x, plan, len(COUNTERS))


@register_model("latent_moe_lm")
def latent_moe_lm(
    vocab: int = 256,
    seq_len: int = 32,
    width: int = 64,
    heads: int = 4,
    latent_rank: int = 32,
    nope_dim: int = 16,
    rope_dim: int = 8,
    value_dim: int = 16,
    rope_theta: float = 50000.0,
    dense_layers: int = 1,
    dense_width: int = 160,
    expert_layers: int = 2,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 24,
    shared_width: int = 48,
    routed_scale: float = 2.446,
    eps: float = 1e-5,
) -> Model:
    """The decoder as a zoo entry (defaults are test-sized): ``dense_layers`` leading
    dense layers, then ``expert_layers`` expert layers.  ``experts`` is what the router
    scores, ``first_expert`` and ``experts_held`` say which of them this program holds
    (all: ``0`` and ``experts``); ``shared_width`` is the shared experts' summed width."""
    cfg = dict(locals())
    if dense_layers < 0 or expert_layers < 0 or dense_layers + expert_layers == 0:
        raise ValueError("a layer at least: dense_layers and expert_layers count them")
    if rope_dim % 2 or min(heads, latent_rank, nope_dim + rope_dim, value_dim) < 1:
        raise ValueError("rope_dim must divide in two; heads, latent_rank, score and value "
                         "head sizes at least 1")
    check_held(experts, first_expert, experts_held, top_k)
    return language_model("latent_moe_lm", cfg, init_latent_moe, hidden_states, COUNTERS,
                          expert_layers)
