"""Mixture-of-experts decoder whose every layer is grouped-query attention followed by
sparse gated experts, the attention's kind given a layer: full causal attention with
no positional term, or sliding-window attention with rotary positions (the
``smallthinker`` layout: ``rope_layout`` and ``window_layout``, one flag a layer each).

A layer, ``x`` [N, T, d] (pre-norm residual, no bias anywhere)::

    u      = RMSNorm_in(x)
    picks, g = top_k(u W_r), softmax over the picked logits     the router reads BEFORE
    q, k, v  = u W_q, u W_k, u W_v;  rotated where rope_layout[l]        attention runs
    x'     = x + attention(q, k, v; window where window_layout[l]) W_o
    h      = RMSNorm_post(x')
    out    = x' + sum over held picks e of g_e W_down,e (relu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head.  No shared expert: the routed loop is the whole
feed-forward.  Like ``hybrid_lm`` it drops into the standard federated pipeline:
``apply`` returns next-token log-probabilities at the LAST position (``[N, vocab]``);
the layers are stacked on a leading axis (``params["layers"]["wq"]`` is ``[layers, d,
heads * head_dim]``, the experts ``[layers, experts held, d, 2 f]`` and ``[layers,
experts held, f, d]``), and every layer is rematerialized (``jax.checkpoint``) but for
what carries a name (``models.experts.KEEP_NAMED_OUTPUTS``): the attention kernels'
output and log-sum-exp (one ``[N, heads, T, head_dim]`` array a layer beside the layer's
input) and the expert dispatch's integer layout (``src``, ``block_expert``, the trip
count: under 0.3 MB), so the backward pass neither launches the forward kernel nor
lays the picks out again.

**Attention** runs block by block in ``ops.attention``'s kernels wherever the sequence
is whole blocks of at least ``MIN_SEQ`` positions — grouped heads read their key/value
head in place, key blocks behind the window are never visited — and densely below that
(tests).  Rotary positions rotate dimension ``i`` with ``i + head_dim / 2`` (the
rotate-half pairing), all ``head_dim`` dimensions, angles in float32.

**Experts**: the layer is TOLD which experts it holds (``first_expert``,
``experts_held``); dispatch and the block loop are ``models.experts``', shared with
``hybrid_lm``, here with the gated activation on a fused ``[d, 2 f]`` leaf (``W_gate |
W_up``: one product a block instead of two — a layout, not a change of function).  The
layer reports :data:`COUNTERS` through ``apply.with_counters``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.models.decoder import language_model, rms_norm, rotate, run_layers
from nanofed_tpu.models.experts import COUNTERS, REGLU, check_held, held_experts, route
from nanofed_tpu.nn import embed_rows
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention, engages

_F32 = jnp.float32


def init_moe_decoder(rng: PRNGKey, *, vocab, width, rope_layout, attn_heads, kv_heads,
                     head_dim, experts, experts_held, expert_width, **_) -> Params:
    """N(0, 1) embeddings; N(0, 0.02) head and matrices, the two projections into the
    residual stream N(0, 0.02 / sqrt(2 layers)); norms 1.  Embeddings that dominate the
    stream keep the first layers' routing spread over the experts: with every leaf at 0.02
    uniform attention over random tokens leaves one common vector after two layers, and
    every token picks the same experts."""
    n = len(rope_layout)
    k = jax.random.split(rng, 9)
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
            "wo": normal(k[5], n, attn_heads * head_dim, width, std=into_stream),
            "norm_post": ones(n, width),
            "router": normal(k[6], n, width, experts),
            "w_gate_up": normal(k[7], n, experts_held, width, 2 * expert_width),
            "w_down": normal(k[8], n, experts_held, expert_width, width, std=into_stream),
        },
    }


def attention(p: Params, u: jax.Array, cfg: dict, *, rope: bool, window: int | None) -> jax.Array:
    """Grouped-query causal attention over the normed ``u`` [N, T, d], its output
    projection included."""
    n, t, _ = u.shape
    hq, hkv, hd = cfg["attn_heads"], cfg["kv_heads"], cfg["head_dim"]
    with jax.named_scope("attention_proj"):
        q = (u @ p["wq"]).reshape(n, t, hq, hd)
        k = (u @ p["wk"]).reshape(n, t, hkv, hd)
        v = (u @ p["wv"]).reshape(n, t, hkv, hd)
    if rope:
        with jax.named_scope("rope"):
            q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    with jax.named_scope("attention_full" if window is None else "attention_window"):
        attend = causal_attention if engages(t) else dense_causal_attention
        out = attend(q, k, v, window=window)
    with jax.named_scope("attention_proj"):
        return out.transpose(0, 2, 1, 3).reshape(n, t, hq * hd) @ p["wo"]


def decoder_layer(p: Params, x: jax.Array, cfg: dict, *, rope: bool, window: int | None):
    """``(the layer's output [N, T, d], its counters)``."""
    n, t, d = x.shape
    u = rms_norm(p["norm_in"], x, cfg["eps"])
    with jax.named_scope("moe_router"):  # before attention: the picks are known while it runs
        picks, weights = route(p["router"], u.reshape(n * t, d), cfg["top_k"])
    x = x + attention(p, u, cfg, rope=rope, window=window)
    h = rms_norm(p["norm_post"], x, cfg["eps"])
    routed, counted = held_experts(
        h.reshape(n * t, d), picks, weights, p["w_gate_up"], p["w_down"],
        first_expert=cfg["first_expert"], activation=REGLU)
    return x + routed.reshape(n, t, d), counted


def hidden_states(params: Params, tokens: jax.Array, cfg: dict):
    """``([N, T, width]`` after the last layer, counters summed over the layers)."""
    x = embed_rows(params["embed"], tokens.astype(jnp.int32))
    plan = [(partial(decoder_layer, cfg=cfg, rope=bool(rope),
                     window=cfg["window"] if windowed else None), params["layers"], index)
            for index, (rope, windowed) in enumerate(zip(cfg["rope_layout"], cfg["window_layout"]))]
    return run_layers(x, plan, len(COUNTERS))


@register_model("moe_decoder_lm")
def moe_decoder_lm(
    vocab: int = 256,
    seq_len: int = 32,
    width: int = 64,
    rope_layout: tuple[int, ...] = (0, 1, 1, 1),
    window_layout: tuple[int, ...] = (0, 1, 1, 1),
    window: int = 8,
    rope_theta: float = 1.5e6,
    attn_heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 48,
    eps: float = 1e-6,
) -> Model:
    """The decoder as a zoo entry (defaults are test-sized).  One layer a flag of the two
    layouts; ``experts`` is what the router scores, ``first_expert`` and
    ``experts_held`` say which of them this program holds (all: ``0`` and ``experts``)."""
    cfg = dict(locals())
    cfg["rope_layout"], cfg["window_layout"] = tuple(rope_layout), tuple(window_layout)
    if not rope_layout or len(rope_layout) != len(window_layout):
        raise ValueError("rope_layout and window_layout: one flag a layer each, same length")
    if attn_heads % kv_heads or head_dim % 2 or window < 1:
        raise ValueError("attn_heads must divide into kv_heads, head_dim in two, window >= 1")
    check_held(experts, first_expert, experts_held, top_k)
    return language_model("moe_decoder_lm", cfg, init_moe_decoder, hidden_states, COUNTERS,
                          len(rope_layout))
