"""Mixture-of-experts decoder trained and read by diffusion over blocks (the ``sdar_moe``
layout: a Qwen3-MoE backbone, grouped-query attention with per-head q/k norms and rotary
positions over softmax-routed gated experts with no shared one, whose sequence is cut
into blocks of ``block`` tokens, causal ACROSS blocks and bidirectional INSIDE one, a
block learned by masked denoising).

The zoo's second decoder ASSEMBLED from shared parts: ``decoder.rms_norm`` and
``turn_pairs``, ``experts.route``, ``experts.held_experts`` with :data:`SWIGLU`,
``ops.attention``'s kernels under their block-diffusion mask, ``decoder.run_layers``,
``nn.embed_rows``.  Its own: the noise, the doubled stream and its repeating positions,
the objective, and ``apply``'s view.  A layer, ``x`` [N, S, d] over ``S`` stream positions
each with a text position ``pos[j]`` (pre-norm residual, no bias anywhere; all layers
alike)::

    u        = RMSNorm_in(x)
    q, k, v  = u W_q [S,H,hd], u W_k [S,H_kv,hd], u W_v [S,H_kv,hd]
    q, k     = RMSNorm_q(q), RMSNorm_k(k)          per head, over the hd dimensions
    q, k     = rotate(q, pos), rotate(k, pos)      rotate-half, all hd dimensions
    a        = softmax(q k^T / sqrt(hd) + M) v     query head h reads head h // (H / H_kv)
    x'       = x + a W_o
    h        = RMSNorm_post(x')
    picks, g = top_k(h W_r), softmax over the picked logits
    out      = x' + sum over held picks e of g_e W_down,e (silu(W_gate,e h) * (W_up,e h))

then a final RMSNorm and an untied head.  **Training** (the objective the model carries,
``apply.sample_nll``; ``L`` the sequence length, ``B`` the block length, ``b(i) = i //
B``), from the step's ``rng``::

    k_t, k_m = split(rng)
    t[n, c]  = eps + (1 - eps) U(k_t)[n, c]            one noise level a block c, eps = 1e-3
    m[n, i]  = U(k_m)[n, i] < t[n, b(i)]
    x_t      = where(m, MASK, x_0)                     MASK the vocabulary's last id
    stream   = [x_0 ; x_t]      S = 2 L, pos = [0 .. L-1, 0 .. L-1], the clean half first
    M        : query j sees key s iff   both clean: b(s) <= b(j);  j noised, s clean:
               b(s) < b(j);  both noised: b(s) = b(j);  j clean, s noised: never
    loss[n]  = (1 / L) sum_i m[n, i] / t[n, b(i)] * -log p(x_0[n, i] | stream)[L + i]

the final norm and the head on the noised half alone, no shift of the logits (the token
at a masked position is predicted at that position), float32.  Every row of ``M`` sees
its own block at least, so no softmax is empty.  **Reading** (``apply``, the ``Model``
contract's ``[N, vocab]`` view that evaluation reads): ONE stream of ``L`` whose last
block is replaced by MASK, under the clean rule alone, log-probabilities at the last
position: one denoising step of generating the last block.

Every layer is rematerialized but for what carries a name
(``models.experts.KEEP_NAMED_OUTPUTS``): the attention kernels' output and log-sum-exp
and the expert dispatch's layout.  **Attention** runs in ``ops.attention``'s kernels
(``blocks=``) wherever the stream is whole tiles of at least ``MIN_SEQ`` positions, and
densely below that (tests).  **Experts**: the layer is TOLD which experts it holds
(``first_expert``, ``experts_held``), routes over all of them and computes its own
experts' part; dispatch and the expert kernels are ``models.experts``'.  The layers report
the experts' :data:`COUNTERS`; the objective adds ``diffusion_masked_share``, masked
positions over noised positions a step (expectation ``(1 + eps) / 2``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.models.decoder import (
    language_model, log_probs_at, pair_frequencies, rms_norm, run_layers, turn_pairs)
from nanofed_tpu.models.experts import COUNTERS, SWIGLU, check_held, held_experts, route
from nanofed_tpu.nn import embed_rows
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention, engages

#: What the objective counts beside the layers' :data:`COUNTERS`.
MASKED_SHARE = "diffusion_masked_share"
#: The least noise level of a block: ``t`` is uniform over ``(NOISE_FLOOR, 1)``, and the
#: loss weighs a masked position by ``1 / t``.
NOISE_FLOOR = 1e-3

_F32 = jnp.float32


def init_diffusion_moe(rng: PRNGKey, *, vocab, width, layers, attn_heads, kv_heads, head_dim,
                       experts, experts_held, expert_width, **_) -> Params:
    """N(0, 1) embeddings; N(0, 0.02) head and matrices, the two projections into the
    residual stream N(0, 0.02 / sqrt(2 layers)); norms 1
    (``moe_decoder.init_moe_decoder`` says why the embeddings dominate)."""
    n = layers
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
            "norm_q": ones(n, head_dim),
            "norm_k": ones(n, head_dim),
            "wo": normal(k[5], n, attn_heads * head_dim, width, std=into_stream),
            "norm_post": ones(n, width),
            "router": normal(k[6], n, width, experts),
            "w_gate_up": normal(k[7], n, experts_held, width, 2 * expert_width),
            "w_down": normal(k[8], n, experts_held, expert_width, width, std=into_stream),
        },
    }


def noised(tokens: jax.Array, rng: PRNGKey, cfg: dict):
    """``(stream [N, 2 L], masked [N, L] bool, level [N, L] float32)``: the clean tokens
    followed by their noised copy, which positions of the copy read MASK, and each
    position's noise level ``t`` (its block's)."""
    n, length = tokens.shape
    key_t, key_m = jax.random.split(rng)
    a_block = NOISE_FLOOR + (1.0 - NOISE_FLOOR) * jax.random.uniform(
        key_t, (n, length // cfg["block"]), _F32)
    level = jnp.repeat(a_block, cfg["block"], axis=1)
    masked = jax.random.uniform(key_m, (n, length), _F32) < level
    copy = jnp.where(masked, jnp.int32(cfg["vocab"] - 1), tokens)
    return jnp.concatenate([tokens, copy], axis=1), masked, level


def attention(p: Params, u: jax.Array, pos: jax.Array, cfg: dict, half: int) -> jax.Array:
    """Grouped-query attention over the normed ``u`` [N, S, d] of a stream whose halves
    are ``half`` positions, under the block-diffusion mask, its output projection
    included; ``pos`` [S] the text position of each stream position."""
    n, s, _ = u.shape
    hq, hkv, hd = cfg["attn_heads"], cfg["kv_heads"], cfg["head_dim"]
    with jax.named_scope("attention_proj"):
        q = rms_norm(p["norm_q"], (u @ p["wq"]).reshape(n, s, hq, hd), cfg["eps"])
        k = rms_norm(p["norm_k"], (u @ p["wk"]).reshape(n, s, hkv, hd), cfg["eps"])
        v = (u @ p["wv"]).reshape(n, s, hkv, hd)
    with jax.named_scope("rope"):
        angle = pos.astype(_F32)[:, None] * pair_frequencies(hd // 2, cfg["rope_theta"])[None, :]
        q, k = turn_pairs(q, angle), turn_pairs(k, angle)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    with jax.named_scope("attention_block_diffusion"):
        attend = causal_attention if engages(s, half) else dense_causal_attention
        out = attend(q, k, v, blocks=(half, cfg["block"]))
    with jax.named_scope("attention_proj"):
        return out.transpose(0, 2, 1, 3).reshape(n, s, hq * hd) @ p["wo"]


def decoder_layer(p: Params, x: jax.Array, pos: jax.Array, cfg: dict, half: int):
    """``(the layer's output [N, S, d], its counters)``."""
    n, s, d = x.shape
    x = x + attention(p, rms_norm(p["norm_in"], x, cfg["eps"]), pos, cfg, half)
    h = rms_norm(p["norm_post"], x, cfg["eps"])
    with jax.named_scope("moe_router"):
        picks, weights = route(p["router"], h.reshape(n * s, d), cfg["top_k"])
    routed, counted = held_experts(
        h.reshape(n * s, d), picks, weights, p["w_gate_up"], p["w_down"],
        first_expert=cfg["first_expert"], activation=SWIGLU)
    return x + routed.reshape(n, s, d), counted


def stream_states(params: Params, stream: jax.Array, cfg: dict, half: int):
    """``([N, S, width]`` after the last layer, counters summed over the layers) of a
    stream of one half (``S = half``) or of a clean half and its noised copy (``S = 2
    half``): the positions repeat, ``pos = [0 .. half-1]`` once a half."""
    x = embed_rows(params["embed"], stream.astype(jnp.int32))
    pos = jnp.arange(stream.shape[1], dtype=jnp.int32) % half
    layer = partial(decoder_layer, cfg=cfg, half=half)  # all layers alike: one trace
    plan = [(layer, params["layers"], index) for index in range(cfg["layers"])]
    return run_layers(x, plan, len(COUNTERS), pos)


def hidden_states(params: Params, tokens: jax.Array, cfg: dict):
    """``apply``'s view: one stream whose last block reads MASK, under the clean rule."""
    length = tokens.shape[1]
    with jax.named_scope("diffusion_noise"):
        last_block = jnp.arange(length) >= length - cfg["block"]
        stream = jnp.where(last_block[None, :], jnp.int32(cfg["vocab"] - 1), tokens.astype(jnp.int32))
    return stream_states(params, stream, cfg, half=length)


def sample_nll(params: Params, x: jax.Array, y: jax.Array, *, rng: PRNGKey, cfg: dict):
    """The objective (``trainer.local.make_grad_fn``): ``(each sequence's weighted masked
    denoising loss [N] float32, the share of its masked positions whose argmax is the
    token [N], the counters)``.  The targets are the sequence's own tokens: ``y`` is not
    read."""
    del y
    tokens = x.astype(jnp.int32)
    length = tokens.shape[1]
    with jax.named_scope("diffusion_noise"):
        stream, masked, level = noised(tokens, rng, cfg)
    hidden, counted = stream_states(params, stream, cfg, half=length)
    logp = log_probs_at(params, hidden, slice(length, None), cfg["eps"])  # the noised half
    with jax.named_scope("nll_loss"):
        of_token = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        nll = -jnp.where(masked, of_token / level, 0.0).sum(axis=1) / length
        right = (jnp.argmax(logp, axis=-1) == tokens) & masked
        hits = right.sum(axis=1) / jnp.maximum(masked.sum(axis=1), 1)
    counters = dict(zip(COUNTERS, jax.lax.stop_gradient(counted) / cfg["layers"]))
    counters[MASKED_SHARE] = masked.mean(dtype=_F32)
    return nll, hits, counters


@register_model("diffusion_moe_lm")
def diffusion_moe_lm(
    vocab: int = 256,
    seq_len: int = 32,
    block: int = 4,
    width: int = 64,
    layers: int = 2,
    attn_heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    rope_theta: float = 1e6,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 48,
    eps: float = 1e-6,
) -> Model:
    """The decoder as a zoo entry (defaults are test-sized).  ``seq_len`` is the TEXT's
    length (a training step's stream is twice it), ``block`` the tokens of a diffusion
    block (a power of two that divides ``seq_len``); a noised position reads MASK, the
    vocabulary's last id; ``experts`` is what the router scores, ``first_expert`` and
    ``experts_held`` say which of them this program holds."""
    cfg = dict(locals())
    if layers < 1 or attn_heads % kv_heads or head_dim % 2:
        raise ValueError("layers >= 1, attn_heads must divide into kv_heads, head_dim in two")
    if block < 1 or block & (block - 1) or seq_len % block:
        raise ValueError(f"block {block}: a power of two that divides seq_len {seq_len}")
    check_held(experts, first_expert, experts_held, top_k)
    return language_model("diffusion_moe_lm", cfg, init_diffusion_moe, hidden_states, COUNTERS,
                          layers, objective=partial(sample_nll, cfg=cfg))
