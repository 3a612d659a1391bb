"""Plain reference of the GPT-2-shaped causal LM the zoo calls ``transformer_lm_scan``.

Token + learned position embedding, ``depth`` pre-LN blocks (causal multi-head
attention, 4x GELU-tanh MLP), final LayerNorm (eps 1e-5), an untied head with bias;
log-probabilities of the next token at the LAST position only, which is where the
repo's token-stream pipeline puts the loss.  Departures from ``openai-community/gpt2``
are the zoo model's: untied biased head, no dropout.  The block leaves are stacked
``[depth, ...]`` and the layers run under ``lax.scan``.  Imports nothing of the program.

``q`` rounds a matmul operand to the precision under test and returns float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TOKEN_STREAM = True


def _dense_init(key, fan_in, fan_out, scale=1.0, lead=()):
    kw, kb = jax.random.split(key)
    bound = 1.0 / math.sqrt(fan_in)
    return {
        "kernel": scale * jax.random.uniform(kw, (*lead, fan_in, fan_out), jnp.float32, -bound, bound),
        "bias": jax.random.uniform(kb, (*lead, fan_out), jnp.float32, -bound, bound),
    }


def _ln_init(width, lead=()):
    return {"scale": jnp.ones((*lead, width), jnp.float32),
            "bias": jnp.zeros((*lead, width), jnp.float32)}


def init_params(key, model_kwargs):
    """Weights from the seed: N(0, 0.02) embeddings, uniform(-1/sqrt(fan_in)) matrices,
    the two residual output projections scaled by 1/sqrt(2 depth) (GPT-2)."""
    vocab, seq_len = model_kwargs["vocab"], model_kwargs["seq_len"]
    width, depth = model_kwargs["width"], model_kwargs["depth"]
    k = jax.random.split(key, 9)
    resid = 1.0 / math.sqrt(2.0 * depth)
    lead = (depth,)
    return {
        "tok_emb": 0.02 * jax.random.normal(k[0], (vocab, width), jnp.float32),
        "pos_emb": 0.02 * jax.random.normal(k[1], (seq_len, width), jnp.float32),
        "head": _dense_init(k[2], width, vocab),
        "ln_f": _ln_init(width),
        "blocks": {
            "ln1": _ln_init(width, lead),
            "attn": {
                "wq": _dense_init(k[3], width, width, lead=lead),
                "wk": _dense_init(k[4], width, width, lead=lead),
                "wv": _dense_init(k[5], width, width, lead=lead),
                "wo": _dense_init(k[6], width, width, resid, lead),
            },
            "ln2": _ln_init(width, lead),
            "mlp": {
                "fc1": _dense_init(k[7], width, 4 * width, lead=lead),
                "fc2": _dense_init(k[8], 4 * width, width, resid, lead),
            },
        },
    }


def _layer_norm(p, x):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _dense(p, x, q):
    return q(x) @ q(p["kernel"]) + p["bias"]


def _attention(p, x, heads, q):
    n, t, d = x.shape
    hd = d // heads
    split = lambda y: y.reshape(n, t, heads, hd).transpose(0, 2, 1, 3)
    qh, kh, vh = (split(_dense(p[w], x, q)) for w in ("wq", "wk", "wv"))
    scores = jnp.einsum("nhqd,nhkd->nhqk", q(qh), q(kh)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nhkd->nhqd", q(att), q(vh))
    return _dense(p["wo"], out.transpose(0, 2, 1, 3).reshape(n, t, d), q)


def log_probs(params, tokens, key, model_kwargs, q=lambda t: t):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    heads = model_kwargs["heads"]
    t = tokens.shape[1]
    x = params["tok_emb"][tokens] + params["pos_emb"][:t]

    def block(x, p):
        x = x + _attention(p["attn"], _layer_norm(p["ln1"], x), heads, q)
        h = jax.nn.gelu(_dense(p["mlp"]["fc1"], _layer_norm(p["ln2"], x), q), approximate=True)
        return x + _dense(p["mlp"]["fc2"], h, q), None

    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    x = _layer_norm(params["ln_f"], x[:, -1, :])
    return jax.nn.log_softmax(_dense(params["head"], x, q))
