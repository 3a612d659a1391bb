"""A family whose objective is not "one label a sample": the tests' stand-in for a
denoising loss, on ``reference/transformer_lm.py``'s layers.

The loss of a sequence is its next-token loss at the positions a Bernoulli(``KEEP``)
mask keeps, over the kept count; the mask is drawn from the step key, and the targets are
the sequence's own tokens (``yb`` is ignored).  ``test_benchmark_objective.py`` copies this
file into a tiny root as ``benchmark/reference/masked_lm.py``, beside the module it builds
on; nothing of the program is imported.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        f"_masked_lm_{name}", Path(__file__).with_name(f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


lm = _beside("transformer_lm")

TOKEN_STREAM = True
KEEP = 0.5
init_params = lm.init_params


def sequence_log_probs(params, tokens, model_kwargs, q):
    """``[N, T, vocab]``: ``transformer_lm.log_probs`` with the head at every position."""
    heads, t = model_kwargs["heads"], tokens.shape[1]
    x = params["tok_emb"][tokens] + params["pos_emb"][:t]

    def block(x, p):
        x = x + lm._attention(p["attn"], lm._layer_norm(p["ln1"], x), heads, q)
        h = jax.nn.gelu(lm._dense(p["mlp"]["fc1"], lm._layer_norm(p["ln2"], x), q), approximate=True)
        return x + lm._dense(p["mlp"]["fc2"], h, q), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return jax.nn.log_softmax(lm._dense(params["head"], lm._layer_norm(params["ln_f"], x), q))


def masked_nll(logp, tokens, key):
    """From ``[N, T, vocab]`` log-probabilities to one loss a sequence; ``key`` draws
    which of the batch's ``[N, T - 1]`` targets count."""
    nll = -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
    keep = jax.random.bernoulli(key, KEEP, nll.shape).astype(jnp.float32)
    return (nll * keep).sum(axis=-1) / jnp.maximum(keep.sum(axis=-1), 1.0)


def make_sample_nll(rekey=lambda key: key):
    """``rekey``: the identity, or what a family that lost the schedule would do to it."""

    def sample_nll(params, xb, yb, key, model_kwargs, q=lambda t: t):
        del yb
        return masked_nll(sequence_log_probs(params, xb, model_kwargs, q), xb, rekey(key))

    return sample_nll


sample_nll = make_sample_nll()
