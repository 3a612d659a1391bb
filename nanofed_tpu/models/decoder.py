"""What a decoder-only language model of this zoo IS: the loop over its layers, the head
and the ``Model`` around them, and the parts more than one decoder uses.  The six
decoders (``models.hybrid``, ``models.moe_decoder``, ``models.latent_moe``,
``models.indexed_moe``, ``models.gated_moe``, ``models.diffusion_moe``) are modules of
layer functions over this one and ``models.experts``; none imports another.  The arrows
run one way: ``ops``, ``nn`` -> ``models.experts`` -> here -> the six.

**A layer function** is ``layer_fn(p, x, *operands) -> (x, counted)``: ``p`` the leaves of
ONE layer (a slice of the model's stacked leaves), ``x`` [N, T, width] the residual
stream, ``operands`` whatever every layer reads beside it and must stay an argument of
the rematerialized function (``indexed_moe``'s positions), ``counted`` float32
``[len(COUNTERS)]``, zeros from a layer that counts nothing.  Everything else a layer
needs (``cfg``, its kind's flags) is bound before it is handed over
(``functools.partial``).  :func:`run_layers` is the one place a layer is rematerialized,
under the zoo's one policy (``models.experts.KEEP_NAMED_OUTPUTS``), and a Python loop:
a ``lax.scan`` over like layers lands here, once, when it lands.

**What a decoder's module provides**: ``COUNTERS`` (what its layers count, by name);
``init_<model>(rng, **cfg)`` with the leaves of each kind of layer stacked on a leading
axis and ``embed``, ``head`` and ``norm_f`` at the top; its layer functions;
``hidden_states(params, tokens, cfg)``: the embedding and the *plan*, the ordered list of
``(layer_fn, stacked leaves, index)`` :func:`run_layers` walks; and a
``@register_model`` factory that checks its own arguments
(``models.experts.check_held`` for the held experts) and returns
:func:`language_model`.  **A decoder that carries its objective** (one whose training
is not the last position's label: ``models.diffusion_moe``'s masked denoising over a
doubled stream) also hands :func:`language_model` an ``objective(params, x, y, *, rng) ->
(nll [N] float32, hits [N], {counter: scalar})``: each sample's own loss, the share of
its predictions that were right and its counters, the noise drawn from the step's ``rng``,
the head over the positions it chooses (:func:`log_probs_at`).  It becomes
``apply.sample_nll`` and ``trainer.local.make_grad_fn`` trains on it; ``apply`` stays
the ``[N, vocab]`` view that evaluation reads.  A new decoder joins two test tables, ``DECODERS`` in
``tests/unit/models/test_layer_checkpoints.py`` (what its checkpoints keep) and in
``tests/unit/ops/test_attention_aot.py`` (its step compiled for the chip at a cell's
widths), and compares itself with a plain reference under ``benchmark/reference/``.

The shared parts: :func:`rms_norm`; rotary positions, the turn of a head's pairs by any
angle (:func:`turn_pairs`) and the angle of a text sequence (:func:`rotate`);
:func:`gated_mlp`.  The routers and the held experts are ``models.experts``', the
attention kernels ``ops.attention``'s, the embedding lookup ``nn.embed_rows``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from nanofed_tpu.core.types import Params
from nanofed_tpu.models.base import Model
from nanofed_tpu.models.experts import KEEP_NAMED_OUTPUTS, SWIGLU

_F32 = jnp.float32


def rms_norm(weight: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis, statistics in float32, result in ``x``'s dtype."""
    x32 = x.astype(_F32)
    scale = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight.astype(_F32)).astype(x.dtype)


def pair_frequencies(half: int, theta: float) -> jax.Array:
    """``theta^(-2i/hd)`` for the ``half = hd/2`` rotary pairs of a head, float32."""
    return theta ** (-jnp.arange(half, dtype=_F32) / half)


def turn_pairs(x: jax.Array, angle: jax.Array) -> jax.Array:
    """``x`` [N, T, heads, hd] with dimension ``i`` paired with ``i + hd/2`` and the pair
    at position ``t`` turned by ``angle[t, i]`` ([T, hd/2], float32): the rotate-half
    pairing; float32 arithmetic, the result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half].astype(_F32), x[..., half:].astype(_F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def rotate(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions of a text sequence on ``x`` [N, T, heads, hd]: the pair ``i`` at
    position ``t`` turns by ``t * theta^(-2i/hd)`` (:func:`turn_pairs`)."""
    freq = pair_frequencies(x.shape[-1] // 2, theta)
    return turn_pairs(x, jnp.arange(x.shape[1], dtype=_F32)[:, None] * freq[None, :])


def gated_mlp(w_gate_up: jax.Array, w_down: jax.Array, h: jax.Array) -> jax.Array:
    """``W_down (silu(W_gate h) * (W_up h))`` on a fused ``[d, 2 f]`` leaf: a dense
    layer's MLP and the shared experts, each at its own width."""
    return SWIGLU.apply(h @ w_gate_up) @ w_down


def run_layers(x: jax.Array, plan: Sequence[tuple[Callable, Params, int]], n_counters: int,
               *operands):
    """``(x after the last layer, the layers' counters summed)``: for each ``(layer_fn,
    stacked, index)`` of ``plan`` in order, ``layer_fn`` rematerialized under the zoo's one
    policy and applied to layer ``index``'s slice of the ``stacked`` leaves, ``x`` and
    the ``operands``.  Entries that share one ``layer_fn`` object share its trace."""
    counters = jnp.zeros((n_counters,), _F32)
    with jax.named_scope("layer_scan"):
        for layer_fn, stacked, index in plan:
            layer = jax.checkpoint(layer_fn, policy=KEEP_NAMED_OUTPUTS)
            x, counted = layer(jax.tree.map(lambda leaf: leaf[index], stacked), x, *operands)
            counters = counters + counted
    return x, counters


def log_probs_at(params: Params, hidden: jax.Array, at, eps: float) -> jax.Array:
    """The head over chosen positions: float32 log-probabilities ``[N, ..., vocab]`` from
    ``hidden[:, at]`` (``hidden`` [N, T, width]; ``at`` an index or a slice of its
    positions), the final norm and the untied head running on those positions alone.
    The one place ``lm_head`` is opened."""
    with jax.named_scope("lm_head"):
        chosen = rms_norm(params["norm_f"], hidden[:, at, :], eps)
        return jax.nn.log_softmax((chosen @ params["head"]).astype(_F32))


def language_model(name: str, cfg: dict, init: Callable, hidden_states: Callable,
                   counters: Sequence[str], counted_layers: int,
                   check: Callable | None = None, objective: Callable | None = None) -> Model:
    """The zoo entry ``name`` around a decoder's ``hidden_states(params, tokens, cfg) ->
    (hidden [N, T, width], counters summed)``: ``apply`` returns next-token
    log-probabilities at the LAST position (``[N, vocab]``, float32), the final norm and
    the untied head running on that position's hidden state alone; ``apply.with_counters``
    returns them beside ``{counter: its mean over the counted_layers}`` and is set only
    where a layer counts.  ``init`` is the model's ``init_*`` (it takes ``cfg`` whole);
    ``check(x)`` refuses a batch the layers cannot take, at every call.  ``objective``
    is what a decoder that carries its own training loss hands over (the module's
    docstring): it becomes ``apply.sample_nll``."""

    def with_counters(params: Params, x: jax.Array, *, train: bool = False, rng=None):
        """``(log-probs [N, vocab] at the last position, {counter: scalar})``."""
        del train, rng  # no dropout
        if check is not None:
            check(x)
        hidden, counted = hidden_states(params, x, cfg)
        logp = log_probs_at(params, hidden, -1, cfg["eps"])
        counted = lax.stop_gradient(counted) / max(counted_layers, 1)
        return logp, dict(zip(counters, counted))

    def apply(params: Params, x: jax.Array, *, train: bool = False, rng=None) -> jax.Array:
        return with_counters(params, x, train=train, rng=rng)[0]

    if counted_layers > 0:
        apply.with_counters = with_counters
    if objective is not None:
        apply.sample_nll = objective
    return Model(
        name=name,
        init=partial(init, **cfg),
        apply=apply,
        input_shape=(cfg["seq_len"],),
        num_classes=cfg["vocab"],
        token_stream=True,
    )
