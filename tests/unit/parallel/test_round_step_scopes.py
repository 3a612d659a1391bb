"""The round program's ``jax.named_scope``s: the chunk loop's name in both round steps,
and that a scope is a name and nothing else (the program lowers to the same text with
every scope taken out)."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import get_model
from nanofed_tpu.parallel import (
    build_round_step,
    init_server_state,
    make_mesh,
    shard_client_data,
)
from nanofed_tpu.security import ValidationConfig
from nanofed_tpu.trainer import TrainingConfig, stack_rngs

CLIENTS = 4
#: Every scope this file's programs carry between them.
SCOPES = ("chunk_loop", "local_fit", "client_reduce", "round_metrics", "server_apply",
          "batch_gather", "cast_params", "nll_loss", "optimizer_step")


def _lowered(devices, model, x, classes, **kwargs):
    """A chunked one-device round step over ``CLIENTS`` clients, lowered."""
    mesh = make_mesh(devices[:1])
    rng = np.random.default_rng(0)
    n = x.shape[1]
    data = shard_client_data(
        ClientData(x=x, y=jnp.asarray(rng.integers(0, classes, size=(CLIENTS, n))),
                   mask=jnp.ones((CLIENTS, n), jnp.float32)), mesh)
    training = TrainingConfig(batch_size=n // 2, local_epochs=1, learning_rate=0.1,
                              compute_dtype="bfloat16")
    strategy = fedavg_strategy()
    params = model.init(jax.random.key(0))
    step = build_round_step(model.apply, training, mesh, strategy, client_chunk=2, **kwargs)
    return step.lower(params, init_server_state(strategy, params), data,
                      compute_weights(data.num_samples), stack_rngs(jax.random.key(7), CLIENTS))


def _mlp(devices, **kwargs):
    x = jnp.asarray(np.random.default_rng(1).normal(size=(CLIENTS, 8, 8)), jnp.float32)
    return _lowered(devices, get_model("mlp", in_features=8, hidden=4, num_classes=3), x, 3,
                    **kwargs)


@pytest.mark.parametrize("kwargs, outer, inner", [
    ({}, "chunk_loop", "local_fit"),  # streaming: the reduce folded into the loop
    ({"validation": ValidationConfig(max_norm=100.0, min_clients_for_stats=100)},
     "local_fit", "chunk_loop"),  # materialising: lax.map inside the fit's scope
], ids=["streaming", "materialising"])
def test_the_chunk_loop_has_a_name_in_both_round_steps(devices, kwargs, outer, inner):
    paths = set(re.findall(r'op_name="([^"]+)"', _mlp(devices, **kwargs).compile().as_text()))
    nested = [p for p in paths if f"/{outer}/" in p and f"/{inner}/" in p]
    assert nested and all(p.index(f"/{outer}/") < p.index(f"/{inner}/") for p in nested)
    # the local step's own scopes sit inside both
    assert any("/optimizer_step/" in p for p in nested)
    if not kwargs:  # the streamed reduce and the deltas' norms run inside the loop too
        assert any("/chunk_loop/" in p and "/client_reduce/" in p for p in paths)
        assert any("/chunk_loop/" in p and "/round_metrics/" in p for p in paths)


def _cnn(devices):
    x = jnp.asarray(np.random.default_rng(1).normal(size=(CLIENTS, 4, 28, 28, 1)), jnp.float32)
    return _lowered(devices, get_model("mnist_cnn"), x, 10)


def _transformer(devices):
    # 16 positions: the dense attention, whose module carries no file's line numbers
    model = get_model("transformer_lm_scan", vocab=32, seq_len=16, width=16, depth=2, heads=2)
    x = jnp.asarray(np.random.default_rng(1).integers(0, 32, size=(CLIENTS, 4, 16)))
    return _lowered(devices, model, x, 32)


@pytest.mark.parametrize("lower, own", [
    (_cnn, ("cnn_conv1", "cnn_conv2", "cnn_pool", "cnn_fc1", "cnn_fc2")),
    (_transformer, ("token_embed", "layer_scan", "attention_proj", "causal_attention",
                    "mlp_block", "lm_head")),
], ids=["mnist_cnn", "transformer_lm_scan"])
def test_scopes_are_names_and_nothing_else(devices, monkeypatch, lower, own):
    def carried(lowered):  # the scopes that are a component of some name path
        text = lowered.as_text(debug_info=True)
        return {scope for scope in SCOPES + own if re.search(rf'[/"(]{scope}[/)"]', text)}

    named = lower(devices)
    assert carried(named) == set(SCOPES + own)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = lower(devices)
    assert carried(bare) == set()
    assert named.as_text() == bare.as_text()
