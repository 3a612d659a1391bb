"""GPT-style causal transformer LM — the first workload with a model worth sharding
(by CPU accounting; what a round really holds on the chip is measured since PR 27 by
``nemotron-twotower-ctx-9l-xsilo-4.sync``: a 667M-parameter ``models.hybrid`` tree,
PERF.md).

Every other zoo member is digits-MLP/CNN/ResNet-8 scale, so the FSDP model axis
(``parallel.mesh.param_partition_spec``) has never sharded a parameter that would
not comfortably fit replicated, and the wire has never carried an update payload
where compression pays.  This model exists to make both real: a next-token
predictor over synthetic token streams (``data.synthetic_token_streams`` — no
dataset download exists in this environment) whose parameter count scales as
``~12 * depth * width^2 + 2 * vocab * width``, so ``transformer_lm(width=2048,
depth=24, vocab=32768)`` is a ~1.3B-parameter tree that genuinely exceeds
replicated per-device capacity on 16 GiB-HBM chips (docs/performance.md "When
adapters pay" carries the math).

Architecture (functional, pure ``(init, apply)`` like the rest of the zoo):
token embedding + learned positional embedding, ``depth`` pre-LN blocks of
multi-head CAUSAL self-attention and a 4x GELU MLP (whose backward is handed the
GELU's input and nothing else of that width: :func:`_mlp_tail`), final LayerNorm,
untied unembedding head.  ``apply`` returns next-token log-probabilities at the LAST
position (``[N, vocab]``) so the model drops into the standard federated
pipeline — ``ClientData.y`` is the true next token, the masked-NLL ``grad_fn``,
evaluator, and every round builder work unchanged; :func:`apply_sequence`
exposes the full ``[N, T, vocab]`` per-position logits (causality tests, future
all-position training).  Both are the same head (final LayerNorm, unembedding,
log-softmax — all row-wise) over the same trunk (embeddings + blocks);
``apply`` takes the last position's hidden state BEFORE the head, so the
``width x vocab`` matmul and the softmax run on ``[N, width]`` and never on the
``T - 1`` positions whose log-probs nothing reads.

Every matrix the FSDP layout rule cares about is 2-D: attention ``wq/wk/wv/wo``
``[D, D]``, MLP ``[D, 4D]``/``[4D, D]``, embeddings/head ``[V, D]``/``[D, V]``
— each leaf's largest divisible dimension shards over the model axis, and these
are exactly the leaves a LoRA :class:`~nanofed_tpu.adapters.AdapterSpec`
targets.

``scan_layers=True`` (the ``transformer_lm_scan`` zoo name) trades the pytree
layout for compile time: the ``depth`` homogeneous block trees stack into
leading-``[depth, ...]`` leaves and the forward pass runs ``lax.scan`` over
them, so XLA compiles ONE block regardless of depth — numerically identical
(the stacked leaves are exactly ``jnp.stack`` of the unrolled ones), and the
FSDP rule never shards the stacking dim (``param_partition_spec`` excludes the
leading dim of rank>=3 leaves from the model axis).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from nanofed_tpu import nn
from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.observability.registry import get_registry
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention
from nanofed_tpu.ops.attention import engages as attention_engages

#: Defaults sized so tier-1 tests compile in seconds; the flagship configs in
#: runs/adapter_* scale width/depth/vocab up through the same factory.
DEFAULT_VOCAB = 256
DEFAULT_SEQ_LEN = 32
DEFAULT_WIDTH = 64
DEFAULT_DEPTH = 2
DEFAULT_HEADS = 4

#: Registry counter: traces of ``_attention``, label ``path`` = ``blockwise`` | ``dense``.
ATTENTION_TRACES = "nanofed_attention_traces_total"


def _layer_norm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def _layer_norm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    mean = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]


def init_transformer(
    rng: PRNGKey,
    vocab: int,
    seq_len: int,
    width: int,
    depth: int,
    scan_layers: bool = False,
) -> Params:
    """Parameter tree for the causal LM.  Embeddings draw N(0, 0.02) (GPT-2
    convention); dense matrices use the zoo's kaiming-uniform ``dense_init``
    with the output projections down-scaled by ``1/sqrt(2*depth)`` (the GPT-2
    residual-accumulation fix, so deep stacks start with unit-scale residual
    streams).

    ``scan_layers=True`` emits the SAME per-layer values (identical RNG splits
    layer for layer) but stacks the ``depth`` homogeneous block trees into one
    ``"blocks"`` subtree whose leaves carry a leading ``[depth, ...]`` stacking
    dim — the layout :func:`apply_sequence` runs a ``lax.scan`` over, so XLA
    traces and compiles ONE block body instead of ``depth`` inlined copies.
    Each stacked leaf is exactly ``jnp.stack`` of the unrolled form's leaves,
    so the two layouts are numerically identical by construction."""
    n_keys = 3 + depth
    keys = jax.random.split(rng, n_keys)
    params: Params = {
        "tok_emb": 0.02 * jax.random.normal(keys[0], (vocab, width), jnp.float32),
        "pos_emb": 0.02 * jax.random.normal(keys[1], (seq_len, width), jnp.float32),
        "head": nn.dense_init(keys[2], width, vocab),
        "ln_f": _layer_norm_init(width),
    }
    resid_scale = 1.0 / math.sqrt(2.0 * depth)
    blocks = []
    for i in range(depth):
        kq, kk, kv, ko, k1, k2 = jax.random.split(keys[3 + i], 6)
        wo = nn.dense_init(ko, width, width)
        fc2 = nn.dense_init(k2, 4 * width, width)
        blocks.append({
            "ln1": _layer_norm_init(width),
            "attn": {
                "wq": nn.dense_init(kq, width, width),
                "wk": nn.dense_init(kk, width, width),
                "wv": nn.dense_init(kv, width, width),
                "wo": {"kernel": wo["kernel"] * resid_scale, "bias": wo["bias"]},
            },
            "ln2": _layer_norm_init(width),
            "mlp": {
                "fc1": nn.dense_init(k1, width, 4 * width),
                "fc2": {"kernel": fc2["kernel"] * resid_scale, "bias": fc2["bias"]},
            },
        })
    if scan_layers:
        params["blocks"] = jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)
    else:
        for i, blk in enumerate(blocks):
            params[f"block_{i}"] = blk
    return params


def stack_blocks(params: Params) -> Params:
    """Convert an UNROLLED parameter tree (``block_0..block_{L-1}``) to the
    scan layout (stacked ``"blocks"`` leaves) — the checkpoint-migration path
    between the two forms; :func:`unstack_blocks` is the exact inverse.  The
    non-block leaves are shared by reference."""
    depth = sum(1 for k in params if k.startswith("block_"))
    if depth == 0:
        raise ValueError("no block_<i> entries to stack — already scan layout?")
    blocks = [params[f"block_{i}"] for i in range(depth)]
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    out["blocks"] = jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)
    return out


def unstack_blocks(params: Params) -> Params:
    """Scan layout -> unrolled layout (inverse of :func:`stack_blocks`)."""
    if "blocks" not in params:
        raise ValueError("no stacked 'blocks' subtree — already unrolled?")
    stacked = params["blocks"]
    depth = int(jax.tree.leaves(stacked)[0].shape[0])
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(depth):
        out[f"block_{i}"] = jax.tree.map(lambda leaf: leaf[i], stacked)
    return out


def _count_attention_trace(path: str) -> None:
    """One more trace of :func:`_attention` by the path it took (``blockwise`` or
    ``dense``), in the process's registry: counted when a program is traced, not when it
    runs, so a run can say which form its programs hold."""
    get_registry().counter(
        ATTENTION_TRACES,
        "Traces of the transformer's causal attention, by the form the shapes chose",
        labels=("path",),
    ).inc(path=path)


def _attention(params: Params, x: jax.Array, heads: int) -> jax.Array:
    """Multi-head causal self-attention over ``x`` [N, T, D].

    The sequence length decides the form: whole blocks of ``ops.attention`` and at
    least ``MIN_SEQ`` positions run block by block (no ``[N, H, T, T]`` array, forward
    or backward); anything shorter keeps the dense spelling, where a ``[T, T]`` tile is
    small and every small model's values stay what they were."""
    n, t, d = x.shape
    hd = d // heads

    def split_heads(y):  # [N, T, D] -> [N, H, T, hd]
        return y.reshape(n, t, heads, hd).transpose(0, 2, 1, 3)

    with jax.named_scope("attention_proj"):
        q = split_heads(nn.dense(params["wq"], x))
        k = split_heads(nn.dense(params["wk"], x))
        v = split_heads(nn.dense(params["wv"], x))
    blockwise = attention_engages(t)
    _count_attention_trace("blockwise" if blockwise else "dense")
    with jax.named_scope("causal_attention"):
        out = (causal_attention if blockwise else dense_causal_attention)(q, k, v)
    with jax.named_scope("attention_proj"):
        out = out.transpose(0, 2, 1, 3).reshape(n, t, d)
        return nn.dense(params["wo"], out)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _mlp_tail(fc2: Params, h: jax.Array) -> jax.Array:
    """The MLP's tail ``fc2(gelu(h))`` over ``h = fc1(ln2(x))`` ``[N, T, 4D]``, keeping
    ``h`` alone for its backward.

    Plain autodiff of the tanh GELU and of fc2 saves six arrays of ``h``'s shape a block
    (``h``, its square, the tanh, the cdf, the product, fc2's input), each an elementwise
    function of ``h``; under ``checkpoint`` the backward recomputes them where it uses
    them, by the same arithmetic, so values and gradients are autodiff's bit for bit.
    fc2's own product is not run again: its output is no input of the backward.
    ``prevent_cse=False``: the default's optimization barriers cost two slice copies a
    layer inside the layer scan, and without a scan XLA's scheduler decides what to keep
    either way."""
    return nn.dense(fc2, jax.nn.gelu(h))


def _trunk(params: Params, tokens: jax.Array, heads: int) -> jax.Array:
    """Embeddings + ``depth`` blocks: int token ids ``[N, T]`` -> the hidden
    state ``[N, T, D]`` the final LayerNorm and the head read."""
    tokens = tokens.astype(jnp.int32)
    t = tokens.shape[1]
    x = nn.embed_rows(params["tok_emb"], tokens) + params["pos_emb"][None, :t]

    def block(x, blk):
        x = x + _attention(blk["attn"], _layer_norm(blk["ln1"], x), heads)
        with jax.named_scope("mlp_block"):
            h = nn.dense(blk["mlp"]["fc1"], _layer_norm(blk["ln2"], x))
            return x + _mlp_tail(blk["mlp"]["fc2"], h)

    with jax.named_scope("layer_scan"):
        if "blocks" in params:
            # Scan layout: one traced block body, scanned over the stacked
            # [depth, ...] leaves — XLA compiles O(1) block HLO in depth instead
            # of O(depth) inlined copies (the compile-wall fix).
            x, _ = jax.lax.scan(
                lambda carry, blk: (block(carry, blk), None), x, params["blocks"]
            )
        else:
            depth = sum(1 for k in params if k.startswith("block_"))
            for i in range(depth):
                x = block(x, params[f"block_{i}"])
    return x


def _head(params: Params, hidden: jax.Array) -> jax.Array:
    """Final LayerNorm -> unembedding -> log-softmax over the LAST axis of
    ``hidden`` (``[..., D]`` -> ``[..., vocab]``).  Every step is row-wise, so
    the head of a slice of positions is that slice of the head."""
    with jax.named_scope("lm_head"):
        hidden = _layer_norm(params["ln_f"], hidden)
        return nn.log_softmax(nn.dense(params["head"], hidden))


def apply_sequence(
    params: Params,
    tokens: jax.Array,
    *,
    heads: int = DEFAULT_HEADS,
    train: bool = False,
    rng: PRNGKey | None = None,
) -> jax.Array:
    """Full per-position next-token log-probs ``[N, T, vocab]`` for int token
    ids ``[N, T]``.  Deterministic (no dropout) — ``train``/``rng`` are accepted
    for apply-signature parity and unused, which keeps fused-vs-single round
    parity exact on every mesh."""
    del train, rng
    return _head(params, _trunk(params, tokens, heads))


def transformer_param_count(
    vocab: int, seq_len: int, width: int, depth: int
) -> int:
    """Analytic parameter count of :func:`init_transformer` — the memory-math
    side of docs/performance.md "When adapters pay", exact (asserted in tests
    against the real tree)."""
    per_block = (
        4 * (width * width + width)  # wq/wk/wv/wo kernels + biases
        + (width * 4 * width + 4 * width)  # fc1
        + (4 * width * width + width)  # fc2
        + 4 * width  # ln1 + ln2 scale/bias
    )
    return (
        vocab * width  # tok_emb
        + seq_len * width  # pos_emb
        + width * vocab + vocab  # head kernel + bias
        + 2 * width  # ln_f
        + depth * per_block
    )


@register_model("transformer_lm")
def transformer_lm(
    vocab: int = DEFAULT_VOCAB,
    seq_len: int = DEFAULT_SEQ_LEN,
    width: int = DEFAULT_WIDTH,
    depth: int = DEFAULT_DEPTH,
    heads: int = DEFAULT_HEADS,
    scan_layers: bool = False,
) -> Model:
    """The causal-LM zoo entry.  ``apply`` returns the LAST position's
    next-token log-probs ``[N, vocab]`` so the standard masked-NLL pipeline
    trains it with ``y`` = true next token; the full ``[N, T, vocab]`` surface
    is :func:`apply_sequence`.  ``apply`` slices the trunk's hidden state to the
    last position and runs the head on that ``[N, width]`` alone — the same
    values as ``apply_sequence(...)[:, -1, :]`` without the head's work (forward
    and backward) on the other ``T - 1`` positions.

    ``scan_layers=True`` (also registered as ``transformer_lm_scan``) selects
    the scan-over-layers parameter layout: the ``depth`` block trees stack into
    leading-``[depth, ...]`` leaves and the forward pass is a ``lax.scan`` over
    them, so compile cost is O(1) in depth instead of O(depth) — identical
    logits (the stacked leaves ARE the unrolled leaves, asserted in tests), a
    different pytree structure (checkpoints don't interchange between layouts;
    ``stack_blocks``/``unstack_blocks`` migrate them)."""
    if width % heads != 0:
        raise ValueError(f"width {width} must be divisible by heads {heads}")

    def init(rng: PRNGKey) -> Params:
        return init_transformer(
            rng, vocab, seq_len, width, depth, scan_layers=scan_layers
        )

    def apply(
        params: Params, x: jax.Array, *, train: bool = False, rng=None
    ) -> jax.Array:
        # The loss reads the last position only, so the head (ln_f, the
        # width x vocab matmul, log-softmax) runs on [N, D], not [N, T, D].
        del train, rng
        return _head(params, _trunk(params, x, heads)[:, -1, :])

    return Model(
        name="transformer_lm_scan" if scan_layers else "transformer_lm",
        init=init,
        apply=apply,
        input_shape=(seq_len,),
        num_classes=vocab,
        token_stream=True,
    )


@register_model("transformer_lm_scan")
def transformer_lm_scan(**kwargs: Any) -> Model:
    """The scan-over-layers causal LM as its own zoo name, so every name-keyed
    surface (CLI ``--model``, ``run_experiment``, autotune fingerprints — the
    two layouts compile DIFFERENT programs and must never share a cache entry)
    addresses it directly."""
    kwargs.pop("scan_layers", None)
    return transformer_lm(scan_layers=True, **kwargs)


#: Flagship shapes for the evidence artifacts (runs/adapter_*): the factory is
#: the same, only the dims scale.  Listed here so the artifact generator, the
#: docs math, and the tests agree on one source.
FLAGSHIP_CONFIGS = {
    # name: (vocab, seq_len, width, depth, heads)
    "tiny": (DEFAULT_VOCAB, DEFAULT_SEQ_LEN, DEFAULT_WIDTH, DEFAULT_DEPTH, DEFAULT_HEADS),
    "small": (512, 64, 128, 4, 4),
    # ~4.5M params, CPU-trainable in minutes: the committed adapter-evidence
    # workload — wide enough that rank-16 adapters are >10x smaller than the
    # kernels they adapt (the wire-bytes headline needs the ratio, and tiny
    # kernels would hide it).
    "evidence": (1024, 64, 256, 4, 4),
    # ~124M params: the smallest config whose replicated f32 train state
    # (params + SGD momentum + a round's delta) crosses a 16 GiB v5e budget
    # only when stacked across resident clients — the mid rung of the docs math.
    "base": (8192, 128, 768, 12, 12),
    # ~1.21B params (4.8 GiB f32): params + momentum + one gathered copy +
    # one delta ≈ 19.4 GiB replicated — over a 16 GiB v5e HBM budget on its
    # own, which is what "the model axis is binding" means.  (CPU accounting;
    # the chip's reading is ≈ 19 B a parameter for plain SGD: PERF.md, the
    # nemotron-twotower-ctx-9l-xsilo-4 configuration.)
    "large": (32768, 256, 2048, 24, 16),
}


def flagship(name: str, scan_layers: bool = False) -> Model:
    """Build a named flagship config (see :data:`FLAGSHIP_CONFIGS`)."""
    vocab, seq_len, width, depth, heads = FLAGSHIP_CONFIGS[name]
    return transformer_lm(
        vocab=vocab, seq_len=seq_len, width=width, depth=depth, heads=heads,
        scan_layers=scan_layers,
    )
