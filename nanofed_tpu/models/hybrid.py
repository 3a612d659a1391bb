"""Hybrid state-space / mixture-of-experts causal LM: a decoder whose layers are named
by a pattern string, one letter a layer — ``M`` a Mamba-2 mixer, ``E`` a
mixture-of-experts feed-forward, ``*`` grouped-query attention (the ``nemotron_h``
layout).  Pre-norm residual stack ``x <- x + Mixer_l(RMSNorm_l(x))``, final RMSNorm,
untied head, no bias but the convolution's, no positional term anywhere: the
state-space layers carry the order.

Like ``transformer_lm`` it drops into the standard federated pipeline: ``apply`` returns
next-token log-probabilities at the LAST position (``[N, vocab]``), the head running on
that position's hidden state alone.  Layers of one kind are stacked on a leading axis
(``params["mamba"]["in_proj"]`` is ``[M layers, width, ...]``, the routed experts
``[E layers, experts held, width, expert width]``), so the tree has the same few leaves
at any depth; the forward pass walks the pattern and takes each layer's slice.  Every
layer is rematerialized (``jax.checkpoint``): the backward pass keeps one ``[N, T,
width]`` activation a layer and recomputes inside it, but for what carries a name
(``models.experts.KEEP_NAMED_OUTPUTS``): an ``E`` layer's integer dispatch layout
(``src``, ``block_expert``, the trip count: under 0.3 MB), so its picks are laid out
once a step; an attention layer's kernel output and log-sum-exp (``ops.attention.KEPT``:
33.5 MB a step at the cell's shape), so its forward kernel runs once a step; a mixer
names nothing and keeps nothing.

**Mamba-2** (``ssm_mixer``): ``[z | xBC | dt] = u W_in``; a causal depthwise convolution
and SiLU on ``xBC``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
D x_t`` per head (heads in groups sharing ``B``/``C``), evaluated by chunks
(``ssm_scan``, :func:`ssd_chunked`): inside a chunk the quadratic masked form, between
chunks a ``lax.scan`` over the chunk states; then ``RMSNorm_grouped(y * silu(z)) W_out``.
Step sizes, decays and their cumulative sums stay float32 under mixed precision.

**Experts** (:func:`expert_layer`): the layer is TOLD which experts it holds
(``first_expert``, ``experts_held``).  The router (``moe_router``, float32) scores all
``experts`` with a sigmoid, picks ``top_k`` and normalises over all picks; picks that
land on held experts are laid out by expert in whole blocks of rows (``moe_dispatch``; a
block's rows follow from the experts' shape) and the held experts' squared-ReLU MLPs run
over the blocks in use (``moe_experts``: ``models.experts.held_experts``, the grouped
matmul every routing model of the zoo shares, kernels on the TPU and a loop elsewhere;
the blocks it runs follow the routing, so no capacity limit and no dropped token, and no
work on blocks nobody fills); the shared
expert (``moe_shared``) sees every token.  What experts held elsewhere would add is
left out — on one chip the layer runs without its exchange, and a sum over all the
shares, the shared expert counted once, is the uncut layer (tests).  The layer reports
two counters (:data:`COUNTERS`) through ``apply.with_counters``.

**Attention** (``gqa_attention``): grouped-query, causal, no rotary.  From 512 positions
in whole blocks (``ops.attention.engages``) the attention proper is ``ops.attention``'s
two kernels, as in the zoo's four other decoders: the causal block pairs alone, float32
scores and softmax statistics in VMEM, no copy of a group's keys and values; elsewhere
the dense full-square spelling (``dense_causal_attention``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model
from nanofed_tpu.models.decoder import language_model, rms_norm, run_layers
from nanofed_tpu.models.experts import COUNTERS as EXPERT_COUNTERS
from nanofed_tpu.models.experts import RELU2, check_held, held_experts, sigmoid_route
from nanofed_tpu.nn import embed_rows
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention, engages

#: What ``apply.with_counters`` reports beside the log-probabilities, each the mean over
#: the ``E`` layers of one batch: the share of all picks that landed on held experts, and
#: the held experts' largest token count over their mean (1.0 is even).
COUNTERS = EXPERT_COUNTERS[:2]

# Initialisation ranges of the Mamba-2 mixer: step sizes log-uniform in [DT_MIN, DT_MAX],
# floored; A uniform in A_RANGE.
DT_MIN, DT_MAX, DT_FLOOR, A_RANGE = 0.001, 0.1, 1e-4, (1.0, 16.0)

_F32 = jnp.float32


def mamba_sizes(mamba_heads: int, mamba_head_dim: int, ssm_groups: int, ssm_state: int):
    """``(inner width, convolved channels, in-projection width)`` of the mixer."""
    d_in = mamba_heads * mamba_head_dim
    conv = d_in + 2 * ssm_groups * ssm_state
    return d_in, conv, d_in + conv + mamba_heads


def init_hybrid(rng: PRNGKey, *, vocab, width, pattern, mamba_heads, mamba_head_dim,
                ssm_groups, ssm_state, conv_kernel, attn_heads, kv_heads, head_dim,
                experts, experts_held, expert_width, shared_width, **_) -> Params:
    """N(0, 0.02) matrices and embeddings, the projections back into the residual stream
    scaled by ``1/sqrt(depth)``, ``A_log = log U(1, 16)``, ``dt_bias`` the inverse
    softplus of a log-uniform step size, ``D = 1``, norms 1."""
    n_m, n_e, n_a = pattern.count("M"), pattern.count("E"), pattern.count("*")
    d_in, conv, proj = mamba_sizes(mamba_heads, mamba_head_dim, ssm_groups, ssm_state)
    resid = 1.0 / math.sqrt(len(pattern))
    k = jax.random.split(rng, 17)
    normal = lambda key, *shape, scale=1.0: 0.02 * scale * jax.random.normal(key, shape, _F32)
    ones = lambda *shape: jnp.ones(shape, _F32)
    bound = 1.0 / math.sqrt(conv_kernel)
    dt = jnp.exp(jax.random.uniform(k[5], (n_m, mamba_heads), _F32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        "embed": normal(k[0], vocab, width),
        "head": normal(k[1], width, vocab),
        "norm_f": ones(width),
        "mamba": {
            "norm": ones(n_m, width),
            "in_proj": normal(k[2], n_m, width, proj),
            "conv_w": jax.random.uniform(k[3], (n_m, conv_kernel, conv), _F32, -bound, bound),
            "conv_b": jax.random.uniform(k[4], (n_m, conv), _F32, -bound, bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k[6], (n_m, mamba_heads), _F32, *A_RANGE)),
            "D": ones(n_m, mamba_heads),
            "gate_norm": ones(n_m, d_in),
            "out_proj": normal(k[7], n_m, d_in, width, scale=resid),
        },
        "attn": {
            "norm": ones(n_a, width),
            "wq": normal(k[8], n_a, width, attn_heads * head_dim),
            "wk": normal(k[9], n_a, width, kv_heads * head_dim),
            "wv": normal(k[10], n_a, width, kv_heads * head_dim),
            "wo": normal(k[11], n_a, attn_heads * head_dim, width, scale=resid),
        },
        "moe": {
            "norm": ones(n_e, width),
            "router": normal(k[12], n_e, width, experts),
            "w_up": normal(k[13], n_e, experts_held, width, expert_width),
            "w_down": normal(k[14], n_e, experts_held, expert_width, width, scale=resid),
            "shared_up": normal(k[15], n_e, width, shared_width),
            "shared_down": normal(k[16], n_e, shared_width, width, scale=resid),
        },
    }


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, da, b, c, chunk: int) -> jax.Array:
    """``y_t = S_t C_t`` with ``S_t = exp(da_t) S_{t-1} + dt_t x_t (x) B_t`` by chunks.

    ``x`` [N,T,G,Hg,P] (heads as ``G`` groups of ``Hg``), ``dt``/``da`` [N,T,G,Hg]
    float32 (``da = dt * A <= 0``), ``b``/``c`` [N,T,G,S].  Inside a chunk of ``L``
    tokens the output is a masked ``L x L`` product (``(C B^T) * decay``), across chunks
    the ``[P, S]`` states follow a recurrence under ``lax.scan``; ``T`` is a multiple of
    ``L``.  Matrix products take operands in ``x.dtype`` and accumulate in float32."""
    n, t, g, hg, p = x.shape
    nc, dtype = t // chunk, x.dtype
    split = lambda a: a.reshape(n, nc, chunk, *a.shape[2:])
    x, b, c = split(x), split(b), split(c)
    # [N, nc, G, Hg, L]: within-chunk cumulative log-decay, and the step sizes.
    cum = jnp.cumsum(jnp.moveaxis(split(da), 2, -1), axis=-1)
    dt = jnp.moveaxis(split(dt), 2, -1)
    # Inside the chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s.
    cb = jnp.einsum("nclgs,ncmgs->ncglm", c, b, preferred_element_type=_F32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mixed = (cb[:, :, :, None] * decay * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("ncghlm,ncmghp->nclghp", mixed, x, preferred_element_type=_F32)
    # Each chunk's own contribution to the state at its end ...
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum) * dt, -1, 2)[..., None]
    own = jnp.einsum("ncmghp,ncmgs->ncghps", (x * to_end).astype(dtype), b,
                     preferred_element_type=_F32)
    # ... and the state each chunk starts from: a recurrence over chunks.
    chunk_decay = jnp.exp(cum[..., -1])

    def carry_on(state, inp):
        decay_c, own_c = inp
        return decay_c[..., None, None] * state + own_c, state

    _, entering = lax.scan(carry_on, jnp.zeros_like(own[:, 0]),
                           (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(own, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1).astype(dtype)
    carried = jnp.einsum("nclgs,ncghps->nclghp", c, entering, preferred_element_type=_F32)
    y = y + carried * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(n, t, g, hg, p).astype(dtype)


def mamba_mixer(p: Params, u: jax.Array, cfg: dict) -> jax.Array:
    n, t, _ = u.shape
    heads, hp, groups, state = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                                cfg["ssm_groups"], cfg["ssm_state"])
    d_in, conv, _ = mamba_sizes(heads, hp, groups, state)
    grouped = (groups, heads // groups)
    with jax.named_scope("ssm_mixer"):
        zxbcdt = u @ p["in_proj"]
        z, xbc, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv], zxbcdt[..., d_in + conv:]
        k_conv = cfg["conv_kernel"]
        padded = jnp.pad(xbc, ((0, 0), (k_conv - 1, 0), (0, 0)))
        xbc = sum(padded[:, k:k + t] * p["conv_w"][k] for k in range(k_conv)) + p["conv_b"]
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :d_in].reshape(n, t, *grouped, hp)
        b = xbc[..., d_in:d_in + groups * state].reshape(n, t, groups, state)
        c = xbc[..., d_in + groups * state:].reshape(n, t, groups, state)
        dt = jax.nn.softplus(dt.astype(_F32) + p["dt_bias"].astype(_F32)).reshape(n, t, *grouped)
        da = dt * -jnp.exp(p["A_log"].astype(_F32)).reshape(grouped)
        with jax.named_scope("ssm_scan"):
            y = ssd_chunked(x, dt, da, b, c, cfg["chunk"])
        y = (y + p["D"].reshape(*grouped, 1) * x).reshape(n, t, d_in) * jax.nn.silu(z)
        y = rms_norm(jnp.ones((), _F32), y.reshape(n, t, groups, d_in // groups), cfg["eps"])
        return (y.reshape(n, t, d_in) * p["gate_norm"]) @ p["out_proj"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def gqa_attention(p: Params, x: jax.Array, cfg: dict) -> jax.Array:
    """Causal grouped-query attention with no positional term: ``attn_heads`` query
    heads share ``kv_heads`` keys and values, query head ``h`` reading head
    ``h // (attn_heads / kv_heads)``; ``ops.attention``'s kernels where they take the
    length, the dense spelling elsewhere."""
    n, t, _ = x.shape
    hq, hkv, hd = cfg["attn_heads"], cfg["kv_heads"], cfg["head_dim"]
    with jax.named_scope("gqa_attention"):
        q = (x @ p["wq"]).reshape(n, t, hq, hd)
        k = (x @ p["wk"]).reshape(n, t, hkv, hd)
        v = (x @ p["wv"]).reshape(n, t, hkv, hd)
        attend = causal_attention if engages(t) else dense_causal_attention
        out = attend(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
        return out.transpose(0, 2, 1, 3).reshape(n, t, hq * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# Experts
# ---------------------------------------------------------------------------


def routed_experts(p: Params, x: jax.Array, cfg: dict):
    """The held experts' part of the routed output for tokens ``x`` [n, d], and the two
    counters.  ``p["w_up"]``/``p["w_down"]`` hold experts ``first_expert ..
    first_expert + experts_held`` of the ``experts`` the router scores.  Dispatch and the
    block loop are the zoo's shared ones (``models.experts``), with the squared ReLU; the
    router is the zoo's sigmoid one with no selection bias (the published router adds a
    balancing bias before picking; this model has no such leaf, which is a bias of zero.
    ``latent_moe`` carries one)."""
    with jax.named_scope("moe_router"):
        picks, weights = sigmoid_route(p["router"], x, cfg["top_k"], cfg["routed_scale"])
    out, counted = held_experts(x, picks, weights, p["w_up"], p["w_down"],
                                first_expert=cfg["first_expert"],
                                activation=RELU2)
    return out, counted[:len(COUNTERS)]


def expert_layer(p: Params, x: jax.Array, cfg: dict):
    """``(routed part of the held experts + shared expert, counters)`` for ``x`` [N,T,d]."""
    n, t, d = x.shape
    tokens = x.reshape(n * t, d)
    routed, counters = routed_experts(p, tokens, cfg)
    with jax.named_scope("moe_shared"):
        shared = relu2(tokens @ p["shared_up"]) @ p["shared_down"]
    return (routed + shared).reshape(n, t, d), counters


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

#: Pattern letter -> (the subtree of ``params`` its layers are stacked in, the mixer).
_MIXERS = {"M": ("mamba", mamba_mixer), "*": ("attn", gqa_attention), "E": ("moe", expert_layer)}


def residual_layer(p: Params, x: jax.Array, cfg: dict, *, mixer):
    """``(x + mixer(RMSNorm(x)), the layer's counters)``: one pre-norm layer of the stack;
    only an expert layer counts, the other mixers' counters are zero."""
    u = rms_norm(p["norm"], x, cfg["eps"])
    if mixer is expert_layer:
        mixed, counted = expert_layer(p, u, cfg)
    else:
        mixed, counted = mixer(p, u, cfg), jnp.zeros((len(COUNTERS),), _F32)
    return x + mixed, counted


def hidden_states(params: Params, tokens: jax.Array, cfg: dict):
    """``([N, T, width]`` after the last layer, counters summed over the ``E`` layers)."""
    x = embed_rows(params["embed"], tokens.astype(jnp.int32))
    seen = dict.fromkeys(_MIXERS, 0)
    plan = []
    for letter in cfg["pattern"]:
        kind, mixer = _MIXERS[letter]
        plan.append((partial(residual_layer, cfg=cfg, mixer=mixer), params[kind], seen[letter]))
        seen[letter] += 1
    return run_layers(x, plan, len(COUNTERS))


@register_model("hybrid_lm")
def hybrid_lm(
    vocab: int = 256,
    seq_len: int = 32,
    width: int = 64,
    pattern: str = "MEM*E",
    mamba_heads: int = 2,
    mamba_head_dim: int = 16,
    ssm_groups: int = 2,
    ssm_state: int = 16,
    conv_kernel: int = 4,
    chunk: int = 8,
    attn_heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    experts: int = 16,
    first_expert: int = 0,
    experts_held: int = 4,
    top_k: int = 3,
    expert_width: int = 48,
    shared_width: int = 96,
    routed_scale: float = 2.5,
    eps: float = 1e-5,
) -> Model:
    """The hybrid decoder as a zoo entry (defaults are test-sized).  ``experts`` is what
    the router scores; ``first_expert`` and ``experts_held`` say which of them this
    program holds (all of them: ``0`` and ``experts``)."""
    cfg = dict(locals())
    if set(pattern) - set(_MIXERS) or not pattern:
        raise ValueError(f"pattern {pattern!r}: one of {sorted(_MIXERS)} a layer")
    if seq_len % chunk:
        raise ValueError(f"seq_len {seq_len} must be a multiple of the scan's chunk {chunk}")
    if mamba_heads % ssm_groups or attn_heads % kv_heads:
        raise ValueError("mamba_heads must divide into ssm_groups, attn_heads into kv_heads")
    check_held(experts, first_expert, experts_held, top_k)

    def whole_chunks(x):
        if x.shape[1] % chunk:
            raise ValueError(f"sequence length {x.shape[1]} is not a multiple of chunk {chunk}")

    return language_model("hybrid_lm", cfg, init_hybrid, hidden_states, COUNTERS,
                          pattern.count("E"), check=whole_chunks)
