"""Plain reference of the hybrid decoder the zoo calls ``hybrid_lm``: the causal tower of
a ``nemotron_h`` model, layers given by a pattern string (``M`` Mamba-2 mixer, ``E``
mixture-of-experts feed-forward, ``*`` grouped-query attention).

Pre-norm residual stack ``x <- x + Mixer_l(RMSNorm_l(x))``, final RMSNorm, untied head
with no bias; log-probabilities of the next token at the LAST position only, which is
where the repo's token-stream pipeline puts the loss.  Written for reading, not speed:

* the Mamba-2 recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t`` runs step by step, one token at a time (the program
  evaluates it by chunks);
* every held expert's product is computed densely over all tokens and weighted by the
  router's gate, zero where the expert was not picked (the program sorts by expert);
* the router scores all ``experts``, picks ``top_k`` and normalises over all picks as
  published; only experts ``first_expert .. first_expert + experts_held`` live here, and
  what the absent ones would add is left out (the guide's expert-parallel cut);
* attention has no positional term, as in the ``nemotron_h`` modelling code.

Layers of one kind are stacked on a leading axis (``[layers of that kind, ...]``).
Each layer is rematerialized and attention goes by query blocks, so that a float32
round at the published widths fits one chip.  Imports nothing of the program.

``q`` rounds a matmul operand to the precision under test and returns float32.  The
router is float32 in the configuration's stated precision, so it is not rounded.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

TOKEN_STREAM = True

# Published initialisation ranges of the Mamba-2 mixer (config.json: time_step_min,
# time_step_max, time_step_floor; A drawn in [1, 16]).
DT_MIN, DT_MAX, DT_FLOOR, A_RANGE = 0.001, 0.1, 1e-4, (1.0, 16.0)
QUERY_BLOCK = 256
HIGHEST = lax.Precision.HIGHEST


def sizes(kw):
    """Derived sizes of the Mamba-2 mixer: inner width, conv channels, in-projection."""
    d_in = kw["mamba_heads"] * kw["mamba_head_dim"]
    conv = d_in + 2 * kw["ssm_groups"] * kw["ssm_state"]
    return d_in, conv, d_in + conv + kw["mamba_heads"]


def init_params(key, kw):
    """Weights from the seed: N(0, 0.02) matrices and embeddings, the four projections
    back into the residual stream scaled by 1/sqrt(layers held)
    (``rescale_prenorm_residual``), PyTorch's default depthwise-conv range,
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a log-uniform step in
    [DT_MIN, DT_MAX] floored at DT_FLOOR, ``D = 1``, norms 1."""
    d, vocab, pattern = kw["width"], kw["vocab"], kw["pattern"]
    n_m, n_e, n_a = pattern.count("M"), pattern.count("E"), pattern.count("*")
    heads, groups, state, k_conv = kw["mamba_heads"], kw["ssm_groups"], kw["ssm_state"], kw["conv_kernel"]
    d_in, conv, proj = sizes(kw)
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    held, f, fs = kw["experts_held"], kw["expert_width"], kw["shared_width"]
    resid = 1.0 / math.sqrt(len(pattern))
    k = jax.random.split(key, 16)
    normal = lambda kk, *shape, scale=1.0: 0.02 * scale * jax.random.normal(kk, shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    bound = 1.0 / math.sqrt(k_conv)
    dt = jnp.exp(jax.random.uniform(k[5], (n_m, heads), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        "embed": normal(k[0], vocab, d),
        "head": normal(k[1], d, vocab),
        "norm_f": ones(d),
        "mamba": {
            "norm": ones(n_m, d),
            "in_proj": normal(k[2], n_m, d, proj),
            "conv_w": jax.random.uniform(k[3], (n_m, k_conv, conv), jnp.float32, -bound, bound),
            "conv_b": jax.random.uniform(k[4], (n_m, conv), jnp.float32, -bound, bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k[6], (n_m, heads), jnp.float32, *A_RANGE)),
            "D": ones(n_m, heads),
            "gate_norm": ones(n_m, d_in),
            "out_proj": normal(k[7], n_m, d_in, d, scale=resid),
        },
        "attn": {
            "norm": ones(n_a, d),
            "wq": normal(k[8], n_a, d, hq * hd),
            "wk": normal(k[9], n_a, d, hkv * hd),
            "wv": normal(k[10], n_a, d, hkv * hd),
            "wo": normal(k[11], n_a, hq * hd, d, scale=resid),
        },
        "moe": {
            "norm": ones(n_e, d),
            "router": normal(k[12], n_e, d, kw["experts"]),
            "w_up": normal(k[13], n_e, held, d, f),
            "w_down": normal(k[14], n_e, held, f, d, scale=resid),
            "shared_up": normal(jax.random.fold_in(k[15], 0), n_e, d, fs),
            "shared_down": normal(jax.random.fold_in(k[15], 1), n_e, fs, d, scale=resid),
        },
    }


def _rms_norm(weight, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _recurrence(decay, xdt, b, c, chunk):
    """``y_t = S_t C_t`` with ``S_t = decay_t S_{t-1} + xdt_t (x) B_t``, one token at a
    time.  ``decay`` [B,T,G,Hg], ``xdt`` [B,T,G,Hg,P], ``b``/``c`` [B,T,G,N]: the heads
    come as ``G`` groups of ``Hg``, a group sharing its ``B`` and ``C``.  The steps are
    grouped by ``chunk`` only so that the backward pass keeps one state a group of
    steps and recomputes inside it."""
    n, t = xdt.shape[:2]
    time_major = lambda a: jnp.moveaxis(a, 1, 0).reshape(t // chunk, chunk, n, *a.shape[2:])

    def step(state, inp):
        decay_t, xdt_t, b_t, c_t = inp
        state = decay_t[..., None, None] * state + xdt_t[..., None] * b_t[:, :, None, None, :]
        return state, jnp.einsum("bghpn,bgn->bghp", state, c_t, precision=HIGHEST)

    group = jax.checkpoint(lambda state, inp: lax.scan(step, state, inp))
    zero = jnp.zeros((*xdt.shape[:1], *xdt.shape[2:], b.shape[-1]), jnp.float32)
    _, y = lax.scan(group, zero, tuple(map(time_major, (decay, xdt, b, c))))
    return jnp.moveaxis(y.reshape(t, n, *xdt.shape[2:]), 0, 1)


def _mamba(p, u, kw, q):
    n, t, _ = u.shape
    heads, hp, groups, state = kw["mamba_heads"], kw["mamba_head_dim"], kw["ssm_groups"], kw["ssm_state"]
    d_in, conv, _ = sizes(kw)
    zxbcdt = q(u) @ q(p["in_proj"])
    z, xbc, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv], zxbcdt[..., d_in + conv:]
    k_conv = kw["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k_conv - 1, 0), (0, 0)))
    xbc = sum(padded[:, k:k + t] * p["conv_w"][k] for k in range(k_conv)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    grouped = (groups, heads // groups)
    x = xbc[..., :d_in].reshape(n, t, *grouped, hp)
    b = xbc[..., d_in:d_in + groups * state].reshape(n, t, groups, state)
    c = xbc[..., d_in + groups * state:].reshape(n, t, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(n, t, *grouped)
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]).reshape(grouped))
    y = _recurrence(decay, q(x * dt[..., None]), q(b), q(c), min(kw["chunk"], t))
    y = (y + p["D"].reshape(*grouped, 1) * x).reshape(n, t, d_in) * jax.nn.silu(z)
    y = _rms_norm(1.0, y.reshape(n, t, groups, d_in // groups), kw["eps"]).reshape(n, t, d_in)
    return q(y * p["gate_norm"]) @ q(p["out_proj"])


def _attention(p, x, kw, q):
    n, t, _ = x.shape
    hq, hkv, hd = kw["attn_heads"], kw["kv_heads"], kw["head_dim"]
    qh = (q(x) @ q(p["wq"])).reshape(n, t, hkv, hq // hkv, hd)
    kh = (q(x) @ q(p["wk"])).reshape(n, t, hkv, hd)
    vh = (q(x) @ q(p["wv"])).reshape(n, t, hkv, hd)
    block = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q(q_blk), q(kh)) / math.sqrt(hd)
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", q(att), q(vh))

    blocks = jnp.moveaxis(qh.reshape(n, t // block, block, hkv, hq // hkv, hd), 1, 0)
    out = lax.map(one_block, (blocks, jnp.arange(t // block) * block))
    return q(jnp.moveaxis(out, 0, 1).reshape(n, t, hq * hd)) @ q(p["wo"])


def gates(router, x, kw):
    """``[..., experts]``: the weight each expert's output gets, zero where not picked.
    Sigmoid scores in float32, the ``top_k`` largest, normalised over the picks
    (``norm_topk_prob``) and scaled (``routed_scaling_factor``).  The balancing bias
    that the published router adds before picking is held at zero."""
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router, precision=HIGHEST))
    top, picks = lax.top_k(scores, kw["top_k"])
    weight = kw["routed_scale"] * top / (top.sum(axis=-1, keepdims=True) + 1e-20)
    return (jax.nn.one_hot(picks, kw["experts"], dtype=jnp.float32) * weight[..., None]).sum(axis=-2)


def routed_experts(p, x, kw, q, first, held):
    """The part of an expert layer's routed output that experts ``first .. first+held``
    give; ``p["w_up"]`` / ``p["w_down"]`` hold exactly those."""
    gate = gates(p["router"], x, kw)[..., first:first + held]
    out = jnp.zeros_like(x)
    for e in range(held):
        h = _relu2(q(x) @ q(p["w_up"][e]))
        out = out + gate[..., e:e + 1] * (q(h) @ q(p["w_down"][e]))
    return out


def shared_expert(p, x, q):
    return q(_relu2(q(x) @ q(p["shared_up"]))) @ q(p["shared_down"])


def _experts(p, x, kw, q):
    return routed_experts(p, x, kw, q, kw["first_expert"], kw["experts_held"]) + shared_expert(p, x, q)


MIXERS = {"M": ("mamba", _mamba), "*": ("attn", _attention), "E": ("moe", _experts)}


def hidden_states(params, tokens, kw, q=lambda t: t):
    """``[N, T, width]`` after the last layer, before the final norm."""
    x = params["embed"][tokens]
    seen = {kind: 0 for kind in MIXERS}
    for letter in kw["pattern"]:
        kind, mixer = MIXERS[letter]
        p = jax.tree.map(lambda leaf: leaf[seen[letter]], params[kind])
        seen[letter] += 1
        layer = jax.checkpoint(
            lambda p, x, mixer=mixer: x + mixer(p, _rms_norm(p["norm"], x, kw["eps"]), kw, q))
        x = layer(p, x)
    return x


def log_probs(params, tokens, key, kw, q=lambda t: t):
    """``[N, vocab]`` next-token log-probabilities at the last position.  ``key`` is
    unused: the model has no dropout."""
    del key
    x = hidden_states(params, tokens, kw, q)[:, -1, :]
    return jax.nn.log_softmax(q(_rms_norm(params["norm_f"], x, kw["eps"])) @ q(params["head"]))
