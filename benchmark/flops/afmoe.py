"""Operations of the gated, sandwich-normed window/full decoder over sigmoid-routed
experts as a function of shapes (multiply-add = 2 operations).

Per token, forward (``d`` the hidden width):

* every layer's five projections, the output gate's among them, ``d x (3 H hd + 2 H_kv hd)``;
* scores and their product with the values, ``4 H hd`` a (query, key) pair the mask lets
  through (:func:`attended_pairs`: ``T (T + 1) / 2`` of a sequence in a full layer, ``W (W
  + 1) / 2 + (T - W) W`` under a sliding layer's window of ``W < T``: the needed pairs,
  not the blocks a kernel visits);
* a dense layer's gated MLP, ``3 d x dense_width``;
* an expert layer's router ``d x experts``, its shared expert ``3 d x shared_width``, and
  the routed experts' three matrices on the rows a token is EXPECTED to land here under
  uniform routing, ``top_k * experts_held / experts`` (0.5 at 8, 8 of 128).

The head sees the last position only.  Training costs three times the forward pass; the
recomputation of every layer in the backward pass, the gate's product among it, is not
counted.  Norms, the rotation, the softmax, the gate's sigmoid and multiply, the dispatch,
the embedding's lookup and scale are left out.

What the attention KERNELS execute is counted apart (:func:`attention_kernel_flops`, for
their share of the roofline), by the kernel's kind.
"""

#: Times the program runs the forward kernel a layer and a training step: once (the
#: layer's checkpoint keeps the kernel's output and log-sum-exp; the gate comes after
#: them and is what the backward pass computes again).  A test counts the
#: ``pallas_call``s of a step.
FORWARD_KERNEL_EXECUTIONS = 1
#: Matrix products a block pair: scores and values forward; scores, dP, dV, dK, dQ backward.
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def layers(kw):
    return len(kw["sliding_layout"])


def expert_layers(kw):
    return layers(kw) - kw["dense_layers"]


def attended_pairs(seq_len, window=None):
    """(query, key) pairs of one sequence and head that the mask lets through."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def held_rows_per_token(kw):
    """Rows of expert product a token is expected to cost an expert layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def projection_flops_per_token(kw):
    """A layer's ``W_q``, ``W_g``, ``W_o`` (``d x H hd`` each) and ``W_k``, ``W_v``."""
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    return 2 * kw["width"] * (3 * q + 2 * kv)


def by_part(kw):
    """Forward operations of one sequence by part of the model, the head aside."""
    d, t = kw["width"], kw["seq_len"]
    gate = 2 * d * kw["attn_heads"] * kw["head_dim"]
    return {
        "projections": t * layers(kw) * (projection_flops_per_token(kw) - gate),
        "gate": t * layers(kw) * gate,
        "attention": sum(attention_kernel_flops(kw, backward=False, windowed=bool(s))
                         for s in kw["sliding_layout"]),
        "dense_mlp": t * kw["dense_layers"] * 2 * 3 * d * kw["dense_width"],
        "shared": t * expert_layers(kw) * 2 * 3 * d * kw["shared_width"],
        "routed": t * expert_layers(kw) * held_rows_per_token(kw) * 2 * 3 * d * kw["expert_width"],
        "router": t * expert_layers(kw) * 2 * d * kw["experts"],
    }


def forward_flops_per_sample(kw):
    return sum(by_part(kw).values()) + 2 * kw["width"] * kw["vocab"]


def train_flops_per_sample(kw):
    return 3 * forward_flops_per_sample(kw)


def attention_kernel_flops(kw, *, backward, windowed):
    """Operations ONE execution of one of ``ops.attention``'s kernels needs for one
    sequence: a layer's forward (2 products a pair) or backward (5), over the unmasked
    pairs alone (a diagonal or trailing-edge block is computed whole: the count errs
    low).  ``windowed``: a sliding layer's kernel (``..._window``)."""
    products = BACKWARD_PRODUCTS if backward else FORWARD_PRODUCTS
    pairs = attended_pairs(kw["seq_len"], kw["window"] if windowed else None)
    return kw["attn_heads"] * 2 * kw["head_dim"] * products * pairs


def samples_per_round(fed):
    """Sequences every kernel of the round program sees a round: each silo's, each epoch."""
    return fed["num_clients"] * fed["samples_per_client"] * fed["local_epochs"]


def attention_kernel_flops_per_round(kw, fed):
    """... and what all the kernels' executions of one round need: every layer's forward
    as often as the program runs it, its backward once, on every sequence."""
    a_sample = sum(
        FORWARD_KERNEL_EXECUTIONS * attention_kernel_flops(kw, backward=False, windowed=bool(s))
        + attention_kernel_flops(kw, backward=True, windowed=bool(s))
        for s in kw["sliding_layout"])
    return samples_per_round(fed) * a_sample


def param_count(kw):
    d, hd = kw["width"], kw["head_dim"]
    q, kv = kw["attn_heads"] * hd, kw["kv_heads"] * hd
    attention = d * (3 * q + 2 * kv) + 4 * d + 2 * hd
    dense = attention + 3 * d * kw["dense_width"]
    expert = (attention + d * kw["experts"] + kw["experts"] + 3 * d * kw["shared_width"]
              + kw["experts_held"] * 3 * d * kw["expert_width"])
    return 2 * kw["vocab"] * d + d + kw["dense_layers"] * dense + expert_layers(kw) * expert
