"""Operations of the windowed / full mixture-of-experts decoder as a function of shapes
(multiply-add = 2 operations).

Per token, forward, a layer (``d`` the hidden width):

* the four projections ``d x (2 H hd + 2 H_kv hd)``;
* scores and their product with the values, ``4 H hd`` a (query, key) pair the mask
  lets through — :func:`attended_pairs`: ``T (T + 1) / 2`` of a sequence under the causal
  mask alone, ``W (W + 1) / 2 + (T - W) W`` under a window of ``W < T``; the window
  layers' pairs, not the square;
* the router's ``d x experts``;
* the experts' three matrices on the rows a token is EXPECTED to land here under
  uniform routing, ``top_k * experts_held / experts`` (1.5 at 6, 16 of 64).

The head sees the last position only.  Training costs three times the forward pass; the
recomputation of every layer in the backward pass is not counted.  Norms, the rotation,
the softmax, the dispatch and the embedding lookup are left out.

What the attention KERNELS execute is counted apart (:func:`attention_kernel_flops_per_round`,
for their share of the roofline): every execution the program
makes counts there, a rerun included (:data:`FORWARD_KERNEL_EXECUTIONS`), since the
device spends the time.
"""

#: Times the program runs the forward kernel a layer and a training step: once, in the
#: forward pass.  The layer's checkpoint keeps the kernel's output and log-sum-exp, so
#: the backward pass's rematerialization does not rerun it (the program, since PR 34).
#: A test counts the ``pallas_call``s of a step.
FORWARD_KERNEL_EXECUTIONS = 1
#: Matrix products a block pair: scores and values forward; scores, dP, dV, dK, dQ backward.
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def attended_pairs(seq_len, window=None):
    """(query, key) pairs of one sequence and head that the mask lets through."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _pairs_by_layer(kw):
    return [attended_pairs(kw["seq_len"], kw["window"] if windowed else None)
            for windowed in kw["window_layout"]]


def held_rows_per_token(kw):
    """Rows of expert product a token is expected to cost a layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def forward_flops_per_sample(kw):
    d, t = kw["width"], kw["seq_len"]
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    a_token = (2 * d * (2 * q + 2 * kv) + 2 * d * kw["experts"]
               + held_rows_per_token(kw) * 2 * 3 * d * kw["expert_width"])
    attended = 2 * FORWARD_PRODUCTS * q * sum(_pairs_by_layer(kw))
    return len(kw["rope_layout"]) * t * a_token + attended + 2 * d * kw["vocab"]


def train_flops_per_sample(kw):
    return 3 * forward_flops_per_sample(kw)


def attention_kernel_flops(kw, *, backward, windowed):
    """Operations ONE execution of one of ``ops.attention``'s kernels needs for one
    sequence: a layer's forward (2 products a pair) or backward (5), over the unmasked
    pairs alone (a diagonal or trailing-edge block is computed whole: the count errs low)."""
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
        FORWARD_KERNEL_EXECUTIONS * attention_kernel_flops(kw, backward=False, windowed=bool(w))
        + attention_kernel_flops(kw, backward=True, windowed=bool(w))
        for w in kw["window_layout"])
    return samples_per_round(fed) * a_sample


def param_count(kw):
    d = kw["width"]
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    layer = (2 * d + d * (2 * q + 2 * kv) + d * kw["experts"]
             + kw["experts_held"] * 3 * d * kw["expert_width"])
    return 2 * kw["vocab"] * d + d + len(kw["rope_layout"]) * layer
