"""Operations of the indexed-attention / mixture-of-experts decoder as a function of shapes
(multiply-add = 2 operations).

Per token, forward, a layer (``d`` the hidden width):

* the four attention projections ``d x (2 H hd + 2 H_kv hd)``;
* the indexer's three projections ``d x (J dI + dI + J)``, and its scores, ``2 J dI`` a
  CAUSAL (query, key) pair (``T (T + 1) / 2`` a sequence: every key behind a query is
  scored before any is picked);
* scores and their product with the values, ``4 H hd`` a KEPT pair — :func:`kept_pairs`:
  ``topk (topk + 1) / 2 + (T - topk) topk`` of a sequence where ``topk < T``, else the
  causal pairs: the work the mathematics needs, whatever implements it (a kernel that
  visits every causal block and masks executes more than this; one that skipped or
  gathered would be measured by the same count);
* the router's ``d x experts``;
* the experts' three matrices on the rows a token is EXPECTED to land here under
  uniform routing, ``top_k * experts_held / experts`` (1.0 at 8, 16 of 128).

The head sees the last position only.  Training costs three times the forward pass but
for the indexer, whose projections and scores count once: the pick is a constant of the
backward pass and the indexer takes no gradient.  The recomputation of every layer in
the backward pass is not counted.  Norms, the rotation, the softmax, the selection, the
dispatch and the embedding lookup are left out.

What the attention KERNELS execute is counted apart (:func:`attention_kernel_flops`, for
their share of the roofline), over the kept pairs alone.
"""

#: Times the program runs the forward kernel a layer and a training step: once (the
#: layer's checkpoint keeps the kernel's output and log-sum-exp).
FORWARD_KERNEL_EXECUTIONS = 1
#: Matrix products a block pair: scores and values forward; scores, dP, dV, dK, dQ backward.
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def kept_pairs(seq_len, topk):
    """(query, key) pairs of one sequence that the pick lets through: every causal pair of
    the first ``topk`` queries, ``topk`` a query after them."""
    if topk >= seq_len:
        return causal_pairs(seq_len)
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def held_rows_per_token(kw):
    """Rows of expert product a token is expected to cost a layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def indexer_flops_per_sample(kw):
    """One layer's indexer on one sequence, forward (it has no backward): the three
    projections on every token and the scores of every causal pair."""
    d, t, heads, dim = kw["width"], kw["seq_len"], kw["index_heads"], kw["index_dim"]
    return t * 2 * d * (heads * dim + dim + heads) + 2 * heads * dim * causal_pairs(t)


def forward_flops_per_sample(kw):
    d, t = kw["width"], kw["seq_len"]
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    a_token = (2 * d * (2 * q + 2 * kv) + 2 * d * kw["experts"]
               + held_rows_per_token(kw) * 2 * 3 * d * kw["expert_width"])
    a_layer = (t * a_token + indexer_flops_per_sample(kw)
               + attention_kernel_flops(kw, backward=False))
    return kw["layers"] * a_layer + 2 * d * kw["vocab"]


def train_flops_per_sample(kw):
    """Three times the forward pass, the indexer counted once."""
    return 3 * forward_flops_per_sample(kw) - 2 * kw["layers"] * indexer_flops_per_sample(kw)


def attention_kernel_flops(kw, *, backward, windowed=False):
    """Operations ONE execution of one of ``ops.attention``'s kernels needs for one
    sequence: a layer's forward (2 products a pair) or backward (5), over the KEPT pairs
    alone.  ``windowed`` is the kernels' reader's keyword (a kernel named ``..._window``);
    no layer here has a window."""
    del windowed
    products = BACKWARD_PRODUCTS if backward else FORWARD_PRODUCTS
    pairs = kept_pairs(kw["seq_len"], kw["index_topk"])
    return kw["attn_heads"] * 2 * kw["head_dim"] * products * pairs


def samples_per_round(fed):
    """Sequences every kernel of the round program sees a round: each silo's, each epoch."""
    return fed["num_clients"] * fed["samples_per_client"] * fed["local_epochs"]


def attention_kernel_flops_per_round(kw, fed):
    """... and what all the kernels' executions of one round need: every layer's forward
    as often as the program runs it, its backward once, on every sequence."""
    a_layer = (FORWARD_KERNEL_EXECUTIONS * attention_kernel_flops(kw, backward=False)
               + attention_kernel_flops(kw, backward=True))
    return samples_per_round(fed) * kw["layers"] * a_layer


def param_count(kw):
    d, hd = kw["width"], kw["head_dim"]
    q, kv = kw["attn_heads"] * hd, kw["kv_heads"] * hd
    indexer = d * (kw["index_heads"] * kw["index_dim"] + kw["index_dim"] + kw["index_heads"])
    layer = (2 * d + 2 * hd + d * (2 * q + 2 * kv) + indexer + d * kw["experts"]
             + kw["experts_held"] * 3 * d * kw["expert_width"])
    return 2 * kw["vocab"] * d + d + kw["layers"] * layer
