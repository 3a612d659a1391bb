"""Operations of the block-diffusion mixture-of-experts decoder as a function of shapes
(multiply-add = 2 operations).

A training step runs the layers over a DOUBLED stream, ``S = 2 L`` positions of a
sequence of ``L`` tokens (the clean text, then its noised copy).  Per stream position,
forward, a layer (``d`` the hidden width):

* the four attention projections ``d x (2 H hd + 2 H_kv hd)``;
* scores and their product with the values, ``4 H hd`` a SEEN (query, key) pair —
  :func:`seen_pairs`: with blocks of ``B`` a clean query sees ``(b + 1) B`` clean keys,
  a noised one ``b B`` clean keys and its own block's ``B`` noised ones, so ``L^2 + L B``
  of a sequence: the work the mathematics needs, whatever implements it (kernels that
  walk tiles of 512 and mask the tiles a block boundary cuts execute more than this);
* the router's ``d x experts``;
* the experts' three matrices on the rows a position is EXPECTED to land here under
  uniform routing, ``top_k * experts_held / experts`` (1.0 at 8, 16 of 128).

The head sees the noised half, ``L`` positions.  Training costs three times the forward
pass.  The recomputation of every layer in the backward pass is not counted.  Norms, the
rotation, the softmax, the noise, the dispatch and the embedding lookup are left out.

What the attention KERNELS execute is counted apart (:func:`attention_kernel_flops`, for
their share of the roofline), over the seen pairs alone.
"""

#: Times the program runs the forward kernel a layer and a training step: once (the
#: layer's checkpoint keeps the kernel's output and log-sum-exp).
FORWARD_KERNEL_EXECUTIONS = 1
#: Matrix products a block pair: scores and values forward; scores, dP, dV, dK, dQ backward.
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def stream_len(kw):
    """Positions the layers see a training step: the sequence and its noised copy."""
    return 2 * kw["seq_len"]


def seen_pairs(seq_len, block):
    """(query, key) pairs of one doubled stream that the block-diffusion mask lets
    through: ``L (L + B) / 2`` clean-clean, ``L (L - B) / 2`` noised-clean, ``L B``
    noised-noised."""
    return seq_len * seq_len + seq_len * block


def held_rows_per_token(kw):
    """Rows of expert product a stream position is expected to cost a layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def forward_flops_per_sample(kw):
    d = kw["width"]
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    a_position = (2 * d * (2 * q + 2 * kv) + 2 * d * kw["experts"]
                  + held_rows_per_token(kw) * 2 * 3 * d * kw["expert_width"])
    a_layer = stream_len(kw) * a_position + attention_kernel_flops(kw, backward=False)
    return kw["layers"] * a_layer + kw["seq_len"] * 2 * d * kw["vocab"]


def train_flops_per_sample(kw):
    """Three times the forward pass."""
    return 3 * forward_flops_per_sample(kw)


def attention_kernel_flops(kw, *, backward, windowed=False):
    """Operations ONE execution of one of ``ops.attention``'s kernels needs for one
    sequence: a layer's forward (2 products a pair) or backward (5), over the SEEN pairs
    alone.  ``windowed`` is the kernels' reader's keyword (a kernel named ``..._window``);
    no layer here has a window."""
    del windowed
    products = BACKWARD_PRODUCTS if backward else FORWARD_PRODUCTS
    return (kw["attn_heads"] * 2 * kw["head_dim"] * products
            * seen_pairs(kw["seq_len"], kw["block"]))


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
    layer = (2 * d + 2 * hd + d * (2 * q + 2 * kv) + d * kw["experts"]
             + kw["experts_held"] * 3 * d * kw["expert_width"])
    return 2 * kw["vocab"] * d + d + kw["layers"] * layer
