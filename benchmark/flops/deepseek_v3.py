"""Operations of the latent-attention / mixture-of-experts decoder as a function of shapes
(multiply-add = 2 operations).

Per token, forward (``d`` the hidden width, ``H`` heads, score heads of ``nope + rope``
and value heads of ``value`` dimensions):

* every layer's attention projections: ``d x H (nope + rope)`` (queries), ``d x (rank +
  rope)`` (down to the latent and the shared rotary key), ``rank x H (nope + value)``
  (up to keys and values), ``H value x d`` (output);
* scores and their product with the values, ``2 H (nope + rope) + 2 H value`` a (query,
  key) pair under the causal mask — ``T (T + 1) / 2`` pairs a sequence, not the square;
* a dense layer's gated MLP, ``3 d x dense_width``;
* an expert layer's router ``d x experts``, its shared experts ``3 d x shared_width``, and
  the routed experts' three matrices on the rows a token is EXPECTED to land here under
  uniform routing, ``top_k * experts_held / experts`` (0.75 at 6, 8 of 64).

The head sees the last position only.  Training costs three times the forward pass; the
recomputation of every layer in the backward pass is not counted.  Norms, the rotation,
the softmax, the dispatch and the embedding lookup are left out.

What the attention KERNELS execute is counted apart (:func:`attention_kernel_flops`, for
their share of the roofline): every execution the program
makes counts there, a rerun included (:data:`FORWARD_KERNEL_EXECUTIONS`), since the
device spends the time.
"""

#: Times the program runs the forward kernel a layer and a training step: once, in the
#: forward pass.  The layer's checkpoint keeps the kernel's output and log-sum-exp, so
#: the backward pass's rematerialization does not rerun it (the program, since PR 34).
#: A test counts the ``pallas_call``s of a step.
FORWARD_KERNEL_EXECUTIONS = 1


def layers(kw):
    return kw["dense_layers"] + kw["expert_layers"]


def attended_pairs(seq_len):
    """(query, key) pairs of one sequence and head that the causal mask lets through."""
    return seq_len * (seq_len + 1) // 2


def held_rows_per_token(kw):
    """Rows of expert product a token is expected to cost an expert layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def projection_flops_per_token(kw):
    d, h, rank = kw["width"], kw["heads"], kw["latent_rank"]
    nope, rope, value = kw["nope_dim"], kw["rope_dim"], kw["value_dim"]
    return 2 * (d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + value) + h * value * d)


def forward_flops_per_sample(kw):
    d, t = kw["width"], kw["seq_len"]
    dense = 2 * 3 * d * kw["dense_width"]
    expert = (2 * d * kw["experts"] + 2 * 3 * d * kw["shared_width"]
              + held_rows_per_token(kw) * 2 * 3 * d * kw["expert_width"])
    a_token = (layers(kw) * projection_flops_per_token(kw)
               + kw["dense_layers"] * dense + kw["expert_layers"] * expert)
    attended = layers(kw) * attention_kernel_flops(kw, backward=False)
    return t * a_token + attended + 2 * d * kw["vocab"]


def train_flops_per_sample(kw):
    return 3 * forward_flops_per_sample(kw)


def attention_kernel_flops(kw, *, backward, windowed=False):
    """Operations ONE execution of one of ``ops.attention``'s kernels needs for one
    sequence: a layer's forward (scores over ``nope + rope``, values over ``value``: two
    products a pair) or backward (scores, ``dK``, ``dQ`` over ``nope + rope``; ``dP``,
    ``dV`` over ``value``), over the unmasked pairs alone (a diagonal block is computed
    whole: the count errs low).  ``windowed`` is the kernels' reader's keyword (a kernel
    named ``..._window``); no layer here has a window."""
    del windowed
    scores, values = kw["nope_dim"] + kw["rope_dim"], kw["value_dim"]
    a_pair = 2 * (3 * scores + 2 * values) if backward else 2 * (scores + values)
    return kw["heads"] * a_pair * attended_pairs(kw["seq_len"])


def samples_per_round(fed):
    """Sequences every kernel of the round program sees a round: each silo's, each epoch."""
    return fed["num_clients"] * fed["samples_per_client"] * fed["local_epochs"]


def attention_kernel_flops_per_round(kw, fed):
    """... and what all the kernels' executions of one round need: every layer's forward
    as often as the program runs it, its backward once, on every sequence."""
    a_layer = (FORWARD_KERNEL_EXECUTIONS * attention_kernel_flops(kw, backward=False)
               + attention_kernel_flops(kw, backward=True))
    return samples_per_round(fed) * layers(kw) * a_layer


def param_count(kw):
    d, h, rank = kw["width"], kw["heads"], kw["latent_rank"]
    nope, rope, value = kw["nope_dim"], kw["rope_dim"], kw["value_dim"]
    attention = (d * h * (nope + rope) + d * (rank + rope) + rank + rank * h * (nope + value)
                 + h * value * d + 2 * d)
    dense = attention + 3 * d * kw["dense_width"]
    expert = (attention + d * kw["experts"] + kw["experts"] + 3 * d * kw["shared_width"]
              + kw["experts_held"] * 3 * d * kw["expert_width"])
    return 2 * kw["vocab"] * d + d + kw["dense_layers"] * dense + kw["expert_layers"] * expert
