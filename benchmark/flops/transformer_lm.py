"""Operations of the causal LM as a function of shapes (multiply-add = 2 operations).

Per sequence of ``T`` tokens, forward: the block matrices (4 attention projections and
the two MLP matrices, ``12 * width^2`` weights a layer) see every token; attention
scores and their product with the values are computed as the full ``T x T`` square,
which is what the zoo model materializes (``2 * T * T * width`` multiply-adds a layer);
the head sees the last position only.  Training costs three times the forward pass.
Embedding lookups, LayerNorm, GELU and softmax are left out.
"""


def forward_flops_per_sample(model_kwargs):
    t, d = model_kwargs["seq_len"], model_kwargs["width"]
    depth, vocab = model_kwargs["depth"], model_kwargs["vocab"]
    block_matrices = depth * 12 * d * d * t
    attention = depth * 2 * t * t * d
    head = d * vocab
    return 2 * (block_matrices + attention + head)


def train_flops_per_sample(model_kwargs):
    return 3 * forward_flops_per_sample(model_kwargs)


def param_count(model_kwargs):
    t, d = model_kwargs["seq_len"], model_kwargs["width"]
    depth, vocab = model_kwargs["depth"], model_kwargs["vocab"]
    per_block = 4 * (d * d + d) + (4 * d * d + 4 * d) + (4 * d * d + d) + 4 * d
    return vocab * d + t * d + d * vocab + vocab + 2 * d + depth * per_block
