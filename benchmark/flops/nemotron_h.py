"""Operations of the hybrid decoder as a function of shapes (multiply-add = 2 operations).

Per token, forward, a layer of each kind (``d`` the hidden width):

* ``M``: the in-projection ``d x (2 d_in + 2 G S + H)`` and the out-projection
  ``d_in x d`` see every token; the scan, at chunk ``L``: inside a chunk ``C B^T`` per
  group (``L S G`` multiply-adds a token) and the masked product with ``x`` (``L d_in``),
  between chunks each chunk's state (``d_in S``) and the carried state's read-out
  (``d_in S``);
* ``*``: the four projections, and scores and their product with the values as the full
  ``T x T`` square the program computes band by band (``2 T heads head_dim`` a token);
* ``E``: the router's ``d x experts``, the shared expert's two matrices, and the routed
  experts' two matrices on the rows a token is EXPECTED to land here under uniform
  routing, ``top_k * experts_held / experts`` (0.375 at the published counts, 8 held).

The head sees the last position only.  Training costs three times the forward pass; the
recomputation of every layer in the backward pass is not counted.  Norms, the
convolution, activations, softmax, the dispatch and the embedding lookup are left out.
"""


def _mamba(kw):
    d, heads, p = kw["width"], kw["mamba_heads"], kw["mamba_head_dim"]
    g, s, chunk = kw["ssm_groups"], kw["ssm_state"], kw["chunk"]
    d_in = heads * p
    projections = d * (2 * d_in + 2 * g * s + heads) + d_in * d
    scan = chunk * s * g + chunk * d_in + 2 * d_in * s
    return projections + scan


def _attention(kw):
    d, t = kw["width"], kw["seq_len"]
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    return d * (2 * q + 2 * kv) + 2 * t * q


def held_rows_per_token(kw):
    """Rows of expert product a token is expected to cost an ``E`` layer here."""
    return kw["top_k"] * kw["experts_held"] / kw["experts"]


def _experts(kw):
    d = kw["width"]
    routed = held_rows_per_token(kw) * 2 * d * kw["expert_width"]
    return d * kw["experts"] + 2 * d * kw["shared_width"] + routed


def forward_flops_per_token(kw):
    pattern = kw["pattern"]
    return 2 * (pattern.count("M") * _mamba(kw) + pattern.count("*") * _attention(kw)
                + pattern.count("E") * _experts(kw))


def forward_flops_per_sample(kw):
    return kw["seq_len"] * forward_flops_per_token(kw) + 2 * kw["width"] * kw["vocab"]


def train_flops_per_sample(kw):
    return 3 * forward_flops_per_sample(kw)


def param_count(kw):
    d, pattern = kw["width"], kw["pattern"]
    heads, g, s = kw["mamba_heads"], kw["ssm_groups"], kw["ssm_state"]
    d_in = heads * kw["mamba_head_dim"]
    conv = d_in + 2 * g * s
    mamba = d + d * (d_in + conv + heads) + kw["conv_kernel"] * conv + conv + 3 * heads + d_in + d_in * d
    q, kv = kw["attn_heads"] * kw["head_dim"], kw["kv_heads"] * kw["head_dim"]
    attention = d + d * (q + 2 * kv) + q * d
    experts = (d + d * kw["experts"] + 2 * kw["experts_held"] * d * kw["expert_width"]
               + 2 * d * kw["shared_width"])
    return (2 * kw["vocab"] * d + d + pattern.count("M") * mamba
            + pattern.count("*") * attention + pattern.count("E") * experts)
