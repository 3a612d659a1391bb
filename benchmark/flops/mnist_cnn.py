"""Operations of the MNIST CNN as a function of shapes (multiply-add = 2 operations).

Forward, per sample: conv1 26*26 outputs x 32 channels x 9 taps, conv2 24*24 x 64 x
(9*32), fc1 9216 x 128, fc2 128 x 10.  Training costs three times the forward pass
(forward, gradient to the inputs, gradient to the weights); the input gradient of
conv1 is not needed but is counted, as the usual 3x rule does.  Recomputation is not
counted.  Elementwise work (relu, pool, dropout, softmax) is left out: the count is
the matrix work the MXU is there for.
"""


def forward_flops_per_sample(model_kwargs):
    del model_kwargs
    conv1 = 26 * 26 * 32 * (3 * 3 * 1)
    conv2 = 24 * 24 * 64 * (3 * 3 * 32)
    fc1 = 9216 * 128
    fc2 = 128 * 10
    return 2 * (conv1 + conv2 + fc1 + fc2)


def train_flops_per_sample(model_kwargs):
    return 3 * forward_flops_per_sample(model_kwargs)


def param_count(model_kwargs):
    del model_kwargs
    return (9 * 32 + 32) + (9 * 32 * 64 + 64) + (9216 * 128 + 128) + (128 * 10 + 10)
