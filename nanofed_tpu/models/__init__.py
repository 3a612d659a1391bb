"""Model zoo (parity+: reference ships only ``MNISTModel``, ``nanofed/models/__init__.py``;
the ResNets serve the BASELINE.json benchmark configs)."""

from nanofed_tpu.models import (  # noqa: F401  (registry side effects)
    diffusion_moe,
    gated_moe,
    hybrid,
    indexed_moe,
    latent_moe,
    linear,
    mnist,
    moe_decoder,
    resnet,
    transformer,
)
from nanofed_tpu.models.base import Model, get_model, list_models, register_model
from nanofed_tpu.models.diffusion_moe import diffusion_moe_lm
from nanofed_tpu.models.gated_moe import gated_moe_lm
from nanofed_tpu.models.hybrid import hybrid_lm
from nanofed_tpu.models.indexed_moe import indexed_moe_lm
from nanofed_tpu.models.latent_moe import latent_moe_lm
from nanofed_tpu.models.mnist import mnist_cnn
from nanofed_tpu.models.moe_decoder import moe_decoder_lm
from nanofed_tpu.models.resnet import resnet8, resnet18
from nanofed_tpu.models.transformer import (
    stack_blocks,
    transformer_lm,
    transformer_lm_scan,
    unstack_blocks,
)

__all__ = [
    "Model",
    "get_model",
    "list_models",
    "register_model",
    "diffusion_moe_lm",
    "gated_moe_lm",
    "hybrid_lm",
    "indexed_moe_lm",
    "latent_moe_lm",
    "mnist_cnn",
    "moe_decoder_lm",
    "resnet8",
    "resnet18",
    "stack_blocks",
    "transformer_lm",
    "transformer_lm_scan",
    "unstack_blocks",
]
