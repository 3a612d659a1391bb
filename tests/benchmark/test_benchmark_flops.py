"""The operation counts against hand counts."""

import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
GPT2 = {"vocab": 50257, "seq_len": 1024, "width": 768, "depth": 12, "heads": 12}


def _load(family):
    spec = importlib.util.spec_from_file_location(family, REPO / "benchmark" / "flops" / f"{family}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("family,kwargs,samples,want,rel", [
    # 1000 clients x 60 samples x 2 epochs: the 8.63 TFLOP a round of PERF.md / bench.py.
    ("mnist_cnn", {}, 1000 * 60 * 2, 8.63e12, 1e-3),
    # 6 x 84.9M block parameters x 1024 tokens + 1.2e11 of full-square attention.
    ("transformer_lm", GPT2, 1, 6.4e11, 5e-3),
    ("transformer_lm", GPT2, 8 * 16, 8.17e13, 1e-3),
])
def test_train_flops_match_hand_counts(family, kwargs, samples, want, rel):
    got = _load(family).train_flops_per_sample(kwargs) * samples
    assert abs(got - want) / want < rel


def test_cnn_flops_by_layer():
    per_sample = 2 * (26 * 26 * 32 * 9 + 24 * 24 * 64 * 288 + 9216 * 128 + 1280)
    assert _load("mnist_cnn").forward_flops_per_sample({}) == per_sample == 23_984_896


@pytest.mark.parametrize("family,kwargs,want", [
    ("mnist_cnn", {}, 1_199_882),
    ("transformer_lm", GPT2, 163_087_441),
])
def test_param_counts_match_the_zoo(family, kwargs, want):
    import jax

    from nanofed_tpu.models import get_model

    assert _load(family).param_count(kwargs) == want
    factory = {"mnist_cnn": "mnist_cnn", "transformer_lm": "transformer_lm_scan"}[family]
    tree = jax.eval_shape(get_model(factory, **kwargs).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == want
