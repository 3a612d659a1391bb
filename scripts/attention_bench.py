"""Time ``ops.attention.causal_attention`` against the dense spelling on the chip at hand.

    python scripts/attention_bench.py [N H T hd]      (default: 4 12 1024 64, bfloat16)

Prints one JSON line a variant: forward and forward+backward milliseconds a *layer*,
median of ``REPS`` calls after a warm-up.  A call is a ``lax.scan`` over ``LAYERS`` layers
that attend and nothing else, as the scanned model nests it: one dispatch a call, and the
backward reads what the scan stacked for it (the dense form's ``[N, H, T, T]`` residuals
included).  Needs a TPU (``utils.platform.require_tpu``).
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/scripts/", 1)[0])
from nanofed_tpu.ops.attention import causal_attention, dense_causal_attention  # noqa: E402
from nanofed_tpu.utils.platform import require_tpu  # noqa: E402

REPS = 10
LAYERS = 12


def stack(attend):
    """``x -> scan over LAYERS of x + attend(x, x, x)``, summed against ``w``."""
    def run(x, w):
        layer = lambda h, _: ((h + attend(h, h, h)) * 0.5, None)
        out = jax.lax.scan(layer, x, None, length=LAYERS)[0]
        return (out.astype(jnp.float32) * w).sum()
    return run


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def main() -> None:
    require_tpu()
    shape = tuple(int(a) for a in sys.argv[1:5]) or (4, 12, 1024, 64)
    x, w = (jax.random.normal(k, shape, jnp.bfloat16) for k in jax.random.split(jax.random.key(0)))
    variants = {"dense": dense_causal_attention}
    for block in (128, 256, 512):
        if shape[2] % block == 0:
            variants[f"block{block}"] = lambda q, k, v, b=block: causal_attention(
                q, k, v, block=b, interpret=False)
    for name, fn in variants.items():
        fwd, both = jax.jit(stack(fn)), jax.jit(jax.grad(stack(fn)))
        try:
            print(json.dumps({"variant": name, "shape": shape,
                              "fwd_ms_a_layer": timed(fwd, x, w) / LAYERS,
                              "fwd_bwd_ms_a_layer": timed(both, x, w) / LAYERS}), flush=True)
        except Exception as e:  # a variant the compiler refuses is a result too
            print(json.dumps({"variant": name, "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
