"""The attention kernels compiled ahead of time for a described ``v5e:2x2`` chip, at the
GPT-2 cell's shapes: what the TPU's compiler refuses (a tiling, a VMEM budget, a
transpose it cannot place) fails here, at no chip time.  Nothing runs: this says nothing
about values or times.  All such compiles live in this one file (one worker loads the
TPU's library, inside the fixture): the cell's training step is here too, for what the
compiler keeps of the MLP between its forward and its backward, the SmallThinker
cell's embedding gradient, for where the compiler places its accumulators, the
moonlight cell's kernels with score and value heads of different sizes, the keye cell's
kernels under a computed mask, and a rematerialized layer of each of the five decoders,
for what it launches twice and what it keeps (the trinity cell's: the windowed kernels with
eight query heads a key/value head, a 2048 window at 8192 positions; the sdar cell's: the
step of the OBJECTIVE the model carries, a doubled stream of 8192 positions under the
block-diffusion mask and the head over its 4096 noised positions); in those layers the
held experts' kernels (``ops.experts``) at the five decoders' widths, and alone at the
hybrid's, whose experts are 1856 wide: no whole lanes."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nanofed_tpu import nn
from nanofed_tpu.models import decoder, experts, get_model, transformer
from nanofed_tpu.ops import attention
from nanofed_tpu.ops import experts as expert_kernels
from nanofed_tpu.ops.attention import causal_attention
from nanofed_tpu.trainer.local import make_grad_fn

CELL = (4, 12, 1024, 64)  # gpt2-124m-xsilo-8: batch 4, 12 heads, 1024 positions of 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attend(q, k, v):
    return causal_attention(q, k, v, interpret=False)


def _loss(q, k, v):
    return _attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("shape,dtype,kernels", [
    (CELL, jnp.bfloat16, 1),
    ((2, 4, 2048, 128), jnp.bfloat16, 1),
    ((1, 2, 512, 64), jnp.float32, 1),
], ids=["gpt2-cell", "T2048-hd128", "float32"])
def test_forward_kernel_compiles(one_chip, shape, dtype, kernels):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(_attend).lower(x, x, x).compile().as_text()
    assert text.count("causal_attention_fwd") >= kernels
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype", [
    (CELL, jnp.bfloat16),
    ((2, 4, 2048, 128), jnp.bfloat16),
    ((1, 2, 512, 64), jnp.float32),
], ids=["gpt2-cell", "T2048-hd128", "float32"])
def test_backward_kernel_compiles(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(jax.grad(_loss, (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert "causal_attention_fwd" in text and "causal_attention_bwd" in text


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
def test_latent_head_sizes_compile_at_8192_positions(one_chip, backward):
    """The moonlight cell's shapes: 16 heads, 8192 positions, 192-wide ``q`` and ``K`` over a
    128-wide ``V`` (a 192-wide row is padded to 256 lanes: both kernels pass the default
    scoped VMEM there, and compile under ``WIDE_VMEM``)."""
    shaped = lambda hd: jax.ShapeDtypeStruct((1, 16, 8192, hd), jnp.bfloat16, sharding=one_chip)
    fn = jax.grad(_loss, (0, 1, 2)) if backward else _attend
    text = jax.jit(fn).lower(shaped(192), shaped(192), shaped(128)).compile().as_text()
    assert "causal_attention_bwd" in text if backward else "causal_attention_fwd" in text
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
def test_masked_kernels_compile_at_8192_positions(one_chip, backward):
    """The keye cell's shapes: 32 query heads over 4 key/value heads of 128, 8192
    positions, one ``int8 [8192, 8192]`` mask a sequence: a 4 MiB strip of it a grid step
    beside a head's ``K`` and ``V`` (forward) or ``q``, ``dO`` and float32 ``dQ``
    (backward) passes the default scoped VMEM; both compile under ``WIDE_VMEM``."""
    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    masked = lambda q, k, v, keep: causal_attention(q, k, v, keep=keep, interpret=False)
    loss = lambda q, k, v, keep: masked(q, k, v, keep).astype(jnp.float32).sum()
    fn = jax.grad(loss, (0, 1, 2)) if backward else masked
    text = jax.jit(fn).lower(shaped(1, 32, 8192, 128), shaped(1, 4, 8192, 128), shaped(1, 4, 8192, 128),
                             shaped(1, 8192, 8192, dtype=jnp.int8)).compile().as_text()
    assert ("causal_attention_bwd_keep" if backward else "causal_attention_fwd_keep") in text
    assert "tpu_custom_call" in text


def test_compiles_under_vmap_and_scan(one_chip):
    """The round program's nesting around the call: ``vmap`` over a chunk of one client,
    ``grad`` of a ``scan`` over layers."""
    def through(x):
        layer = lambda h, _: (h + _attend(h, h, h), None)
        return jax.lax.scan(layer, x, None, length=3)[0].astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, *CELL), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.vmap(jax.grad(through))).lower(x).compile().as_text()
    assert "causal_attention_bwd" in text


GPT2_BLOCKS = dict(seq_len=1024, width=768, depth=12, heads=12)  # the cell's, vocab aside
WIDE = "bf16[12,1,4,1024,3072]"  # a [4,1024,3072] array a layer, stacked by the scan


def _tail_as_it_stood(fc2, h):
    return nn.dense(fc2, jax.nn.gelu(h))


@pytest.fixture(scope="module")
def compiled_steps(one_chip):
    """The cell's gradient step (batch 4 of 1024 tokens through twelve scanned blocks,
    bfloat16 compute, one client under ``vmap``) compiled twice: as the model spells the
    MLP's tail, and with the line as it stood before it kept ``h`` alone."""
    m = get_model("transformer_lm_scan", vocab=256, **GPT2_BLOCKS)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(lambda a: shaped(a.shape, a.dtype),
                          jax.eval_shape(m.init, jax.random.key(0)))
    batch = (shaped((1, 4, 1024), jnp.int32), shaped((1, 4), jnp.int32),
             shaped((1, 4), jnp.float32))

    def compile_step():
        grad_fn = make_grad_fn(m.apply, compute_dtype="bfloat16")
        step = jax.vmap(lambda p, x, y, mask: grad_fn(p, x, y, mask, jax.random.key(0))[0],
                        in_axes=(None, 0, 0, 0))
        return jax.jit(step).lower(params, *batch).compile()

    now = compile_step()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "_mlp_tail", _tail_as_it_stood)
        stood = compile_step()
    return {"now": now, "stood": stood}


def _wide_buffers_carried(compiled) -> int:
    """Most ``WIDE`` buffers any loop of the program carries: the backward scan's tuple
    holds every stacked residual the forward scan wrote."""
    return max(line.split(" while(")[0].count(WIDE)
               for line in compiled.as_text().splitlines() if " while(" in line)


@pytest.mark.parametrize("which,buffers", [("now", 1), ("stood", 6)])
def test_mlp_residuals_in_the_compiled_step(compiled_steps, which, buffers):
    assert _wide_buffers_carried(compiled_steps[which]) == buffers


def test_backward_reruns_no_product(compiled_steps):
    """fc2's forward product is not computed again for the backward (its primal output
    is unused there): the step holds as many matrix products as it did."""
    products = {k: len(re.findall(r" (?:convolution|dot)\(", c.as_text()))
                for k, c in compiled_steps.items()}
    assert products["now"] == products["stood"] > 0


def test_temporaries_fall_by_a_gigabyte(compiled_steps):
    temp = {k: c.memory_analysis().temp_size_in_bytes for k, c in compiled_steps.items()}
    assert temp["stood"] - temp["now"] >= 1e9, temp


EMBED = (37984, 2560, 8192)  # smallthinker-21b-4l-xsilo-4: rows held, width, tokens a step


def _embed_gradient_text(one_chip) -> str:
    rows, width, tokens = EMBED
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(table, ids, weight):
        return (nn.embed_rows(table, ids) * weight).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss)).lower(
        shaped((rows, width), jnp.bfloat16), shaped((tokens,), jnp.int32),
        shaped((tokens, width), jnp.bfloat16)).compile().as_text()


def _scatter_results(text: str) -> list[str]:
    """Shape and layout of every scatter's result, as ``bf16[37984,1280]{1,0:T(8,128)(2,1)S(1)}``."""
    return re.findall(r"= (\w+\[[\d,]*\]\{[^}]*\}) scatter\(", text)


@pytest.mark.parametrize("budget,bands", [(nn.EMBED_BAND_BYTES, 4), (96 * 2**20, 2)],
                         ids=["the-budget", "96MiB"])
def test_embedding_gradient_accumulates_on_chip(one_chip, monkeypatch, budget, bands):
    """What ``nn.embed_rows`` is for: each band's accumulator is placed in the chip's
    on-chip memory (memory space 1), and no scatter accumulates into the whole table."""
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", budget)
    assert nn.embed_bands(*EMBED[:2], 2) == bands
    results = _scatter_results(_embed_gradient_text(one_chip))
    band = f"bf16[{EMBED[0]},{EMBED[1] // bands}]"
    assert results and all(r.startswith(band) and "S(1)" in r for r in results), results


def test_whole_table_accumulator_stays_in_hbm(one_chip, monkeypatch):
    """The reason for the bands: at one band the 194 MB accumulator gets no ``S(1)``.  If
    this fails the compiler places it on chip now, and the bands may no longer be needed."""
    monkeypatch.setattr(nn, "EMBED_BAND_BYTES", 2**40)
    results = _scatter_results(_embed_gradient_text(one_chip))
    whole = f"bf16[{EMBED[0]},{EMBED[1]}]"
    assert results and all(r.startswith(whole) and "S(1)" not in r for r in results), results


#: The five cells' layers at their published widths and 8192 positions, a small vocabulary
#: around them: ``(factory, kwargs, layers, bytes of the output and log-sum-exp a
#: layer keeps)``.  An expert layer keeps its dispatch's layout too: under 0.3 MB.
DECODERS = {
    "smallthinker": ("moe_decoder_lm", dict(
        vocab=1024, seq_len=8192, width=2560, rope_layout=[1], window_layout=[1], window=4096,
        rope_theta=1500000, attn_heads=28, kv_heads=4, head_dim=128, experts=64, first_expert=0,
        experts_held=16, top_k=6, expert_width=768, eps=1e-6), 1, 28 * 8192 * (128 * 2 + 4)),
    "moonlight": ("latent_moe_lm", dict(
        vocab=1024, seq_len=8192, width=2048, heads=16, latent_rank=512, nope_dim=128,
        rope_dim=64, value_dim=128, rope_theta=50000, dense_layers=1, dense_width=11264,
        expert_layers=1, experts=64, first_expert=0, experts_held=8, top_k=6, expert_width=1408,
        shared_width=2816, routed_scale=2.446, eps=1e-5), 2, 16 * 8192 * (128 * 2 + 4)),
    # ... and the pick: one int8 [8192, 8192] mask a layer.
    "keye": ("indexed_moe_lm", dict(
        vocab=1024, seq_len=8192, width=2048, layers=1, attn_heads=32, kv_heads=4, head_dim=128,
        rope_theta=1e7, rope_sections=[16, 24, 24], index_heads=16, index_dim=64,
        index_topk=2048, experts=128, first_expert=0, experts_held=16, top_k=8, expert_width=768,
        eps=1e-6), 1, 32 * 8192 * (128 * 2 + 4) + 8192 * 8192),
    # A dense sliding layer and a full expert layer: the window's kernels with 8 query heads
    # a key/value head, and the gate between the kept output and ``W_o``.
    "trinity": ("gated_moe_lm", dict(
        vocab=1024, seq_len=8192, width=2048, sliding_layout=[1, 0], window=2048, rope_theta=10000,
        attn_heads=32, kv_heads=4, head_dim=128, dense_layers=1, dense_width=6144, experts=128,
        first_expert=0, experts_held=8, top_k=8, expert_width=1024, shared_width=1024,
        routed_scale=2.826, eps=1e-5), 2, 32 * 8192 * (128 * 2 + 4)),
    # The objective's step: 4096 tokens are a stream of 8192 positions in two halves, the
    # kernels under the block-diffusion mask in blocks of 4, the head over the noised half.
    "sdar": ("diffusion_moe_lm", dict(
        vocab=1024, seq_len=4096, block=4, width=2048, layers=1, attn_heads=32, kv_heads=4,
        head_dim=128, rope_theta=1e6, experts=128, first_expert=0, experts_held=16, top_k=8,
        expert_width=768, eps=1e-6), 1, 32 * 8192 * (128 * 2 + 4)),
}
#: What buffer assignment may move for reasons of its own when the schedule changes (read
#: here: +11 MB on 60 MB kept and +0.5 MB on 68 MB kept).
SLACK = 16 * 2**20


@pytest.fixture(scope="module", params=list(DECODERS))
def decoder_steps(request, one_chip):
    """A decoder's gradient step (one client under ``vmap``, one sequence of 8192 tokens,
    bfloat16 compute) compiled twice: as the model rematerializes its layers, and under a
    plain ``jax.checkpoint``.  The kernels are compiled, not interpreted: this process
    sees the CPU, so the test says so in ``auto_interpret``'s place."""
    factory, kwargs, layers, kept_bytes = DECODERS[request.param]
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    batch = (shaped((1, 1, kwargs["seq_len"]), jnp.int32), shaped((1, 1), jnp.int32),
             shaped((1, 1), jnp.float32))

    def compile_step():
        m = get_model(factory, **kwargs)
        params = jax.tree.map(lambda a: shaped(a.shape, a.dtype),
                              jax.eval_shape(m.init, jax.random.key(0)))
        grad_fn = make_grad_fn(m.apply, compute_dtype="bfloat16")
        step = jax.vmap(lambda p, x, y, mask: grad_fn(p, x, y, mask, jax.random.key(0))[0],
                        in_axes=(None, 0, 0, 0))
        return jax.jit(step).lower(params, *batch).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "auto_interpret", lambda interpret: False)
        patch.setattr(experts, "auto_interpret", lambda interpret: False)
        kept = compile_step()
        patch.setattr(decoder, "KEEP_NAMED_OUTPUTS", None)
        plain = compile_step()
    return {"kept": kept, "plain": plain, "layers": layers, "kept_bytes": layers * kept_bytes,
            "cell": request.param}


def _kernel_launches(compiled, kernel: str) -> int:
    return sum("tpu_custom_call" in line and kernel in line.split(" = ")[0]
               for line in compiled.as_text().splitlines())


def test_a_rematerialized_layer_launches_the_forward_kernel_once(decoder_steps):
    """... and its memory grows by no more than the two outputs it keeps."""
    layers = decoder_steps["layers"]
    launches = {which: [_kernel_launches(decoder_steps[which], kernel)
                        for kernel in ("causal_attention_fwd", "causal_attention_bwd")]
                for which in ("kept", "plain")}
    assert launches == {"kept": [layers, layers], "plain": [2 * layers, layers]}
    temp = {which: decoder_steps[which].memory_analysis().temp_size_in_bytes
            for which in ("kept", "plain")}
    assert temp["kept"] - temp["plain"] <= decoder_steps["kept_bytes"] + SLACK, temp


def test_a_rematerialized_indexer_picks_once_and_never_sorts(decoder_steps):
    """Under the models' policy no operation of an indexer's scores or selection stands in
    a rematerialized computation (none exists in two of the three decoders); in the keye
    cell's layer a plain checkpoint reruns both, the program's sorts are the router's
    ``top_k``, none the pick's, and a band's keys stay in the
    chip's fast memory through the selection's passes."""
    kept, plain = (decoder_steps[which].as_text() for which in ("kept", "plain"))
    rerun = lambda text: len(re.findall(
        r'op_name="[^"]*rematted_computation[^"]*indexer_(?:scores|select)', text))
    assert rerun(kept) == 0
    if "indexer_select" in kept:
        assert rerun(plain) > 0
        sorts = [line for line in kept.splitlines() if " sort(" in line]
        assert sorts and not any("indexer_" in line for line in sorts)
        # The selection's keys, a band at a time: u32 [keys, 512] in memory space 1.
        assert re.search(r"u32\[1,1,8192,512\]\{[^}]*S\(1\)\}", kept)


def test_a_rematerialized_expert_layer_lays_its_picks_out_once(decoder_steps):
    """What the TPU's compiler leaves of the dispatch's rerun once its three outputs are
    kept: each cell has one expert layer here, its dispatch one scatter of int32 (the
    write of ``src``, the program's only integer scatter; under the client ``vmap`` XLA
    rewrites it without its name path, so it is found by its type), and the plain
    checkpoint's program holds that scatter a second time; nothing under ``moe_dispatch``
    sorts (the sorts left are the router's ``top_k``)."""
    texts = {which: decoder_steps[which].as_text() for which in ("kept", "plain")}
    scatters = {which: len(re.findall(r" = s32\[\d+\]\S* scatter\(", text))
                for which, text in texts.items()}
    assert scatters == {"kept": 1, "plain": 2}
    assert not re.search(r' sort\([^\n]*op_name="[^"]*moe_dispatch', texts["plain"])


def test_an_expert_layer_runs_the_two_expert_kernels(decoder_steps):
    """Each cell has one expert layer here: its step launches the forward kernel once
    (twice in the trinity cell's, whose sandwich norms keep the rerun alive) and the
    backward kernel once, compiled at the cell's widths under the client ``vmap``."""
    forward = 2 if decoder_steps["cell"] == "trinity" else 1
    assert [_kernel_launches(decoder_steps["kept"], kernel)
            for kernel in ("expert_tiles_fwd", "expert_tiles_bwd")] == [forward, 1]


def test_the_expert_kernels_compile_at_the_hybrids_widths(one_chip):
    """``[2688, 1856]`` and ``[1856, 2688]``, squared ReLU: rows that are not whole lanes,
    and the largest accumulators of the five cells (single-buffered matrices in the
    backward kernel)."""
    tile = expert_kernels.tile_rows(2688, 1856)
    assert expert_kernels.engages(tile, 2688, 1856, 1856, jnp.bfloat16)
    rows = 4096 * 6 + 8 * tile
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    operands = (shaped((rows, 2688)), shaped((rows, 1), jnp.float32), shaped((rows // tile,), jnp.int32),
                shaped((1,), jnp.int32), shaped((8, 2688, 1856)), shaped((8, 1856, 2688)))
    static = dict(activation=experts.RELU2, tile=tile)
    for kernel, args in ((expert_kernels.expert_tiles, operands),
                         (expert_kernels.expert_tiles_grads, (*operands[:2], operands[0], *operands[2:]))):
        assert "tpu_custom_call" in kernel.lower(*args, **static).compile().as_text()
