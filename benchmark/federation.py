"""From a configuration file and a seed to the system under test.

The benchmark makes the data and the weights itself, on the device, each in one jitted
call from the seed, and hands them to the program through its public types
(``ClientData``, a ``Model`` whose ``init`` returns the benchmark's weights).  The plain
reference is later given the same arrays, so it takes nothing the program has made.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


_LOADED: dict[Path, object] = {}


def load_named(root: Path, kind: str, name: str):
    """The module ``<root>/benchmark/<kind>/<name>.py``, found by name alone."""
    path = Path(root) / "benchmark" / kind / f"{name}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise SystemExit(f"{path} is missing: BENCHMARK.json or a file it names asks for it")
        spec = importlib.util.spec_from_file_location(f"_benchmark_{kind}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def scope_metrics(root: Path) -> tuple[dict[str, dict], frozenset[str]]:
    """``({metric: its file's object}, every scope named)``.  A per-scope metric is
    ``<root>/benchmark/scope_metrics/<metric>.json`` and needs no reader module: ``scopes``
    (the ``jax.named_scope``s of the program it sums; absent: every operation), ``pass``
    (forward, recomputed or backward; absent: all), ``innermost`` (only what ran directly
    under a scope, inside no other that is named).  The files of
    ``<root>/benchmark/scope_table/`` name scopes that make no metric: rows of their own in
    the printed table, which an enclosing ``innermost`` reading then leaves out."""
    directory = Path(root) / "benchmark"
    specs = {p.stem: load_json(p) for p in sorted((directory / "scope_metrics").glob("*.json"))}
    apart = [load_json(p) for p in sorted((directory / "scope_table").glob("*.json"))]
    names = {n for spec in [*specs.values(), *apart] for n in spec.get("scopes") or ()}
    return specs, frozenset(names)


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's key and NumPy generators take 31 bits."""
    return int(seed) % (2**31 - 1)


def _key(seed: int, stream: int):
    # RBG: XLA compiles a threefry draw of these sizes for tens of seconds (PERF.md).
    key = jax.random.key(program_seed(seed), impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 31), stream)


def capacity(fed: dict) -> int:
    """Rows per client after padding to whole batches."""
    return math.ceil(fed["samples_per_client"] / fed["batch_size"]) * fed["batch_size"]


@functools.partial(jax.jit, static_argnames=("clients", "rows", "real", "shape", "classes", "tokens"))
def _make_data(key, *, clients, rows, real, shape, classes, tokens):
    kx, kt = jax.random.split(key)
    if tokens:
        x = jax.random.randint(kx, (clients, rows, *shape), 0, classes, jnp.int32)
        # A seeded affine teacher on the last token: learnable, so the loss can fall.
        a, b = jax.random.randint(kt, (2,), 1, classes, jnp.int32)
        y = ((x[..., -1].astype(jnp.uint32) * a.astype(jnp.uint32) + b.astype(jnp.uint32))
             % jnp.uint32(classes)).astype(jnp.int32)
    else:
        x = jax.random.normal(kx, (clients, rows, *shape), jnp.float32)
        feat = math.prod(shape)
        teacher = jax.random.normal(kt, (feat, classes), jnp.float32)
        y = jnp.argmax(x.reshape(clients, rows, feat) @ teacher, axis=-1).astype(jnp.int32)
    mask = jnp.broadcast_to((jnp.arange(rows) < real).astype(jnp.float32), (clients, rows))
    return x, y, mask


def make_data(config: dict, family, seed: int, input_shape, classes):
    """``(x, y, mask)``, each ``[clients, capacity, ...]``: every client holds
    ``samples_per_client`` real rows and padding up to whole batches."""
    fed = config["federation"]
    return _make_data(
        _key(seed, 1), clients=fed["num_clients"], rows=capacity(fed),
        real=fed["samples_per_client"], shape=tuple(input_shape), classes=int(classes),
        tokens=bool(family.TOKEN_STREAM),
    )


def make_weights(config: dict, family, seed: int):
    kwargs = json.dumps(config["model"]["kwargs"], sort_keys=True)
    return _make_weights(_key(seed, 2), family=family, kwargs=kwargs)


@functools.partial(jax.jit, static_argnames=("family", "kwargs"))
def _make_weights(key, *, family, kwargs):
    return family.init_params(key, json.loads(kwargs))


def build_model(config: dict, family, seed: int):
    """The zoo model the configuration names, with ``init`` returning the benchmark's
    seeded weights.  The tree has to be the model's own, leaf for leaf."""
    from nanofed_tpu.models import get_model

    model = get_model(config["model"]["factory"], **config["model"]["kwargs"])
    own = jax.eval_shape(model.init, jax.random.key(0))
    ours = jax.eval_shape(lambda: make_weights(config, family, seed))
    if jax.tree.structure(own) != jax.tree.structure(ours) or any(
        a.shape != b.shape or a.dtype != b.dtype
        for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(ours))
    ):
        raise SystemExit(
            f"reference/{config['family']}.py does not build the parameter tree of "
            f"{config['model']['factory']}: the configuration cannot be checked"
        )
    return dataclasses.replace(model, init=lambda key: make_weights(config, family, seed))


def start_system(config: dict, traffic: dict, family, seed: int, devices, base_dir):
    """``(data, coordinator, its round generator)``: the seeded data and weights, and
    the ``Coordinator`` a user would build for this federation on a 1-D clients mesh
    over ``devices``; everything not named in the files is the program's default."""
    from nanofed_tpu.core.types import ClientData
    from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
    from nanofed_tpu.parallel.mesh import make_mesh
    from nanofed_tpu.trainer import TrainingConfig

    fed, precision = config["federation"], config["precision"]
    if fed["strategy"] != "fedavg":
        raise SystemExit(f"strategy {fed['strategy']!r} is not wired into the benchmark yet")
    model = build_model(config, family, seed)
    data = make_data(config, family, seed, model.input_shape, model.num_classes)
    x, y, mask = data
    coordinator = Coordinator(
        model=model,
        train_data=ClientData(x=x, y=y, mask=mask),
        config=CoordinatorConfig(
            num_rounds=traffic["num_rounds"], participation_rate=fed["participation"],
            seed=program_seed(seed), base_dir=base_dir,
            rounds_per_block=config["rounds_per_block"],
        ),
        training=TrainingConfig(
            batch_size=fed["batch_size"], local_epochs=fed["local_epochs"],
            learning_rate=fed["learning_rate"], compute_dtype=precision["compute_dtype"],
        ),
        mesh=make_mesh(devices=list(devices)),
        client_chunk=config["client_chunk"],
    )
    return data, coordinator, coordinator.start_training()
