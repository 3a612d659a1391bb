"""The comparison that decides ``correct``: the program's first rounds against the
plain reference's, number by number, each with a limit of its own.

The numbers (the limits live in the configuration's file, under ``correct``):

* ``loss_gap.<r>`` — round ``r``'s loss, relative gap to the reference's.  It hardly
  moves with precision; it is there to catch a part of the cohort or of a batch left out.
* ``first_step_gap`` — the first aggregate step as the server optimizer gets it
  (new global - old global after round one), leaf by leaf: the gap between the
  program's norm and the reference's over the reference's norm of that leaf or of the
  median leaf, whichever is larger; the worst leaf.  This is the number a lower
  precision moves.
* ``update_gap`` — the same of the parameters' change over all compared rounds; it is
  there to catch a round that returns its state unchanged or a server step skipped.
"""

from __future__ import annotations

import jax
import numpy as np


def first_rounds(loop, generator, coordinator, rounds: int) -> dict:
    """Drive the system's first ``rounds`` rounds through the loop's own step and keep
    what is compared: every round's loss, and host copies of the global parameters
    after the first and after the last of them."""
    losses, trees = [], {}
    for r in range(rounds):
        _, metrics = loop.step(generator)
        losses.append(float(metrics.agg_metrics.get("loss", float("nan"))))
        if r in (0, rounds - 1):
            trees[r] = [np.asarray(x) for x in jax.tree.leaves(coordinator.params)]
    return {"losses": losses, "first": trees[0], "last": trees[rounds - 1]}


def reference_rounds(fedavg, family, config: dict, data, seed: int, device, rounds: int, q) -> dict:
    """The plain reference's first ``rounds`` rounds on one device, from the same seeded
    weights and data, with matmul operands rounded by ``q`` (the identity for the
    reference, a lower precision for the control).  Same shape as ``first_rounds``,
    plus ``start``: the seeded weights as host arrays."""
    from benchmark import federation

    weights = federation.make_weights(config, family, seed)
    start = [np.asarray(x) for x in jax.tree.leaves(weights)]
    losses, trees = fedavg.run_rounds(
        family, config["model"]["kwargs"], config["federation"], weights,
        jax.device_put(data, device), federation.program_seed(seed), rounds,
        q=q, block=config["reference"]["block"],
    )
    return {"losses": losses, "start": start,
            "first": jax.tree.leaves(trees[0]), "last": jax.tree.leaves(trees[-1])}


def norms(rounds: dict, start: list) -> dict:
    """What ``compare`` takes: the losses and the per-leaf norms of the first step and
    of the whole change, both from ``start``."""
    return {"losses": rounds["losses"], "first_step": leaf_norms(rounds["first"], start),
            "update": leaf_norms(rounds["last"], start)}


def leaf_norms(after, before) -> np.ndarray:
    """Per-leaf L2 norm of ``after - before`` over two trees given as leaf lists."""
    return np.array([
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
        for a, b in zip(after, before)
    ])


def worst_leaf_gap(program: np.ndarray, reference: np.ndarray) -> float:
    scale = np.maximum(reference, np.median(reference))
    return float(np.max(np.abs(program - reference) / np.maximum(scale, 1e-30)))


def compare(observed: dict, reference: dict, limits: dict) -> list[dict]:
    """``observed`` / ``reference``: ``losses`` (one per round), ``first_step`` and
    ``update`` (per-leaf norms).  Returns one entry per number compared:
    ``{"name", "value", "limit", "ok"}``.  A number that is not finite fails."""
    rows = []
    for r, (got, want) in enumerate(zip(observed["losses"], reference["losses"])):
        rows.append((f"loss_gap.{r}", abs(got - want) / max(abs(want), 1e-30), limits["loss_gap"]))
    rows.append(("first_step_gap",
                 worst_leaf_gap(observed["first_step"], reference["first_step"]),
                 limits["first_step_gap"]))
    rows.append(("update_gap",
                 worst_leaf_gap(observed["update"], reference["update"]),
                 limits["update_gap"]))
    return [
        {"name": n, "value": float(v), "limit": float(lim),
         "ok": bool(np.isfinite(v) and v <= lim)}
        for n, v, lim in rows
    ]
