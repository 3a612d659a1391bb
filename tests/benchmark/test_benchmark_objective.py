"""The plain reference trains a family on the family's own objective: where a family
module brings ``sample_nll``, ``reference/fedavg.py`` takes each sample's loss from it and
keeps everything around it.  At tiny sizes on the CPU: the hook changes no digit where it
says what the reference said; a key-drawn loss at every position agrees with the program
driven through ``Coordinator(grad_fn=...)``, and stops agreeing in float8 or on another
key; a hook that breaks the contract is told so."""

import functools
import shutil
import tempfile
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import REPO

from benchmark import check, federation, run

# Read at this size on the CPU (seeds 1-6): the program (bfloat16 casts in its grad_fn)
# gives loss gaps up to 0.00036 and step gaps up to 0.0035 / 0.0036; the float8 control
# 0.098-0.253 / 0.102-0.228, the reference on another key 0.087-0.337 / 0.086-0.242 (its
# loss gaps up to 0.021, the control's up to 0.0091).
MASKED_LIMITS = {"loss_gap": 0.002, "first_step_gap": 0.02, "update_gap": 0.02}


def _cell(root, workload):
    _, _, config, traffic = run.load_cell(root, workload)
    family = federation.load_named(root, "reference", config["family"])
    fedavg = federation.load_named(root, "reference", "fedavg")
    return config, traffic, family, fedavg


def _with(family, **changed):
    """``family`` as a module of its own with ``changed`` laid over it."""
    module = types.ModuleType(f"{family.__name__}_changed")
    module.__dict__.update({k: v for k, v in vars(family).items() if not k.startswith("__")})
    module.__dict__.update(changed)
    return module


def _picks_the_label(family):
    """``sample_nll`` saying in the hook's words what ``fedavg.py`` says without it."""

    def sample_nll(params, xb, yb, key, model_kwargs, q):
        logp = family.log_probs(params, xb, key, model_kwargs, q)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    return _with(family, sample_nll=sample_nll)


def _rounds(fedavg, family, config, seed, q, rounds=3):
    model = federation.build_model(config, family, seed)
    data = federation.make_data(config, family, seed, model.input_shape, model.num_classes)
    return fedavg.run_rounds(
        family, config["model"]["kwargs"], config["federation"],
        federation.make_weights(config, family, seed), data, federation.program_seed(seed),
        rounds, q=q, block=config["reference"]["block"])


@pytest.mark.parametrize("workload, control", [
    ("tiny-cnn.pairs", False), ("tiny-lm.sync", False), ("tiny-lm.sync", True)])
def test_a_hook_that_picks_the_label_changes_no_bit(tiny_root, workload, control):
    """The CNN's reference splits the step key for its two dropouts, so its case also says
    that the hook is handed the key ``log_probs`` was; with ``control`` the float8 rounding
    has to reach the hook through ``q`` for the digits to agree."""
    config, _, family, fedavg = _cell(tiny_root, workload)
    q = fedavg.float8 if control else fedavg.identity
    losses, trees = _rounds(fedavg, family, config, 5, q)
    hooked_losses, hooked_trees = _rounds(fedavg, _picks_the_label(family), config, 5, q)
    assert len(losses) == 3 and hooked_losses == losses
    for tree, hooked in zip(trees, hooked_trees):
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(hooked)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    if control:
        exact, _ = _rounds(fedavg, family, config, 5, fedavg.identity)
        assert exact != losses


def test_where_a_family_has_both_forms_the_hook_decides(tiny_root):
    """A hook that says 7 for every sample: the round's loss is 7 and, its gradient being
    nought, no leaf moves, with ``log_probs`` beside it or without."""
    config, _, family, fedavg = _cell(tiny_root, "tiny-lm.sync")
    seven = _with(family, sample_nll=lambda params, xb, yb, key, kw, q: jnp.full(
        xb.shape[:1], 7.0, jnp.float32))
    hook_alone = _with(seven)
    del hook_alone.log_probs
    start = jax.tree.map(np.asarray, federation.make_weights(config, family, 5))
    for both_or_one in (seven, hook_alone):
        losses, trees = _rounds(fedavg, both_or_one, config, 5, fedavg.identity, rounds=1)
        assert losses == [7.0]
        assert all(np.array_equal(a, b)
                   for a, b in zip(jax.tree.leaves(trees[0]), jax.tree.leaves(start)))


@pytest.mark.parametrize("returned, said", [
    (lambda xb: jnp.zeros(xb.shape, jnp.float32), "float32[4, 16]"),
    (lambda xb: jnp.zeros((), jnp.float32), "float32[]"),
    (lambda xb: jnp.zeros(xb.shape[:1], jnp.bfloat16), "bfloat16[4]"),
    (lambda xb: None, "None[]"),
])
def test_a_hook_that_breaks_the_contract_is_told_so(tiny_root, returned, said):
    """One float32 loss a sample; anything else is refused by name while the round is
    traced, before XLA sees a shape it would complain of in its own words."""
    config, _, family, fedavg = _cell(tiny_root, "tiny-lm.sync")
    broken = _with(family, sample_nll=lambda params, xb, yb, key, kw, q: returned(xb))
    with pytest.raises(TypeError) as raised:
        _rounds(fedavg, broken, config, 5, fedavg.identity, rounds=1)
    message = str(raised.value)
    assert "sample_nll(params, xb, yb, key, model_kwargs, q)" in message
    assert "one float32 loss a sample, float32[4]" in message and said in message
    assert "fedavg.py's docstring" in message


def test_rounds_give_up_the_weights_they_started_from(tiny_root):
    """``run_rounds`` consumes ``params``: the caller's name for the start weights would
    else hold a third tree on the device through the second round.  A host copy made
    before (on the CPU a view of the very buffer) stays good."""
    config, _, family, fedavg = _cell(tiny_root, "tiny-lm.sync")
    weights = federation.make_weights(config, family, 5)
    start = jax.tree.map(np.asarray, weights)
    model = federation.build_model(config, family, 5)
    data = federation.make_data(config, family, 5, model.input_shape, model.num_classes)
    _, trees = fedavg.run_rounds(family, config["model"]["kwargs"], config["federation"], weights,
                                 data, 5, 2, block=config["reference"]["block"])
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(weights))
    again = federation.make_weights(config, family, 5)
    for a, b, moved in zip(*map(jax.tree.leaves, (start, again, trees[-1]))):
        assert np.array_equal(a, np.asarray(b)) and a.shape == moved.shape
    assert not np.array_equal(start["tok_emb"], trees[-1]["tok_emb"])


# --- a loss at every kept position, its mask drawn from the step key.


@pytest.fixture(scope="module")
def masked_root():
    """A tiny root to which one family is ADDED: ``reference/masked_lm.py``."""
    from benchlib import make_tiny_root

    root = make_tiny_root(Path(tempfile.mkdtemp(prefix="bench_masked_")))
    shutil.copy(REPO / "tests" / "benchmark" / "masked_lm_family.py",
                root / "benchmark" / "reference" / "masked_lm.py")
    yield root
    shutil.rmtree(root, ignore_errors=True)


def _masked_grad_fn(masked, heads):
    """What a user of the program writes for this objective: the program's own
    ``apply_sequence`` in bfloat16 (``make_local_fit`` refuses ``compute_dtype`` beside a
    custom ``grad_fn``, so the casts are here), the same draw from the step's ``rng``."""
    from nanofed_tpu.models import transformer
    from nanofed_tpu.trainer.local import StepStats

    def loss_fn(params, xb, mb, rng):
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
        logp = transformer.apply_sequence(params, xb, heads=heads, train=True, rng=rng)
        nll = masked.masked_nll(logp.astype(jnp.float32), xb, rng)
        count = mb.sum()
        return (nll * mb).sum() / jnp.maximum(count, 1.0), count

    def grad_fn(params, xb, yb, mb, rng):
        del yb
        (loss, count), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, xb, mb, rng)
        return grads, StepStats(loss_sum=loss * count, correct=jnp.zeros(()), count=count)

    return grad_fn


def _start_system_with(grad_fn, config, *rest):
    """``federation.start_system`` itself, its ``Coordinator`` given ``grad_fn=`` besides,
    and without the ``compute_dtype`` a custom ``grad_fn`` may not stand beside."""
    from nanofed_tpu import orchestration

    real = orchestration.Coordinator
    orchestration.Coordinator = functools.partial(real, grad_fn=grad_fn)
    try:
        return federation.start_system(
            {**config, "precision": {**config["precision"], "compute_dtype": None}}, *rest)
    finally:
        orchestration.Coordinator = real


def masked_readings(root, seed):
    """``(program, control, other key)``: the rows ``check.compare`` gives for the program
    through its ``grad_fn``, for the reference in float8, and for the reference with its
    mask drawn from another key, each against the reference as it stands."""
    config, traffic, _, fedavg = _cell(root, "tiny-lm.sync")
    masked = federation.load_named(root, "reference", "masked_lm")
    config = {**config, "family": "masked_lm", "correct": MASKED_LIMITS}
    loop = federation.load_named(root, "loops", traffic["loop"])
    rounds = config["reference"]["rounds"]
    with tempfile.TemporaryDirectory() as work:
        data, coordinator, generator = _start_system_with(
            _masked_grad_fn(masked, config["model"]["kwargs"]["heads"]),
            config, traffic, masked, seed, jax.devices()[:1], work)
        observed = check.first_rounds(loop, generator, coordinator, rounds)

    def reference(family, q):
        return check.reference_rounds(
            fedavg, family, config, data, seed, jax.devices()[0], rounds, q)

    exact = reference(masked, fedavg.identity)
    want = check.norms(exact, exact["start"])
    against = lambda got: check.compare(check.norms(got, exact["start"]), want, config["correct"])
    other_key = _with(masked, sample_nll=masked.make_sample_nll(
        lambda key: jax.random.fold_in(key, 1)))
    return (against(observed), against(reference(masked, fedavg.float8)),
            against(reference(other_key, fedavg.identity)))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_key_drawn_loss_at_every_position_against_the_program(masked_root, seed):
    """The test that says the key schedule is one schedule: the step key ``fedavg.py``
    hands to ``sample_nll`` is the ``rng`` the program hands to its ``grad_fn``, so both
    keep the same positions and differ by precision alone.  One step under bfloat16 the
    reference fails a limit, and so does the reference that folds the key once more."""
    program, control, other_key = masked_readings(masked_root, seed)
    assert [r["name"] for r in program] == [
        "loss_gap.0", "loss_gap.1", "loss_gap.2", "first_step_gap", "update_gap"]
    assert all(r["ok"] for r in program), program
    assert not all(r["ok"] for r in control), control
    assert not all(r["ok"] for r in other_key), other_key
