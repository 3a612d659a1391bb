#!/usr/bin/env python
"""Record the real-data accuracy evidence artifact (VERDICT r1 item 4).

Runs federated FedAvg on REAL handwritten-digit images to >= 97% held-out test accuracy
and writes ``runs/accuracy_<dataset>_r{N}.json`` with the config, per-eval trajectory,
and wall-clock-to-97.

Dataset choice: with MNIST IDX files present (``--data-dir``, see
``scripts/fetch_mnist.py``), runs the MNIST CNN at reference parity
(``docs/source/getting_started/tutorial.rst:325-334`` records 93.75% round-1 aggregated
accuracy; BASELINE.md's north star is wall-clock to 97% test accuracy).  In zero-egress
environments it falls back to the bundled sklearn digits dataset (1,797 real 8x8 digit
images) — smaller, but real pixels, real generalization, same 97% bar.

Usage:
    python scripts/record_accuracy.py [--data-dir data/mnist] [--round-tag r02]
    python scripts/record_accuracy.py --platform cpu   # force the virtual CPU mesh
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

TARGET_ACC = 0.97


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data-dir", default=None, help="MNIST IDX dir (else bundled digits)")
    ap.add_argument("--round-tag", default="r03")
    ap.add_argument("--platform", choices=["auto", "cpu"], default="auto")
    ap.add_argument("--max-rounds", type=int, default=60)
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument(
        "--model", choices=["mlp", "cnn"], default="cnn",
        help="evidence model when MNIST is unavailable: digits_mlp on native 8x8, or "
        "the flagship MNIST CNN on the real digits bilinearly upsampled to 28x28",
    )
    ap.add_argument(
        "--clients", type=int, default=None,
        help="override the client count (north-star configs: 100/1000; with the "
        "1,797-image digits set, 100 clients is a realistic ~18-images-per-client "
        "cross-device regime — the artifact name and body record the count)",
    )
    # Optimizer overrides (round-5 sweep: at 100 clients the MLP plateaus at 96.1%
    # with the defaults but crosses 97.5% by round ~21 with momentum 0.9 + 4 local
    # epochs — the fragmented-shard regime needs more local progress per round).
    ap.add_argument("--momentum", type=float, default=None)
    ap.add_argument("--local-epochs", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--hidden", type=int, default=None,
                    help="digits_mlp width override (mlp evidence model only)")
    ap.add_argument("--lr-schedule", default="constant",
                    choices=["constant", "cosine", "linear", "step"])
    ap.add_argument("--lr-min-factor", type=float, default=0.0)
    args = ap.parse_args()

    from nanofed_tpu.utils.platform import force_cpu_mesh, log_stage

    if args.platform == "cpu":
        force_cpu_mesh(args.n_devices)

    import jax

    from nanofed_tpu.data import federate, load_digits_dataset, load_mnist, pack_eval
    from nanofed_tpu.models import get_model
    from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
    from nanofed_tpu.trainer import TrainingConfig

    devices = jax.devices()
    log_stage(f"devices: {len(devices)}x {devices[0].platform}")

    mnist_available = False
    if args.data_dir is not None:
        try:
            load_mnist("train", args.data_dir, synthetic_fallback=False)
            mnist_available = True
        except FileNotFoundError:
            log_stage(f"no MNIST under {args.data_dir}; using bundled digits")

    if mnist_available:
        dataset, model_name = "mnist", "mnist_cnn"
        model = get_model(model_name)
        train = load_mnist("train", args.data_dir, synthetic_fallback=False)
        test = load_mnist("test", args.data_dir, synthetic_fallback=False)
        training = TrainingConfig(batch_size=64, local_epochs=2, learning_rate=0.1)
        num_clients, batch_eval = 10, 256
    elif args.model == "cnn":
        # Flagship-model evidence without MNIST: the REAL digits images upsampled to
        # 28x28 so the parity CNN architecture itself (not a stand-in MLP) is what
        # crosses the 97% bar on real data.
        from nanofed_tpu.data.datasets import resize_images

        dataset, model_name = "digits_cnn28", "mnist_cnn"
        model = get_model(model_name)
        train = resize_images(load_digits_dataset("train"), 28, 28)
        test = resize_images(load_digits_dataset("test"), 28, 28)
        training = TrainingConfig(batch_size=16, local_epochs=2, learning_rate=0.1)
        num_clients, batch_eval = 8, 128
    else:
        dataset, model_name = "digits", "digits_mlp"
        model = get_model(model_name, hidden=args.hidden or 96)
        train = load_digits_dataset("train")
        test = load_digits_dataset("test")
        training = TrainingConfig(batch_size=16, local_epochs=2, learning_rate=0.5)
        num_clients, batch_eval = 8, 128

    import dataclasses

    overrides = {
        k: v for k, v in (
            ("momentum", args.momentum),
            ("local_epochs", args.local_epochs),
            ("learning_rate", args.lr),
        ) if v is not None
    }
    if overrides:
        training = dataclasses.replace(training, **overrides)

    if args.clients is not None:
        num_clients = args.clients
        dataset = f"{dataset}_{num_clients}c"
        if num_clients * 2 > len(train):
            # Degenerate shards (< 2 images/client) — keep batches meaningful.
            training = dataclasses.replace(training, batch_size=2)
    log_stage(f"dataset={train.name}: {len(train)} train / {len(test)} test (REAL data)")
    cd = federate(train, num_clients=num_clients, scheme="iid",
                  batch_size=training.batch_size, seed=0)
    coord = Coordinator(
        model=model,
        train_data=cd,
        config=CoordinatorConfig(num_rounds=args.max_rounds, seed=0,
                                 base_dir="runs/accuracy_run", eval_every=1,
                                 lr_schedule=args.lr_schedule,
                                 lr_min_factor=args.lr_min_factor),
        training=training,
        eval_data=pack_eval(test, batch_size=batch_eval),
    )

    t0 = time.time()
    trajectory = []
    reached_at = None
    for m in coord.start_training():
        acc = m.eval_metrics.get("accuracy")
        if acc is None:
            continue
        trajectory.append({"round": m.round_id, "test_accuracy": round(float(acc), 4),
                           "elapsed_s": round(time.time() - t0, 2)})
        log_stage(f"round {m.round_id}: test acc {acc:.4f}")
        if acc >= TARGET_ACC and reached_at is None:
            reached_at = trajectory[-1]
            break

    artifact = {
        "artifact": f"accuracy_{dataset}_{args.round_tag}",
        "dataset": train.name,
        "real_data": True,
        "data_note": (
            "sklearn digits: 1,797 REAL handwritten-digit images (UCI optdigits), "
            "bilinearly upsampled 8x8 -> 28x28 so the flagship MNIST-CNN architecture "
            "is the model under test; MNIST itself is unfetchable here (see "
            "runs/mnist_fetch_attempt_*.log for the documented zero-egress attempt)"
            if dataset == "digits_cnn28"
            else "sklearn digits: 1,797 REAL handwritten-digit images (UCI optdigits)"
        ) if dataset != "mnist" else "MNIST IDX files",
        "model": (f"{model_name}(hidden={args.hidden or 96})"
                  if model_name == "digits_mlp" else model_name),
        "num_clients": num_clients,
        "scheme": "iid",
        "training": {"batch_size": training.batch_size,
                     "local_epochs": training.local_epochs,
                     "learning_rate": training.learning_rate,
                     "momentum": training.momentum,
                     "lr_schedule": args.lr_schedule},
        "target_accuracy": TARGET_ACC,
        "reached": reached_at is not None,
        "reached_at_round": reached_at["round"] if reached_at else None,
        "wall_clock_to_target_s": reached_at["elapsed_s"] if reached_at else None,
        "final_test_accuracy": trajectory[-1]["test_accuracy"] if trajectory else None,
        "trajectory": trajectory,
        "platform": str(devices[0].platform),
        "devices": len(devices),
        "reference_parity_note": (
            "reference records 93.75% round-1 aggregated accuracy on MNIST "
            "(docs/source/getting_started/tutorial.rst:325-334); target here is the "
            "BASELINE.md 97% test-accuracy bar on real data"
        ),
    }
    out = REPO / "runs" / f"accuracy_{dataset}_{args.round_tag}.json"
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(artifact, indent=2))
    print(json.dumps({k: v for k, v in artifact.items() if k != "trajectory"}, indent=2))
    log_stage(f"artifact written to {out}")
    return 0 if reached_at else 1


if __name__ == "__main__":
    sys.exit(main())
