#!/usr/bin/env python
"""Warm (or verify) the shippable persistent compilation cache.

The warm-ship workflow (tuning.compile_cache): on the BUILD host, pre-compile
the full candidate program set into a cache directory off the critical path
and stamp a toolchain manifest::

    python scripts/warm_cache.py --model digits_mlp

then ``tar`` the directory, move it to the accelerator host, and on the RECEIVING
host check the manifest before trusting a single entry::

    python scripts/warm_cache.py --verify-only

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when set and ``<checkout>/.jax_cache``
otherwise (``utils.platform.compilation_cache_dir``) — the one place every entry
point keeps its compile cache.

``--verify-only`` exits 1 on an incompatible cache (foreign jax/jaxlib/
platform — XLA would silently key-miss and recompile everything; the manifest
says so up front).  Both modes print one JSON document to stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="digits_mlp")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--compile-budget", type=float, default=None,
                    help="cap the sweep's total compile seconds (remaining "
                    "candidates are skipped, stated in the table)")
    ap.add_argument("--candidate-deadline", type=float, default=None,
                    help="per-candidate compile deadline in seconds (a wedged "
                    "compile is recorded, not waited out)")
    ap.add_argument("--force", action="store_true",
                    help="re-sweep over a warm autotune table (XLA entries "
                    "still hit, so a forced re-warm is cheap)")
    ap.add_argument("--verify-only", action="store_true",
                    help="verify an existing cache's manifest against THIS "
                    "host's toolchain instead of warming; exit 1 on mismatch")
    args = ap.parse_args(argv)

    from nanofed_tpu.tuning import verify_manifest

    if args.verify_only:
        from nanofed_tpu.utils.platform import compilation_cache_dir

        verdict = verify_manifest(compilation_cache_dir())
        print(json.dumps(verdict, indent=2, default=str))
        return 0 if verdict["compatible"] else 1

    from nanofed_tpu.models import get_model
    from nanofed_tpu.trainer import TrainingConfig
    from nanofed_tpu.tuning import PopulationSpec, TuningSpace, warm

    model = get_model(args.model)
    sample_shape = tuple(model.input_shape)
    result = warm(
        model,
        PopulationSpec(num_clients=args.clients, capacity=args.capacity,
                       sample_shape=sample_shape),
        TrainingConfig(batch_size=args.batch_size, local_epochs=1,
                       learning_rate=0.1),
        num_rounds=args.rounds,
        space=TuningSpace(
            client_chunks=(None,), rounds_per_blocks=(1, args.rounds),
            model_shards=(1,), batch_sizes=(args.batch_size,),
        ),
        force=args.force,
        compile_budget_s=args.compile_budget,
        candidate_deadline_s=args.candidate_deadline,
    )
    out = result.to_dict()
    out["verify"] = verify_manifest(result.cache_dir)
    print(json.dumps(out, indent=2, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
