"""Read the two numbers each limit of ``correct`` is set from, on the chip.

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3,... --control 1,2,3

For every seed: the cell's system driven through its first rounds against the plain
reference (the sound readings).  For every control seed: the reference computed in
float8 — one step under the bfloat16 the configurations state — put in the program's
place (the control's readings).  No window is measured.  The limits then go into the
configuration's file by hand, above the sound runs' largest and below the control's
smallest; ``PERF.md`` keeps the readings.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    args = ap.parse_args()

    from benchmark import check, federation, run

    run.configure_cache(ROOT)
    manifest, cell, config, traffic = run.load_cell(ROOT, args.workload)
    found, _ = run.look_for_chips(ROOT, cell["chips"])
    devices = found[: cell["chips"]]
    family = federation.load_named(ROOT, "reference", config["family"])
    fedavg = federation.load_named(ROOT, "reference", "fedavg")
    loop = federation.load_named(ROOT, "loops", traffic["loop"])
    rounds = int(config["reference"]["rounds"])
    limits = config["correct"]
    control = {int(s) for s in args.control.split(",") if s}
    for seed in sorted({int(s) for s in args.seeds.split(",")} | control):
        work = tempfile.mkdtemp(prefix="nanofed-calibrate-")
        try:
            data, coordinator, generator = federation.start_system(
                config, traffic, family, seed, devices, work)
            observed = check.first_rounds(loop, generator, coordinator, rounds)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        del coordinator, generator
        gc.collect()
        reference = check.reference_rounds(
            fedavg, family, config, data, seed, devices[0], rounds, fedavg.identity)
        want = check.norms(reference, reference["start"])
        rows = check.compare(check.norms(observed, reference["start"]), want, limits)
        print(json.dumps({"seed": seed, "side": "program",
                          **{r["name"]: r["value"] for r in rows}}), flush=True)
        if seed in control:
            lower = check.reference_rounds(
                fedavg, family, config, data, seed, devices[0], rounds, fedavg.float8)
            rows = check.compare(check.norms(lower, reference["start"]), want, limits)
            print(json.dumps({"seed": seed, "side": "control-float8",
                              **{r["name"]: r["value"] for r in rows}}), flush=True)
        del data, reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
