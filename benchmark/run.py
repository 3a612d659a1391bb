"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's federation from the seed, drives its first rounds (they compile or
read the cache, warm every shape, and are what ``correct`` compares), measures whole
rounds for ``--seconds``, frees the system, runs the plain reference, and prints one
JSON object as the last line; its last key, ``checks``, holds every number ``correct``
was decided from beside its limit (``[value, limit]``), and the same pairs are the last
lines on standard error.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics with the profiler on for a few rounds of the window,
and prints the traced rounds' device time by the program's named scopes and by pass.
Needs the chips the cell asks for: there is no fallback to another backend.

Names of configurations, mixes and metrics come from ``BENCHMARK.json`` and select
files; none is written here.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import trace  # noqa: E402  (the standard library alone until ``load`` runs)


def say(*parts) -> None:
    print(*parts, flush=True)


def device_memory_peak(device) -> int:
    """Peak bytes on one chip: the buffers the runtime held at their peak plus the
    largest reservation a running program made for its temporaries.  On the TPU
    ``peak_bytes_in_use`` leaves the temporaries out and ``peak_bytes_reserved`` is
    where they show (my chip run, PR 23: a program with 512 MiB of temporaries and a
    1 GiB argument reads 1.07e9 in use, 5.4e8 reserved)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def say_scopes(rows: list, rounds: int, busy_s: float) -> None:
    """The whole table, ms a traced round: every scope the trace holds by pass, and what
    ran in none; its sum beside the device's busy time, which it tiles."""
    table, kinds = trace.scope_table(rows), trace.PASSES
    per_round = lambda seconds: 1000.0 * seconds / max(rounds, 1)
    say("# device time by scope, ms a traced round: " + " ".join(f"{k:>11}" for k in kinds))
    for scope, row in sorted(table.items(), key=lambda kv: -sum(kv[1].values())):
        say(f"#   {scope:<24}" + " ".join(f"{per_round(row.get(k, 0.0)):11.3f}" for k in kinds)
            + f"   {per_round(sum(row.values())):11.3f}")
    total = sum(seconds for _, _, seconds in rows)
    say(f"#   the rows' sum {per_round(total):.3f}, busy {per_round(busy_s):.3f} "
        f"({100.0 * (total / busy_s - 1):+.3f}%)")


def scope_ms_per_round(ctx: dict, spec: dict) -> float | None:
    """The reading every per-scope metric shares: milliseconds a traced round of the rows
    of the scope table (``ctx["scopes"]``) that the metric's own file asks for.  ``None``
    where the run was not traced or no operation ran under its scopes: nothing to read."""
    rows, rounds = ctx.get("scopes"), ctx.get("traced_rounds")
    if not rows or not rounds:
        return None
    seconds = trace.scope_seconds(rows, spec.get("scopes"), spec.get("pass"),
                                  bool(spec.get("innermost")))
    return None if seconds is None else 1000.0 * seconds / rounds


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def look_for_chips(root: Path, chips: int):
    """``(devices JAX found, this device kind's peaks)``, or a non-zero exit: no
    accelerator, one the peaks table does not know, or fewer chips than the cell needs."""
    import jax

    from benchmark import federation

    table = federation.load_json(root / "benchmark" / "peaks.json")["peaks"]
    found = jax.devices()
    kind, platform = found[0].device_kind, found[0].platform
    if kind not in table or table[kind]["platform"] != platform:
        raise SystemExit(f"device {platform}/{kind!r} is not in benchmark/peaks.json: "
                         "the benchmark measures on a chip it knows the peaks of")
    if len(found) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(found)}")
    return found, table[kind]


def configure_cache(root: Path) -> None:
    """JAX's persistent compilation cache: where the environment says, else at a fixed
    path inside the checkout; every program kept, so a warm run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_cell(root: Path, workload: str):
    """``(manifest, cell, configuration, traffic)`` of a workload named in BENCHMARK.json."""
    from benchmark import federation

    manifest = federation.load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    spec = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = federation.load_json(root / spec["file"])
    traffic = federation.load_json(root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    return manifest, cell, config, traffic


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", help="write the trace's events here as JSON (how the "
                    "recorded trace under tests/benchmark/data was made)")
    args = ap.parse_args(argv)

    if importlib.util.find_spec("nanofed_tpu") is None:
        raise SystemExit("the program (nanofed_tpu) is not in this checkout: nothing to measure")
    configure_cache(ROOT)
    chips = load_cell(ROOT, args.workload)[1]["chips"]
    found, peaks = look_for_chips(ROOT, chips)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                      found, peaks, keep_trace=args.keep_trace)
    say(json.dumps(result))
    for name, (value, limit) in result["checks"].items():
        print(f"{name} {value} limit {limit}", file=sys.stderr, flush=True)
    return 0


def run_cell(root: Path, workload: str, seed: int, seconds: float, traced: bool,
             found, peaks: dict, keep_trace: str | None = None) -> dict:
    """Everything of a run but the look for a chip; returns the result line's object."""
    import jax
    from benchmark import check, federation

    manifest, cell, config, traffic = load_cell(root, workload)
    family = federation.load_named(root, "reference", config["family"])
    flops = federation.load_named(root, "flops", config["family"])
    loop = federation.load_named(root, "loops", traffic["loop"])
    devices = found[: cell["chips"]]
    kind, platform = found[0].device_kind, found[0].platform
    say(f"# {workload} seed={seed} on {len(found)} x {platform}/{kind}, jax {jax.__version__}")

    stages = [("start", time.perf_counter() - T0)]
    mark = lambda name: stages.append((name, time.perf_counter() - T0))
    # Every trace, lowering and backend compile JAX reports; the listener cannot be taken
    # off again, so it is switched off when the window has closed.
    compiles: list[str] = []
    counting = {"on": True}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append(event)
        if counting["on"] and event.startswith("/jax/core/compile/") else None
    )

    # --- set-up: data and weights from the seed, the system, its first rounds.
    work = tempfile.mkdtemp(prefix="nanofed-bench-")
    try:
        data, coordinator, generator = federation.start_system(
            config, traffic, family, seed, devices, work)
        mark("system")
        n_check = int(config["reference"]["rounds"])
        observed = check.first_rounds(loop, generator, coordinator, n_check)
        setup_s = time.perf_counter() - T0
        mark("first rounds")
        say("# set-up stages, seconds from process start: "
            + ", ".join(f"{name} {at:.2f}" for name, at in stages))
        say(f"# set-up {setup_s:.2f} s; first losses {observed['losses']}; "
            f"{len(compiles)} trace/lower/compile events so far")

        # --- the window.
        compiles.clear()
        trace_dir = tempfile.mkdtemp(prefix="nanofed-trace-") if traced else None
        run = loop.measure(generator, traffic, seconds, trace_dir)
        compiles_in_window = len(compiles)
        memory_peak = max(device_memory_peak(d) for d in devices)
    finally:
        counting["on"] = False
        shutil.rmtree(work, ignore_errors=True)

    fed = config["federation"]
    rounds = run["rounds"]
    good = [m for _, m in rounds
            if m.status.name == "COMPLETED" and math.isfinite(m.agg_metrics.get("loss", math.nan))]
    client_samples = sum(m.num_clients for m in good) * fed["samples_per_client"] * fed["local_epochs"]

    # Which named scopes make which metric is data: one file a metric.
    scope_specs, scope_names = federation.scope_metrics(root)
    reduced = scopes = None
    if trace_dir:
        t_read = time.perf_counter()
        try:
            path = trace.find_xplane(trace_dir)
            events = trace.load(path, set(traffic["host_spans"])) if path else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if events and keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            with open(os.path.join(keep_trace, f"{workload}.trace.json"), "w") as f:
                json.dump(events, f)
        t_reduce = time.perf_counter()
        reduced = trace.reduce(events, traffic["window_span"]) if events else None
        t_scopes = time.perf_counter()
        if reduced:
            scopes = trace.by_scope(events, scope_names)
            say_scopes(scopes, run["traced_rounds"], reduced["busy_s"])
        say(f"# trace read in {t_reduce - t_read:.2f} s, reduced in {t_scopes - t_reduce:.2f} s, "
            f"by scope in {time.perf_counter() - t_scopes:.2f} s")

    # --- free the system, then the plain reference on one device.
    del coordinator, generator
    gc.collect()
    t_ref = time.perf_counter()
    fedavg = federation.load_named(root, "reference", "fedavg")
    reference = check.reference_rounds(
        fedavg, family, config, data, seed, devices[0], n_check, fedavg.identity)
    rows = check.compare(check.norms(observed, reference["start"]),
                         check.norms(reference, reference["start"]), config["correct"])
    say(f"# reference: {n_check} rounds in {time.perf_counter() - t_ref:.2f} s, losses {reference['losses']}")
    for row in rows:
        say(f"# compared {row['name']}: {row['value']:.6g} (limit {row['limit']:g}) "
            f"{'ok' if row['ok'] else 'FAILED'}")
    say(f"# compiles inside the window: {compiles_in_window} (limit 0)")
    failed = len(rounds) - len(good)
    correct = (all(r["ok"] for r in rows) and compiles_in_window == 0
               and failed == 0 and len(rounds) > 0)
    # A number that is not finite has failed; as null it leaves the line JSON.
    checks = {**{r["name"]: [r["value"] if math.isfinite(r["value"]) else None, r["limit"]]
                 for r in rows},
              "compiles_in_window": [compiles_in_window, 0], "failed_rounds": [failed, 0]}

    # --- the metrics.
    ctx = {
        "trace": reduced, "scopes": scopes, "rounds": rounds, "samples": run["samples"],
        "window_s": run["window_s"], "setup_seconds": setup_s,
        "traced_rounds": run["traced_rounds"], "client_samples": client_samples,
        "train_flops_per_sample": flops.train_flops_per_sample(config["model"]["kwargs"]),
        "chips": cell["chips"], "peaks": peaks, "memory_peak_bytes": memory_peak,
        "config": config, "traffic": traffic,
    }
    section, readers = ("per_layer", "layer_metrics") if traced else ("end_to_end", "end_to_end")
    out: dict[str, dict] = {}
    for metric in manifest[section]:
        if applies(metric, workload):
            if metric["name"] in scope_specs:
                value = scope_ms_per_round(ctx, scope_specs[metric["name"]])
            else:
                value = federation.load_named(root, readers, metric["name"]).read(ctx)
            if value is not None:
                out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(found),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": len(rounds), "failed": failed,
              "metrics": out, "device": device}
    if traced and reduced:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
                               "device_scopes": trace.longest_scopes(scopes)}
    say(f"# {len(run['samples'])} samples of {traffic['rounds_per_sample']} round(s) in "
        f"{run['window_s']:.3f} s; {len(rounds)} rounds, {failed} failed")
    result["checks"] = checks  # last in the line: what a record of its end keeps
    return result


if __name__ == "__main__":
    sys.exit(main())
