"""The persistent compilation cache as a managed subsystem, not an ambient
side effect.

``utils.platform.enable_compilation_cache`` turns JAX's persistent cache on at
the one directory the location rule names; this module owns what lands there,
whether a run actually hit it, and how a cache built on one host can be trusted
on another.  FedJAX (arXiv:2108.02117) amortizes jit compilation across rounds,
but amortization starts at zero every time the cache is cold:

* :func:`install_compile_cache_metrics` — bridges JAX's compilation-cache
  ``jax.monitoring`` events into ``nanofed_compile_cache_hits_total`` /
  ``nanofed_compile_cache_misses_total`` counters, so a scrape (or the final
  telemetry snapshot) states whether the run compiled or replayed.
* :func:`warm` — pre-compiles a program set (an :func:`~nanofed_tpu.tuning.
  autotuner.autotune` sweep: every candidate the coordinator could dispatch)
  into the cache directory OFF the critical path, emitting one ``compile``
  telemetry record per program, then stamps a :func:`manifest <build_manifest>`.
* :func:`build_manifest` / :func:`verify_manifest` — the cache-key manifest:
  what toolchain (jax/jaxlib/platform) produced the entries, how many, how
  large.  ``verify_manifest`` is the receiving side of the warm-ship workflow —
  a cache built under a different jaxlib is DEAD WEIGHT (XLA keys miss), and
  the manifest says so before the accel window finds out the slow way.

The cache directory is shippable: ``tar`` it, move it to the accelerator host,
point ``JAX_COMPILATION_CACHE_DIR`` at it, and verify the manifest.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from nanofed_tpu.utils.logger import Logger

__all__ = [
    "CACHE_HIT_EVENT",
    "CACHE_MISS_EVENT",
    "COMPILE_CACHE_HITS",
    "COMPILE_CACHE_MISSES",
    "MANIFEST_NAME",
    "WarmResult",
    "build_manifest",
    "install_compile_cache_metrics",
    "verify_manifest",
    "warm",
    "write_manifest",
]

_log = Logger()

#: The jax.monitoring occurrence events the XLA persistent cache emits.
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

COMPILE_CACHE_HITS = "nanofed_compile_cache_hits_total"
COMPILE_CACHE_MISSES = "nanofed_compile_cache_misses_total"

MANIFEST_NAME = "manifest.json"

_metrics_installed = False
#: The registry the FIRST install adopted — later callers' registries are NOT
#: wired (jax.monitoring keeps listeners forever); read this to find where the
#: counters actually land.
_metrics_registry: Any = None
_metrics_lock = threading.Lock()


def install_compile_cache_metrics(registry: Any = None) -> bool:
    """Count persistent-compilation-cache hits and misses as first-class
    metrics (idempotent, process-wide, same one-registry rule as
    ``install_jax_event_bridge``: jax.monitoring keeps listeners forever, so
    only the FIRST caller's registry receives the counters).

    Distinct from the generic ``nanofed_jax_events_total{event=...}`` bridge:
    these two counters are the warm-ship workflow's acceptance test — a warmed
    run shows hits ≈ programs and misses ≈ 0.

    Returns False when jax.monitoring is unavailable."""
    global _metrics_installed, _metrics_registry
    with _metrics_lock:
        if _metrics_installed:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        from nanofed_tpu.observability.registry import get_registry

        reg = registry if registry is not None else get_registry()
        hits = reg.counter(
            COMPILE_CACHE_HITS,
            "XLA persistent compilation cache hits (program replayed, no compile)",
        )
        misses = reg.counter(
            COMPILE_CACHE_MISSES,
            "XLA persistent compilation cache misses (program compiled from scratch)",
        )

        def _on_event(event: str, **kwargs: Any) -> None:
            if event == CACHE_HIT_EVENT:
                hits.inc()
            elif event == CACHE_MISS_EVENT:
                misses.inc()

        try:
            monitoring.register_event_listener(_on_event)
        except Exception:
            return False
        _metrics_installed = True
        _metrics_registry = reg
        return True


def _toolchain() -> dict[str, str]:
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(getattr(jaxlib, "__version__", jax.__version__)),
        "platform": str(devices[0].platform),
        "device_kind": str(
            getattr(devices[0], "device_kind", devices[0].platform)
        ),
        "num_devices": str(len(devices)),
    }


def build_manifest(cache_dir: str | os.PathLike) -> dict[str, Any]:
    """Inventory a cache directory: the producing toolchain plus what is in it
    (XLA cache entries, autotune tables).  Pure read — writes nothing."""
    root = Path(cache_dir)
    xla_entries = 0
    xla_bytes = 0
    autotune_entries: list[dict[str, Any]] = []
    if root.is_dir():
        for p in sorted(root.iterdir()):
            if not p.is_file() or p.name == MANIFEST_NAME:
                continue
            if p.name.startswith("autotune_") and p.suffix == ".json":
                entry: dict[str, Any] = {"file": p.name}
                try:
                    d = json.loads(p.read_text())
                    entry["cache_key"] = d.get("cache_key", "?")[:16]
                    entry["winner"] = d.get("winner")
                except (OSError, json.JSONDecodeError):
                    entry["error"] = "unreadable"
                autotune_entries.append(entry)
            else:
                xla_entries += 1
                xla_bytes += p.stat().st_size
    return {
        "version": 1,
        "created_unix": round(time.time(), 3),
        "cache_dir": str(root),
        "toolchain": _toolchain(),
        "xla_entries": xla_entries,
        "xla_bytes": xla_bytes,
        "autotune_entries": autotune_entries,
    }


def write_manifest(
    cache_dir: str | os.PathLike, extra: dict[str, Any] | None = None,
) -> Path:
    """Stamp ``manifest.json`` into the cache directory (atomic rename)."""
    root = Path(cache_dir)
    root.mkdir(parents=True, exist_ok=True)
    manifest = build_manifest(root)
    if extra:
        manifest.update(extra)
    path = root / MANIFEST_NAME
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(manifest, indent=2, sort_keys=True) + "\n")
    tmp.replace(path)
    return path


def verify_manifest(cache_dir: str | os.PathLike) -> dict[str, Any]:
    """The receiving end of a shipped cache: does the manifest's toolchain
    match THIS host?  Returns ``{"compatible": bool, "reasons": [...],
    "manifest": ...}`` — never raises on a missing/corrupt manifest (that is
    itself a stated reason).  XLA would key-miss a foreign cache silently and
    recompile everything; this says so up front."""
    path = Path(cache_dir) / MANIFEST_NAME
    reasons: list[str] = []
    manifest: dict[str, Any] | None = None
    try:
        manifest = json.loads(path.read_text())
    except OSError:
        reasons.append(f"no manifest at {path} (cache never warmed, or not shipped)")
    except json.JSONDecodeError as e:
        reasons.append(f"manifest unreadable: {e}")
    if manifest is not None:
        shipped = manifest.get("toolchain", {})
        here = _toolchain()
        for dim in ("jax", "jaxlib", "platform"):
            if shipped.get(dim) != here[dim]:
                reasons.append(
                    f"{dim} mismatch: cache built under {shipped.get(dim)!r}, "
                    f"this host runs {here[dim]!r} — XLA entries will miss"
                )
        if shipped.get("device_kind") != here["device_kind"]:
            reasons.append(
                f"device_kind differs: {shipped.get('device_kind')!r} vs "
                f"{here['device_kind']!r} — autotune tables keyed elsewhere"
            )
    return {
        "compatible": not reasons,
        "reasons": reasons,
        "manifest": manifest,
    }


@dataclass
class WarmResult:
    """What :func:`warm` did: where the cache lives, what was compiled, and
    the stamped manifest."""

    cache_dir: str
    manifest_path: str
    manifest: dict[str, Any]
    autotune: Any = None
    programs: list[dict[str, Any]] = field(default_factory=list)

    def to_dict(self) -> dict[str, Any]:
        return {
            "cache_dir": self.cache_dir,
            "manifest_path": self.manifest_path,
            "manifest": self.manifest,
            "programs": self.programs,
            **(
                {"autotune": self.autotune.telemetry_payload()}
                if self.autotune is not None else {}
            ),
        }


def warm(
    model: Any,
    population: Any,
    training: Any = None,
    *,
    num_rounds: int,
    participation: float = 1.0,
    eval_every: int = 0,
    space: Any = None,
    adapter: Any = None,
    telemetry: Any = None,
    force: bool = False,
    compile_budget_s: float | None = None,
    candidate_deadline_s: float | None = None,
) -> WarmResult:
    """Pre-compile the coordinator/autotuner program set into the persistent
    cache, off the critical path.

    Runs the full :func:`~nanofed_tpu.tuning.autotuner.autotune` sweep with
    the persistent compilation cache enabled (at
    ``utils.platform.compilation_cache_dir()``) — every
    candidate round program the coordinator could dispatch gets lowered,
    compiled, and serialized into the cache (the sweep result itself lands as
    an ``autotune_*.json`` table beside the XLA entries).  One ``compile``
    telemetry record is emitted per compiled program when ``telemetry`` is
    given, the hit/miss counters are installed, and the directory is stamped
    with a manifest so the receiving host can :func:`verify_manifest` before
    trusting it.  ``force=True`` re-sweeps over a warm autotune table (the
    XLA entries still hit, so a forced re-warm is cheap)."""
    from nanofed_tpu.tuning.autotuner import autotune
    from nanofed_tpu.utils.platform import enable_compilation_cache

    path = enable_compilation_cache()
    install_compile_cache_metrics()
    t0 = time.perf_counter()
    result = autotune(
        model, population, training,
        num_rounds=num_rounds, participation=participation,
        eval_every=eval_every, space=space, adapter=adapter,
        cache_dir=path, out_dir=None, telemetry=telemetry, force=force,
        include_epilogues=False,
        compile_budget_s=compile_budget_s,
        candidate_deadline_s=candidate_deadline_s,
    )
    # On an autotune cache hit nothing compiled THIS pass — the outcomes'
    # compile_seconds describe the original sweep, not this warm.
    programs = [] if result.cache_hit else [
        {
            "program": _cand_name(o.config),
            "compile_seconds": o.cost["compile_seconds"],
            "feasible": o.feasible,
        }
        for o in result.outcomes
        if o.cost.get("compile_seconds") is not None
    ]
    manifest_path = write_manifest(path, extra={
        "warmed": {
            "model": getattr(model, "name", type(model).__name__),
            "cache_key": result.cache_key[:16],
            "programs": programs,
            "compiles": result.compiles,
            "compile_seconds_total": round(result.compile_seconds_total, 4),
            "cache_hit": result.cache_hit,
            "warm_seconds": round(time.perf_counter() - t0, 4),
        },
    })
    _log.info(
        "compile cache warmed at %s: %d programs, %.1fs compile (%s)",
        path, result.compiles, result.compile_seconds_total,
        "autotune cache hit" if result.cache_hit else "fresh sweep",
    )
    return WarmResult(
        cache_dir=str(path),
        manifest_path=str(manifest_path),
        manifest=json.loads(Path(manifest_path).read_text()),
        autotune=result,
        programs=programs,
    )


def _cand_name(config: Any) -> str:
    from nanofed_tpu.tuning.autotuner import candidate_program_name

    return candidate_program_name(config)
