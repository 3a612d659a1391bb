"""Platform helpers: the virtual CPU mesh for tests, and where the compile cache lives.

Tests and dry runs use a virtual multi-device CPU mesh (``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count``); :func:`force_cpu_mesh` sets that up from
inside a process that has not yet initialized a backend.  Anything that measures runs
on the device JAX finds and fails when that is not the one it needs — nothing here
falls back from a missing accelerator to the CPU.

The persistent compilation cache has ONE location rule (:func:`compilation_cache_dir`):
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — JAX reads that variable
itself and no code here touches the setting — and otherwise ``.jax_cache`` in the
checkout that holds this package.  The path is part of the cache's usefulness: a
directory that moves with the caller's cwd never hits.
"""

from __future__ import annotations

import os
import re
import sys
import time
from pathlib import Path
from typing import Any

_CHECKOUT = Path(__file__).resolve().parents[2]


def log_stage(msg: str, *, t0: float | None = None) -> None:
    """Timestamped progress line on stderr (flushed), so a killed process leaves a
    diagnostic tail showing the last stage reached."""
    stamp = time.strftime("%H:%M:%S")
    rel = f" +{time.time() - t0:7.1f}s" if t0 is not None else ""
    print(f"[{stamp}{rel}] {msg}", file=sys.stderr, flush=True)


def force_cpu_mesh(n_devices: int = 8) -> None:
    """Force a virtual ``n_devices``-device CPU mesh.  Safe to call whether or not jax
    is already imported; must be called before the first backend initialization
    (``jax.devices()`` etc.)."""
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        # Replace a pre-set count (it may differ from n_devices) rather than skip.
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax binds JAX_PLATFORMS at import; a process that imported jax first needs
    # the config value set too.
    jax.config.update("jax_platforms", "cpu")


def require_tpu() -> tuple[list, Any]:
    """``(jax.devices(), peaks)`` when the default backend is a TPU whose
    ``device_kind`` the peaks table (``observability.profiling.TPU_PEAKS``) knows;
    ``SystemExit`` otherwise.  For entry points that measure, or that prove the chip
    path runs: a missing or unknown chip is a failure there, never a mode."""
    import jax

    from nanofed_tpu.observability.profiling import peaks_for_device_kind

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"need a TPU, but JAX's default backend is {d.platform!r} "
            f"({len(devices)}x {d.device_kind}); refusing to run on it"
        )
    peaks = peaks_for_device_kind(d.device_kind, d.platform)
    if peaks is None:
        raise SystemExit(
            f"device_kind {d.device_kind!r} is not in the peaks table "
            "(observability.profiling.TPU_PEAKS); add its published peaks first"
        )
    return devices, peaks


def compilation_cache_dir() -> str:
    """Where the persistent compilation cache lives: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` — anchored on this package's location,
    never on the caller's cwd."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache at :func:`compilation_cache_dir`
    and return that directory.  Call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already configured itself from
    the environment and this function changes nothing.  Otherwise it points JAX at
    the checkout's ``.jax_cache`` and keeps EVERY compiled program there (minimum
    compile time 0): with a threshold, the sub-second programs that straddle it are
    kept by one run and recompiled by the next, and a warm run never reaches zero
    misses."""
    path = compilation_cache_dir()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return path
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
