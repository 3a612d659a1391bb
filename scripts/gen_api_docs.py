#!/usr/bin/env python
"""Generate the API reference (``docs/api/*.md``) from the package's docstrings.

The reference publishes a Sphinx API site via readthedocs; this repo keeps docs in
markdown, so the reference pages are generated straight from ``inspect`` — every public
module, class, function and dataclass with its signature and docstring.  Regenerate with
``make api-docs`` (or ``python scripts/gen_api_docs.py``) after API changes; CI treats a
dirty regeneration as a failure the same way formatters are treated.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

MODULES = [
    ("core", ["nanofed_tpu.core.types", "nanofed_tpu.core.interfaces",
              "nanofed_tpu.core.exceptions"]),
    ("data", ["nanofed_tpu.data.datasets", "nanofed_tpu.data.partition",
              "nanofed_tpu.data.batching"]),
    ("models", ["nanofed_tpu.models.base", "nanofed_tpu.models.linear",
                "nanofed_tpu.models.mnist", "nanofed_tpu.models.resnet",
                "nanofed_tpu.models.transformer", "nanofed_tpu.nn"]),
    ("adapters", ["nanofed_tpu.adapters.lora",
                  "nanofed_tpu.adapters.evidence"]),
    ("fleet", ["nanofed_tpu.fleet.profile", "nanofed_tpu.fleet.aggregate",
               "nanofed_tpu.fleet.wire", "nanofed_tpu.fleet.gateway",
               "nanofed_tpu.fleet.swarm", "nanofed_tpu.fleet.tuning",
               "nanofed_tpu.fleet.evidence"]),
    ("trainer", ["nanofed_tpu.trainer.config", "nanofed_tpu.trainer.local",
                 "nanofed_tpu.trainer.private", "nanofed_tpu.trainer.scaffold",
                 "nanofed_tpu.trainer.schedules",
                 "nanofed_tpu.trainer.personalization",
                 "nanofed_tpu.trainer.callbacks", "nanofed_tpu.trainer.api"]),
    ("aggregation", ["nanofed_tpu.aggregation.base", "nanofed_tpu.aggregation.fedavg",
                     "nanofed_tpu.aggregation.privacy",
                     "nanofed_tpu.aggregation.robust"]),
    ("parallel", ["nanofed_tpu.parallel.mesh", "nanofed_tpu.parallel.round_step",
                  "nanofed_tpu.parallel.multi_round",
                  "nanofed_tpu.parallel.scaffold_step",
                  "nanofed_tpu.parallel.resilience"]),
    ("privacy", ["nanofed_tpu.privacy.config", "nanofed_tpu.privacy.noise",
                 "nanofed_tpu.privacy.accounting", "nanofed_tpu.privacy.mechanisms"]),
    ("security", ["nanofed_tpu.security.validation", "nanofed_tpu.security.signing",
                  "nanofed_tpu.security.secure_agg"]),
    ("persistence", ["nanofed_tpu.persistence.serialization",
                     "nanofed_tpu.persistence.model_manager",
                     "nanofed_tpu.persistence.state_store",
                     "nanofed_tpu.persistence.generation_store"]),
    ("orchestration", ["nanofed_tpu.orchestration.types",
                       "nanofed_tpu.orchestration.coordinator"]),
    ("communication", ["nanofed_tpu.communication.codec",
                       "nanofed_tpu.communication.transport",
                       "nanofed_tpu.communication.http_server",
                       "nanofed_tpu.communication.http_client",
                       "nanofed_tpu.communication.retry",
                       "nanofed_tpu.communication.network_coordinator"]),
    ("faults", ["nanofed_tpu.faults.plan",
                "nanofed_tpu.faults.injector",
                "nanofed_tpu.faults.host_injector"]),
    ("ingest", ["nanofed_tpu.ingest.buffer",
                "nanofed_tpu.ingest.pipeline"]),
    ("loadgen", ["nanofed_tpu.loadgen.swarm",
                 "nanofed_tpu.loadgen.harness"]),
    ("service", ["nanofed_tpu.service.scheduler",
                 "nanofed_tpu.service.tenant",
                 "nanofed_tpu.service.service",
                 "nanofed_tpu.service.harness"]),
    ("observability", ["nanofed_tpu.observability.registry",
                       "nanofed_tpu.observability.spans",
                       "nanofed_tpu.observability.telemetry",
                       "nanofed_tpu.observability.profiling",
                       "nanofed_tpu.observability.tracing",
                       "nanofed_tpu.observability.critical_path"]),
    ("tuning", ["nanofed_tpu.tuning.autotuner",
                "nanofed_tpu.tuning.epilogues"]),
    ("analysis", ["nanofed_tpu.analysis.fedlint",
                  "nanofed_tpu.analysis.program_audit",
                  "nanofed_tpu.analysis.contracts"]),
    ("ops", ["nanofed_tpu.ops.reduce",
             "nanofed_tpu.ops.quantize"]),
    ("utils", ["nanofed_tpu.utils.logger", "nanofed_tpu.utils.profiling",
               "nanofed_tpu.utils.trees", "nanofed_tpu.utils.platform",
               "nanofed_tpu.utils.clock", "nanofed_tpu.utils.aio",
               "nanofed_tpu.utils.dates"]),
    ("top-level", ["nanofed_tpu.experiments", "nanofed_tpu.cli"]),
]


def _sig(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Function-object defaults repr with a memory address ("<function sum at 0x...>"),
    # which would churn the generated files on every run; keep just the name.
    return re.sub(r"<function (\S+) at 0x[0-9a-f]+>", r"<function \1>", sig)


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else "*(undocumented)*"


def _summary(obj) -> str:
    """First PARAGRAPH of the docstring as one line (a first physical line can end
    mid-sentence when the source wraps)."""
    return " ".join(_doc(obj).split("\n\n")[0].split())


def _is_public(name: str) -> bool:
    return not name.startswith("_")


def document_module(modname: str) -> str:
    try:
        mod = importlib.import_module(modname)
    except ImportError as e:
        # An optional dependency (e.g. `cryptography` for the security modules) may
        # be absent in this environment; keep the page generable rather than dying
        # halfway with some files regenerated and others stale.
        print(f"  SKIPPED {modname}: {e}", file=sys.stderr)
        return "\n".join([
            f"## `{modname}`", "",
            f"*(not regenerated here — import failed: `{e}`; rerun `make api-docs` "
            "in an environment with the module's optional dependencies)*", "",
        ])
    lines = [f"## `{modname}`", "", _doc(mod), ""]
    members = []
    for name, obj in vars(mod).items():
        if not _is_public(name):
            continue
        # Plain classes/functions, plus functools.wraps'd wrapper objects —
        # notably jax.jit callables (the Pallas ops are module-level jits):
        # they carry the wrapped function's __module__/__doc__/signature, and
        # skipping them silently dropped every kernel from the ops page.
        wrapped_fn = inspect.isfunction(getattr(obj, "__wrapped__", None))
        if inspect.isclass(obj) or inspect.isfunction(obj) or (
            callable(obj) and wrapped_fn
        ):
            if getattr(obj, "__module__", None) != modname:
                continue  # re-exports documented at their home module
            members.append((name, obj))
    for name, obj in members:
        if inspect.isclass(obj):
            kind = "dataclass" if dataclasses.is_dataclass(obj) else "class"
            lines += [f"### {kind} `{name}{_sig(obj)}`", "", _doc(obj), ""]
            if dataclasses.is_dataclass(obj):
                rows = [
                    f"| `{f.name}` | `{getattr(f.type, '__name__', f.type)}` | "
                    f"`{f.default if f.default is not dataclasses.MISSING else '—'}` |"
                    for f in dataclasses.fields(obj)
                ]
                lines += ["| field | type | default |", "|---|---|---|", *rows, ""]
            for mname, meth in vars(obj).items():
                if not _is_public(mname):
                    continue
                func = meth.__func__ if isinstance(meth, (classmethod, staticmethod)) else meth
                if inspect.isfunction(func) and inspect.getdoc(func):
                    lines += [f"- **`{mname}{_sig(func)}`** — {_summary(func)}"]
            lines += [""]
        else:
            lines += [f"### `{name}{_sig(obj)}`", "", _doc(obj), ""]
    return "\n".join(lines)


def main() -> int:
    outdir = REPO / "docs" / "api"
    outdir.mkdir(parents=True, exist_ok=True)
    index = ["# API reference", "",
             "Generated from docstrings by `scripts/gen_api_docs.py` — do not edit by",
             "hand; run `make api-docs` after API changes.", ""]
    for group, mods in MODULES:
        fname = f"{group.replace('-', '_')}.md"
        parts = [f"# `{group}` API", ""]
        for m in mods:
            parts.append(document_module(m))
        (outdir / fname).write_text("\n".join(parts) + "\n")
        index.append(f"- [{group}]({fname}): " + ", ".join(f"`{m}`" for m in mods))
        print(f"  wrote docs/api/{fname}")
    (outdir / "index.md").write_text("\n".join(index) + "\n")
    print("wrote docs/api/index.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
