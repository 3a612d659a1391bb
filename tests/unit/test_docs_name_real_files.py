"""The documents that tell a reader what to run name things that exist.

A case per document: every script, module, ``runs/`` record and CLI subcommand it names
is in the tree.  A document that sends its reader to a deleted measurement harness (or to
a subcommand that is an argparse error) fails here, not in the reader's terminal."""

import glob
import importlib.util
import re
from pathlib import Path

import pytest

from nanofed_tpu import cli

REPO = Path(__file__).resolve().parents[2]

DOCUMENTS = [
    "README.md", "CONTRIBUTING.md", "RELEASE.md", "Makefile",
    ".claude/skills/verify/SKILL.md", "docs/performance.md", "docs/tutorial.md",
    "docs/observability.md", "docs/static-analysis.md", "runs/README.md",
]

_PY_FILE = re.compile(r"python3?\s+(?:-[A-Za-z]\s+)*([\w./-]+\.py)\b")
_SCRIPT = re.compile(r"\bscripts/\w+\.py\b")
_MODULE = re.compile(r"python3?\s+-m\s+(nanofed_tpu(?:\.\w+)*)")
# A record under runs/: a path with an extension (a bare ``runs/<dir>`` is where a
# command writes, not something a reader is sent to).  ``*`` stands for a stamp.
_RECORD = re.compile(r"\bruns/[\w*.-]+\.(?:json|log|md)\b")
# ``nanofed-tpu <sub>`` where it is a command (in backticks or first on its line: the
# project's name also appears in prose), ``nanofed_tpu.cli <sub>``, and ``cli <sub>`` or
# ``cli <sub>|<sub>`` in backticks.
_SUBCOMMAND = re.compile(
    r"(?:(?:^[ \t]*(?:\$ )?|`)nanofed-tpu|nanofed_tpu\.cli|`cli)[ \t]+([a-z][a-z|-]*)\b",
    re.MULTILINE,
)


def _named(text: str) -> dict[str, set[str]]:
    return {
        "file": set(_PY_FILE.findall(text)) | set(_SCRIPT.findall(text)),
        "module": set(_MODULE.findall(text)),
        "record": set(_RECORD.findall(text)),
        "subcommand": {s for m in _SUBCOMMAND.findall(text) for s in m.split("|")},
    }


def _module_exists(name: str) -> bool:
    try:
        return importlib.util.find_spec(name) is not None
    except ModuleNotFoundError:  # a parent package that is not there
        return False


def _subcommand_exists(name: str) -> bool:
    try:
        cli.main([name, "--help"])
    except SystemExit as exit_:
        return exit_.code == 0
    return False


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document, capsys):
    named = _named((REPO / document).read_text())
    missing = (
        [f for f in sorted(named["file"]) if not (REPO / f).is_file()]
        + [m for m in sorted(named["module"]) if not _module_exists(m)]
        + [r for r in sorted(named["record"]) if not glob.glob(str(REPO / r))]
        + [f"cli {s}" for s in sorted(named["subcommand"]) if not _subcommand_exists(s)]
    )
    capsys.readouterr()  # the subcommands' help texts
    assert not missing, f"{document} names what is not in the tree: {missing}"
    assert any(named.values()), f"{document} names nothing to run: wrong list, or wrong patterns"
