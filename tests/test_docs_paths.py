"""A document names no file that is gone.

Every back-quoted token of a document that ends like a source or record
file has to name a tracked file. It guards against the names of deleted
files (the pre-chip bench scripts and their records went in PR 30 and
nothing else in the suite would have noticed the documents that still
sent a reader to them); it is no link checker.
"""

import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (
    "README.md",
    "docs/PARITY.md",
    "docs/design.md",
    "docs/collectives.md",
    "docs/devtools.md",
    "docs/telemetry.md",
    "docs/knobs.md",
)
SUFFIXES = (".py", ".md", ".json", ".yaml", ".sh", ".cpp", ".c")
# a token with a `/` is looked up under each of these
ROOTS = ("", "kungfu_tpu/", "tests/", "docs/", "benchmark/", "native/")
# files that a run writes and git ignores
WRITTEN_BY_A_RUN = {
    "docs/devtools.md": {".kfcheck-cache.json"},  # kfcheck's cache
    "docs/telemetry.md": {"trace.json"},  # a saved /trace download
}


@pytest.fixture(scope="module")
def tracked():
    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
            check=True,
        ).stdout.split("\n")
    except (OSError, subprocess.CalledProcessError):
        out = []
    files = [f for f in out if f]
    if not files:
        # a checkout without its .git: what is on disk, less what runs leave
        for base, dirs, names in os.walk(REPO):
            dirs[:] = [
                d for d in dirs
                if not d.startswith(".")
                and d not in ("__pycache__", "chiprun_out")
            ]
            rel = os.path.relpath(base, REPO)
            files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return frozenset(files) | {os.path.basename(f) for f in files}


def _names_a_file(token: str, tracked) -> bool:
    if "/" in token:
        return any(root + token in tracked for root in ROOTS)
    return token in tracked  # a bare name: any tracked file's base name


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_is_tracked(doc, tracked):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    tokens = {
        t for t in re.findall(r"`([^`\n]+)`", text)
        if t.endswith(SUFFIXES)
        and not t.startswith("/")
        and not any(c in t for c in " *<{")
    }
    assert tokens, f"{doc}: no file name found, so the rule reads nothing"
    missing = sorted(
        t for t in tokens - WRITTEN_BY_A_RUN.get(doc, set())
        if not _names_a_file(t, tracked)
    )
    assert not missing, f"{doc} names files that are not tracked: {missing}"
