"""The documents a user reads name only files that exist.

Checked: every path a document writes under one of the repo's own
directories, and every root script it tells the reader to run
(``python[3] <name>.py``). Not checked: bare file names (the documents
use them for run-time files such as ``flight.json`` and for the
reference's modules) and history (``CHANGES.md``, ``PERF.md``,
``ROADMAP.md``).
"""

import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "docs/DESIGN.md",
    "docs/PARITY.md",
    ".claude/skills/verify/SKILL.md",
]

OWN_DIRS = (
    "dlrover_tpu", "tools", "tests", "benchmark", "examples", "docs",
    "native",
)
# a path under one of the repo's directories, not the tail of a longer
# path or of a URL
_PATH = re.compile(
    r"(?<![\w/.<>-])((?:%s)/[\w./*<>{}-]+)" % "|".join(OWN_DIRS)
)
_ROOT_SCRIPT = re.compile(r"\bpython3?\s+(\w+\.py)\b")
# a placeholder or a pattern, not one file's name
_NOT_A_NAME = re.compile(r"[*<>{}]|\.\.\.")


def named_paths(text: str) -> set[str]:
    names = {m.group(1).rstrip(".") for m in _PATH.finditer(text)}
    names = {n for n in names if not _NOT_A_NAME.search(n)}
    return names | set(_ROOT_SCRIPT.findall(text))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document):
    with open(os.path.join(REPO_ROOT, document)) as f:
        names = named_paths(f.read())
    assert names, f"{document} names no file: the pattern is broken"
    missing = sorted(
        n for n in names
        if not os.path.exists(os.path.join(REPO_ROOT, n))
    )
    assert missing == [], f"{document} names files that do not exist"
