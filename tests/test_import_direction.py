"""The arrows between the repo's top-level trees point one way.

The package imports nothing of ``tools``, ``benchmark``, ``tests`` or
a root script; the benchmark imports the package but not ``tools``,
``tests`` or a root script; the tools import the package but not
``benchmark``, ``tests`` or a root script. Read off the AST, so an
import inside a function counts.
"""

import ast
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROOT_SCRIPTS = {
    name[:-3] for name in os.listdir(REPO_ROOT) if name.endswith(".py")
}

# tree -> top-level module names nothing under it may import
FORBIDDEN = {
    "dlrover_tpu": {"tools", "benchmark", "tests"} | ROOT_SCRIPTS,
    "benchmark": {"tools", "tests"} | ROOT_SCRIPTS,
    "tools": {"benchmark", "tests"} | ROOT_SCRIPTS,
}


def imported_roots(path: str):
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            yield node.lineno, (node.module or "").split(".")[0]


@pytest.mark.parametrize("tree", sorted(FORBIDDEN))
def test_tree_imports_nothing_above_it(tree):
    assert ROOT_SCRIPTS, "no root script found: the listing is broken"
    wrong = []
    for dirpath, _dirs, files in os.walk(os.path.join(REPO_ROOT, tree)):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            for lineno, root in imported_roots(path):
                if root in FORBIDDEN[tree]:
                    rel = os.path.relpath(path, REPO_ROOT)
                    wrong.append(f"{rel}:{lineno} imports {root}")
    assert wrong == []
