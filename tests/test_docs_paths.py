"""The documents name files that exist. A path in backticks, or one a
document runs with ``python``, is checked when it ends in ``.py`` and
lies under tools/, tests/, benchmark/, paddle_tpu/ or examples/, or
has no directory (then it is a file at the root, or shorthand for a
file of that name under one of the five); a bare ``*.md`` is a file at
the root. Paths under other prefixes (``generation/engine.py``) and
what a run writes (``*.json``) are not checked."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = ("tools", "tests", "benchmark", "paddle_tpu", "examples")

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"(?<![\w/.*-])([\w./-]*[\w-]+\.(?:py|md))\b")
_RUN = re.compile(r"python3?\s+(?:-m\s+pytest\s+)?([\w./-]+\.py)")


def _named(text):
    names = set(_RUN.findall(text))
    for span in _TICKED.findall(text):
        names.update(_PATH.findall(span))
    return names


def _basenames():
    out = set()
    for tree in TREES:
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, tree)):
            out.update(f for f in files if f.endswith(".py"))
    return out


@pytest.mark.parametrize("doc", ["README.md", "PERF.md",
                                 ".github/workflows/ci.yml"])
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        names = _named(f.read())
    nested = _basenames()
    missing = []
    for name in sorted(names):
        if "/" in name and name.split("/", 1)[0] not in TREES:
            continue
        if not (os.path.exists(os.path.join(REPO, name))
                or ("/" not in name and name in nested)):
            missing.append(name)
    assert names, f"{doc}: the patterns found no path at all"
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
