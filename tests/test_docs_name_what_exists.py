"""The documents a newcomer and the next session read first name files that
exist: a path in back-ticks that starts with ``tools/``, ``tests/``,
``paddle_tpu/`` or ``benchmark/``, or is a root file ending ``.py`` or
``.json``, is there (a trailing ``:line`` is cut). A deletion that leaves a
document pointing at what went fails here, not in a reader's shell."""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TICKED = re.compile(r"`([^`\s]+)`")
TREES = ("tools/", "tests/", "paddle_tpu/", "benchmark/")
ROOT_FILE = re.compile(r"[\w.-]+\.(py|json)")


def named_paths(text):
    """Every back-ticked word that claims to be a file of this repository."""
    for word in TICKED.findall(text):
        path = re.sub(r":[\d,:-]+$", "", word)  # `file.py:12`, `file.py:12-40`
        if path.startswith(TREES) or ROOT_FILE.fullmatch(path):
            yield path


def missing(path):
    """A name with ``*``, ``{a,b}`` or ``<x>`` stands for a family of files:
    its directory is what has to exist."""
    m = re.search(r"[*{<]", path)
    if m:
        path = os.path.dirname(path[:m.start()])
    return not os.path.exists(os.path.join(ROOT, path))


@pytest.mark.parametrize("document", ["README.md", "PERF.md"])
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(ROOT, document)) as fh:
        gone = sorted({p for p in named_paths(fh.read()) if missing(p)})
    assert not gone, f"{document} names files that do not exist: {gone}"
