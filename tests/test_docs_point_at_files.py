"""Every file and make target a document sends its reader to exists:
``README.md`` and the files of ``docs/``. A deletion that leaves an
instruction dangling fails here, on the document that carries it."""

import os
import re
import subprocess

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", n) for n in os.listdir(os.path.join(_REPO, "docs"))
    if n.endswith(".md"))

_PY = re.compile(r"(?<![\w/.-])((?:[\w.-]+/)*[\w-]+\.py)\b")
_MAKE = re.compile(r"(?:`|^)make ([a-z][\w-]*)", re.M)     # a command
# Upstream Horovod's own tree, which the documents cite for the mapping,
# and a user's script in a command line: not files of this repository.
_NOT_OURS = re.compile(r"^(horovod/|examples/pytorch|(train|serve)\.py$)")


def _tracked():
    out = subprocess.run(["git", "ls-files", "--cached", "--others",
                          "--exclude-standard"], cwd=_REPO, text=True,
                         capture_output=True)
    if out.returncode == 0 and out.stdout:     # less what is deleted on disk
        return [p for p in out.stdout.split()
                if os.path.exists(os.path.join(_REPO, p))]
    found = []          # no git (the chip machine's copy): walk the tree
    for here, dirs, names in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))]
        found += [os.path.relpath(os.path.join(here, n), _REPO)
                  for n in names]
    return found


@pytest.fixture(scope="module")
def tree():
    files = set(_tracked())
    with open(os.path.join(_REPO, "Makefile")) as f:
        targets = set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))
    return files, targets


def _exists(path, files):
    """A document's path starts at the checkout, at the package, or in the
    directory the sentence is about (`engine.py` in SERVING.md)."""
    return path in files or any(f.endswith("/" + path) for f in files)


@pytest.mark.parametrize("doc", DOCS)
def test_document_points_at_files_that_exist(doc, tree):
    files, targets = tree
    with open(os.path.join(_REPO, doc)) as f:
        text = f.read()
    missing = sorted(
        {p for p in _PY.findall(text)
         if not _NOT_OURS.match(p) and not _exists(p, files)}
        | {f"make {t}" for t in _MAKE.findall(text) if t not in targets})
    assert not missing, f"{doc} names what is not in the tree: {missing}"


def test_the_documents_are_found():
    """An empty parametrisation passes in silence: README.md and the eight
    files of docs/ are what the cases above run over."""
    assert len(DOCS) == 9, DOCS
