"""The documents cite what is in the tree.

A document that names a script or a record as its evidence is read as a
claim that the evidence can be opened. The first twenty PRs left dozens of
such names whose files nothing else read; this holds every path and every
root record a document names to the tree, so that a file cannot go, nor a
claim be written, without the other side noticing.
"""

import glob
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
TOP = ("vtpu/", "vbench/", "tests/", "hack/", "benchmarks/", "docs/",
       "charts/", "libvtpu/", "examples/", "docker/")
DOCS = ["README.md", "benchmarks/README.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md"))

# a backticked string, or the target of a markdown link
_CITE = re.compile(r"`([^`\n]+)`|\]\(([^)\s]+)\)")
_RECORD = re.compile(r"[A-Za-z0-9_]+\.json")


def _cites(text):
    for m in _CITE.finditer(text):
        yield (m.group(1) or m.group(2)).strip()


def _is_there(cite):
    """A file, a directory, a pattern that matches one, or a module with a
    name in it: ``a/b.py:12``, ``a/b.py::test_x``, ``a/b.Name``, ``a/<x>.py``."""
    path = cite.split()[0].split("::")[0].split("#")[0]
    path = re.sub(r":\d+(-\d+)?$", "", path).rstrip("/.,;")
    pattern = re.sub(r"<[^>]*>|\{[^}]*\}", "*", path)
    # the path itself, or a module named without its suffix, then the same
    # with an attribute (``.Name``) cut off its end
    stem = pattern
    while True:
        if glob.glob(str(ROOT / stem)) or glob.glob(str(ROOT / (stem + ".py"))):
            return True
        if "." not in stem.rsplit("/", 1)[-1]:
            return False
        stem = stem.rsplit(".", 1)[0]


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_cites_only_what_exists(doc):
    text = (ROOT / doc).read_text()
    missing = sorted({
        c for c in _cites(text)
        if (c.startswith(TOP) and not _is_there(c))
        or (_RECORD.fullmatch(c) and not (ROOT / c).is_file())})
    assert not missing, (
        f"{doc} cites what is not in the tree: {missing} — name what holds "
        f"the behaviour now, or take the sentence out")
