"""The documents name only what is there: every repo path a document gives in
backticks or as a link target exists, and every ``make <target>`` it names is
a target of the ``Makefile``.  Pure text, no import of the package: a deleted
file or target fails the sentence that still points at it."""

import functools
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    os.path.relpath(p, REPO_ROOT)
    for pattern in (
        "README.md", "docs/*.md", "deploy/README.md", "examples/*/README.md",
        "Makefile", ".github/workflows/ci.yaml",
        ".claude/skills/verify/SKILL.md",
    )
    for p in glob.glob(os.path.join(REPO_ROOT, pattern))
)

_SUFFIXES = (".py", ".md", ".json", ".yaml")
_TOP_LEVEL = set(os.listdir(REPO_ROOT))
_PACKAGE = "seldon_core_tpu"
_SUBPACKAGES = {
    d for d in os.listdir(os.path.join(REPO_ROOT, _PACKAGE))
    if os.path.isdir(os.path.join(REPO_ROOT, _PACKAGE, d))
}
# what a run writes or a user brings: named in the documents, never committed
_WRITTEN_BY_A_RUN = ("chiprun_out/", ".jax_cache/", ".benchmark_cache/")

_BACKTICKED = re.compile(r"`([^`\n]+)`")
_LINK_TARGET = re.compile(r"\]\(([^)\s]+)\)")
_PATH = re.compile(r"[\w.\-/]+")
_MAKE = re.compile(r"(?:`|^\s*|run:\s*)make ([a-z][\w-]*)", re.M)


@functools.cache
def _makefile_targets() -> frozenset[str]:
    text = open(os.path.join(REPO_ROOT, "Makefile")).read()
    return frozenset(re.findall(r"^([a-z][\w-]*):", text, re.M))


def _candidates(text: str):
    """(word, is_link) for every word of a backticked span and every markdown
    link target; a span the sentence gives as the reference tree's is left out."""
    for m in _BACKTICKED.finditer(text):
        if "reference" in text[max(0, m.start() - 60):m.start()].lower():
            continue
        for word in m.group(1).split():
            yield word, False
    for target in _LINK_TARGET.findall(text):
        yield target, True


def _clean(raw: str) -> str | None:
    if "://" in raw or any(c in raw for c in "*<>{}$"):
        return None  # URL, glob, placeholder, shell variable
    raw = raw.split("#", 1)[0].split("::", 1)[0]
    raw = re.sub(r":[\d,\-–]+$", "", raw).rstrip(".,;:)")
    if not raw.endswith(_SUFFIXES) or _PATH.fullmatch(raw) is None:
        return None
    if raw.startswith(("/", "~", "reference/")) or raw.startswith(_WRITTEN_BY_A_RUN):
        return None  # outside the repo, the reference tree, a run's output
    return raw


@functools.cache
def _basenames() -> frozenset[str]:
    """Every file name of the tree; dot-directories (``.git``, a scratch copy of
    another commit) and what a run leaves behind are no part of it."""
    names: set[str] = set()
    for _, dirs, files in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ("chiprun_out", "__pycache__")]
        names.update(files)
    return frozenset(names)


def _missing(doc: str, text: str) -> list[str]:
    here = os.path.dirname(os.path.join(REPO_ROOT, doc))
    missing = []
    for raw, is_link in _candidates(text):
        path = _clean(raw)
        if path is None:
            continue
        first = path.split("/", 1)[0]
        if "/" not in path and not is_link:
            found = path in _basenames()  # a bare name: some file of the repo
        elif is_link or os.path.exists(os.path.join(here, path)):
            found = os.path.exists(os.path.join(here, path))
        elif first in _TOP_LEVEL:
            found = os.path.exists(os.path.join(REPO_ROOT, path))
        elif first in _SUBPACKAGES:
            found = os.path.exists(os.path.join(REPO_ROOT, _PACKAGE, path))
        else:
            continue  # not a path of this repo (a judge's, a checkpoint's, a user's)
        if not found:
            missing.append(raw)
    return missing


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc):
    text = open(os.path.join(REPO_ROOT, doc)).read()
    assert _missing(doc, text) == []
    unknown = sorted(set(_MAKE.findall(text)) - _makefile_targets())
    assert unknown == [], f"{doc} names make targets the Makefile lacks"


def test_every_kind_of_document_is_covered():
    assert len(DOCUMENTS) >= 26
    for must in ("README.md", "Makefile", "docs/BENCHMARKING.md",
                 ".github/workflows/ci.yaml", ".claude/skills/verify/SKILL.md"):
        assert must in DOCUMENTS
