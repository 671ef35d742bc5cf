"""Static docs-drift checks.  Every ``TPUMX_*`` environment variable READ
anywhere in mxnet_tpu/ must be documented in docs/env_vars.md (PRs 9 and
11 each had to fix this drift by hand; this makes it a tier-1 failure
instead of a reviewer catch).  And a document that names a file names one
the tree has (PR 43 found thirteen documents teaching files that measured
nothing the driver reads).
"""
import functools
import os
import re

import pytest

pytestmark = pytest.mark.tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# an env READ site: getenv("VAR", ...) / os.environ.get("VAR") /
# os.environ["VAR"] / os.environ.setdefault("VAR", ...) — NOT a mere
# mention in a docstring or comment
_READ = re.compile(
    r'(?:getenv|environ(?:\.get|\.setdefault|\.pop)?)'
    r'\s*[\(\[]\s*f?["\'](TPUMX_[A-Z0-9_]+)["\']')


def _source_files():
    for root, _dirs, files in os.walk(os.path.join(REPO, "mxnet_tpu")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def test_every_env_var_read_in_source_is_documented():
    reads = {}
    for path in _source_files():
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        for m in _READ.finditer(src):
            reads.setdefault(m.group(1), set()).add(rel)
    assert len(reads) > 60, \
        f"scanner regressed: only {len(reads)} env reads found"
    with open(os.path.join(REPO, "docs", "env_vars.md")) as f:
        docs = f.read()
    missing = {v: sorted(files) for v, files in sorted(reads.items())
               if v not in docs}
    assert not missing, (
        "environment variables read in source but missing from "
        f"docs/env_vars.md: {missing} — document them (name, default, "
        "effect) in the appropriate section")


def test_documented_tpumx_vars_exist_in_source():
    """The reverse direction: a TPUMX_ var documented as a knob should
    still be read somewhere (stale docs rows are drift too)."""
    reads = set()
    for path in _source_files():
        with open(path) as f:
            src = f.read()
        for m in _READ.finditer(src):
            reads.add(m.group(1))
        # vars can also be SET for subprocesses; mentions in code strings
        # count as alive
        for m in re.finditer(r'["\'](TPUMX_[A-Z0-9_]+)["\']', src):
            reads.add(m.group(1))
    with open(os.path.join(REPO, "docs", "env_vars.md")) as f:
        docs = f.read()
    documented = set(re.findall(r"`(TPUMX_[A-Z0-9_]+)`", docs))
    # wildcard-family rows (e.g. the TPUMX_FAULT_* umbrella) and names
    # documented for the launcher rather than the library are fine
    stale = {v for v in documented - reads if not v.endswith("_")}
    assert not stale, (
        f"docs/env_vars.md documents {sorted(stale)} but nothing in "
        "mxnet_tpu/ reads them — remove or fix the rows")


# -- a document names files the tree has --------------------------------------
_DOCS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))
# a path under one of the tree's directories (a leading "/" makes it
# somebody else's: /root/reference/docs/faq/perf.md), and a bare file name
_PATH = re.compile(
    r"(?<![\w/.-])((?:mxnet_tpu|tests|tools|perfbench|docs|benchmark|example)"
    r"/[\w./-]*\.(?:py|md|json))\b")
_NAME = re.compile(r"(?<![\w/.-])([A-Za-z_][\w-]*\.(?:py|md))\b")


@functools.lru_cache(maxsize=None)
def _tree_names():
    """Base names of the files git would commit: the tree walked, less
    what ``.gitignore`` lists as a directory."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    names = set()
    for root, dirs, files in os.walk(REPO):
        rel = os.path.relpath(root, REPO)
        dirs[:] = [d for d in dirs if d != ".git" and d not in ignored
                   and os.path.normpath(os.path.join(rel, d)) not in ignored]
        names.update(files)
    return names


@pytest.mark.parametrize("doc", _DOCS)
def test_docs_name_files_in_the_tree(doc):
    """A path with one of the tree's directories in front exists, and a
    bare ``*.py`` / ``*.md`` name is some file's in the tree (``engine.py``
    as shorthand passes; a deleted file's name does not).  Bare ``*.json``
    names are not checked: the documents use them for files a run writes
    (``manifest.json``)."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    gone = sorted({p for p in _PATH.findall(text)
                   if not os.path.exists(os.path.join(REPO, p))})
    gone += sorted({n for n in _NAME.findall(_PATH.sub("", text))
                    if n not in _tree_names()})
    assert not gone, (
        f"{doc} names files the tree does not have: {gone} — reword, or "
        "give another tree's file its root (/root/reference/...)")
