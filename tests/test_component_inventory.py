"""Inventory drift guard: docs/component_inventory.md is the parity map
between components and the tests that prove them — it must not rot as
either side grows.

Two directions:

* every ``tests/test_*.py`` file must appear in the inventory (a new
  test suite without a row is invisible coverage);
* every module under ``distributed_learning_tpu/`` must be mapped (by
  package-relative path or basename) so no subsystem ships untracked.

Package plumbing (``__init__.py``/``__main__.py``) is exempt: it holds
re-exports and CLI dispatch, which the module rows already cover.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "component_inventory.md")
PKG = os.path.join(REPO, "distributed_learning_tpu")

_EXEMPT_BASENAMES = {"__init__.py", "__main__.py"}


def _doc_text() -> str:
    with open(DOC, "r", encoding="utf-8") as fh:
        return fh.read()


def test_every_test_file_is_in_the_inventory():
    doc = _doc_text()
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    missing = [
        fn
        for fn in sorted(os.listdir(tests_dir))
        if fn.startswith("test_") and fn.endswith(".py") and fn not in doc
    ]
    assert not missing, (
        "tests with no row in docs/component_inventory.md (add one so "
        f"the parity map stays honest): {missing}"
    )


def test_every_package_module_is_mapped():
    doc = _doc_text()
    missing = []
    for dirpath, dirnames, filenames in os.walk(PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py") or fn in _EXEMPT_BASENAMES:
                continue
            rel = os.path.relpath(
                os.path.join(dirpath, fn), PKG
            ).replace(os.sep, "/")
            if rel not in doc and os.path.basename(rel) not in doc:
                missing.append(rel)
    assert not missing, (
        "distributed_learning_tpu modules unmapped in "
        f"docs/component_inventory.md: {missing}"
    )


# --------------------------------------------------------------------- #
# Documents name files that exist                                       #
# --------------------------------------------------------------------- #
#: The documents a builder is told to read.  ``CHANGES.md`` is history
#: and names what each PR found, deleted files included.
_DOCUMENTS = [
    "README.md", "CLAUDE.md", "BASELINE.md", "PERF.md", "ROADMAP.md",
    "examples/README.md", ".claude/skills/verify/SKILL.md",
] + sorted(
    os.path.relpath(p, REPO).replace(os.sep, "/")
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

#: What makes a back-quoted token a file of a checkout (a trailing ``/``
#: makes it a directory).  Notebooks are the reference's, binaries and
#: traces are made at run time.
_FILE_EXT = re.compile(
    r"[^/.][^/]*\.(py|md|json|jsonl|sh|cpp|h|txt|toml|cfg|ini|yaml|yml|csv)$"
)


def _cited_paths(text):
    """Back-quoted single tokens that read as a relative path: a file
    extension or a trailing ``/``, after ``::name`` / ``:line`` suffixes.
    Left out by rule: absolute paths (``/root/``, ``/opt/``, ``/tmp/``:
    outside the checkout), anything with a glob or placeholder
    character (the token must be plain path characters)."""
    for m in re.finditer(r"`([^`\n]+)`", text):
        token = m.group(1).strip()
        token = re.sub(r"(::.*|:\d[\d,\-]*)$", "", token)
        if not re.fullmatch(r"[\w.\-/+]+", token) or token.startswith("/"):
            continue
        if token.endswith("/") or _FILE_EXT.search(token):
            yield token


@functools.lru_cache(maxsize=None)
def _checkout():
    """Every file and directory (with a trailing ``/``) of the checkout,
    and the directories ``.gitignore`` lists: those are made at run
    time, so a document may name one that is not there yet; and the
    text of ``SURVEY.md``."""
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as fh:
        made_at_run_time = {
            line.strip() for line in fh if line.strip().endswith("/")
        }
    paths = set()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [
            d for d in dirnames
            if d != ".git" and d + "/" not in made_at_run_time
        ]
        rel = os.path.relpath(dirpath, REPO).replace(os.sep, "/")
        prefix = "" if rel == "." else rel + "/"
        paths.update(prefix + d + "/" for d in dirnames)
        paths.update(prefix + f for f in filenames)
    with open(os.path.join(REPO, "SURVEY.md"), encoding="utf-8") as fh:
        return paths, made_at_run_time, fh.read()


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_documents_name_files_that_exist(document):
    """A path a document names resolves: it is the tail of a path in the
    checkout (``training/trainer.py`` names
    ``distributed_learning_tpu/training/trainer.py``), or a directory
    ``.gitignore`` says is made at run time, or one of the reference's
    own files, which are the ones ``SURVEY.md`` (the map of the
    reference) names."""
    paths, made_at_run_time, survey = _checkout()
    with open(os.path.join(REPO, document), encoding="utf-8") as fh:
        cited = sorted(set(_cited_paths(fh.read())))
    assert cited or document.startswith("docs/"), "the scan found nothing"
    missing = [
        p for p in cited
        if not any(q == p or q.endswith("/" + p) for q in paths)
        and p.split("/")[0] + "/" not in made_at_run_time
        and p not in survey
    ]
    assert not missing, (
        f"{document} names paths that are not in the checkout: {missing}"
    )
