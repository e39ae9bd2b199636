"""The documents cite files that exist.

A back-ticked ``path/to/file.py`` (or ``.sh``) in a document is a
promise that a reader can open it.  The root-level bench script outlived
its last user by nine PRs in eleven documents because nothing held the
prose to the tree; this does.  Stdlib-only, no jax.
"""

import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Where a cited path may be rooted: the documents abbreviate
# ``npairloss_tpu/serve/engine.py`` to ``serve/engine.py`` and
# ``benchmarks/harness/counts.py`` to ``harness/counts.py``.
ROOTS = ("", "npairloss_tpu", "benchmarks", "benchmarks/harness", "tests",
         "tests/benchmarks", "scripts", "docs")
DOCS = ["README.md", "examples/README.md", "PERF.md", "ROADMAP.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md"))
# These two recount earlier PRs by the names files had then, so a bare
# basename there may be history; a path with a directory is still held.
HISTORY = {"PERF.md", "ROADMAP.md"}

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_TICKED = re.compile(r"`([^`\n]+)`")
_SUFFIX = re.compile(r"(::[\w.]+|:[\d,:\- ]+)$")
_PLACEHOLDER = re.compile(r"[<>*{}…$]")


def _walk():
    """Without git (an unpacked archive): every file outside the
    dot-directories and the directories ``.gitignore`` names."""
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    for dirpath, dirnames, filenames in os.walk(REPO):
        rel = os.path.relpath(dirpath, REPO)
        dirnames[:] = [
            d for d in dirnames if not d.startswith(".")
            and not {d, os.path.normpath(os.path.join(rel, d))} & ignored]
        for name in filenames:
            yield os.path.normpath(os.path.join(rel, name))


@pytest.fixture(scope="module")
def files():
    """The files git would commit, as root-relative paths: tracked, or
    new and not ignored.  Ignored scratch never satisfies a citation."""
    listed = []
    try:
        listed = subprocess.run(
            ["git", "ls-files", "-z", "--cached", "--others",
             "--exclude-standard"],
            cwd=REPO, capture_output=True, text=True, check=True,
        ).stdout.split("\0")
    except (OSError, subprocess.CalledProcessError):
        pass
    listed = [f for f in listed
              if f and os.path.isfile(os.path.join(REPO, f))]
    return set(listed or _walk())


def _cited(text):
    """(line number, path) for each back-ticked word, outside fenced
    blocks, that names a ``.py`` / ``.sh`` file once its ``:line``,
    ``:a-b`` or ``::name`` is cut."""
    text = _FENCE.sub(lambda m: "\n" * m.group(0).count("\n"), text)
    for no, line in enumerate(text.splitlines(), 1):
        for ticked in _TICKED.findall(line):
            for word in ticked.split():
                word = _SUFFIX.sub("", word.strip("(),;"))
                if word.endswith((".py", ".sh")) \
                        and not _PLACEHOLDER.search(word):
                    yield no, word


@pytest.mark.parametrize("doc", DOCS)
def test_cited_files_exist(doc, files):
    basenames = {os.path.basename(f) for f in files}
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    stale = []
    for no, path in _cited(text):
        if "/" in path:
            if not any(os.path.normpath(os.path.join(root, path)) in files
                       for root in ROOTS):
                stale.append(f"{doc}:{no}: `{path}`")
        elif doc not in HISTORY and path not in basenames:
            stale.append(f"{doc}:{no}: `{path}`")
    assert not stale, "cited files that do not exist:\n" + "\n".join(stale)


def test_ignored_scratch_is_no_witness(files):
    """A citation of a file that only this workspace has must fail."""
    assert files and not any(
        f.startswith((".chipscratch/", ".bench_checkout/", "chiprun_out/"))
        or "__pycache__" in f for f in files)
