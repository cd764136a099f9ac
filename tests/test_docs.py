"""The documents that describe the system as it is cite only what exists.

One case a document: every file name it cites in code spans resolves to
a file of the repository, and every ``make <target>`` it names is a
target of the Makefile. The histories (CHANGES.md, PERF.md, ROADMAP.md)
name files that are gone on purpose and are not held to this; nor is a
span that is itself a pointer into history (``git show <commit>:<path>``).
"""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "PARITY.md", "Makefile",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))

_FILE = re.compile(r"[\w*][\w.*/\-]*\.(?:py|sh|cc|json|md)\b(?![\w*])"
                   r"|\.\w[\w.\-]*\.(?:json|md)\b")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_MAKE = re.compile(r"\bmake (?!-)([a-z][\w\-]*)")


def _ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        return [ln.strip().rstrip("/") for ln in f
                if ln.strip() and not ln.startswith("#")]


def _repo_files(ignored):
    """Repo-relative paths of the files git would track: the tree minus
    .git and whatever .gitignore names."""
    out = []
    for root, dirs, files in os.walk(REPO):
        rel = os.path.relpath(root, REPO)
        dirs[:] = [d for d in dirs if d != ".git" and not any(
            fnmatch.fnmatch(d, p)
            or os.path.normpath(os.path.join(rel, d)) == p
            for p in ignored)]
        out += [os.path.normpath(os.path.join(rel, f)) for f in files]
    return out


@pytest.fixture(scope="module")
def tree():
    ignored = _ignored()
    files = _repo_files(ignored)
    return ignored, set(files), {os.path.basename(p) for p in files}


@pytest.fixture(scope="module")
def make_targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w\-]*):", f.read(), re.M))


def _resolves(name, ignored, files, basenames):
    base = os.path.basename(name)
    if any(fnmatch.fnmatch(base, p) for p in ignored):
        return True                     # a run-time artifact
    if "*" in name:
        return any(fnmatch.fnmatch(p, name) or fnmatch.fnmatch(
            p, os.path.join("matrel_tpu", name)) for p in files)
    return (name in files or os.path.join("matrel_tpu", name) in files
            or ("/" not in name and base in basenames))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_cites_what_exists(doc, tree, make_targets):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    spans = [text] if doc == "Makefile" else [
        s for s in _CODE.findall(text) if not s.startswith("`git show ")]
    cited = sorted({m.group(0) for s in spans for m in _FILE.finditer(s)})
    stray = [n for n in cited if not _resolves(n, *tree)]
    assert not stray, f"{doc} cites files that do not exist: {stray}"
    unknown = sorted({t for s in spans for t in _MAKE.findall(s)}
                     - make_targets)
    assert not unknown, f"{doc} names make targets that do not exist: " \
        f"{unknown}"
