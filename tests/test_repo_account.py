"""The account a new owner reads first points at files that exist.

`README.md` and `.claude/skills/verify/SKILL.md` say what to run and what
to read. A path they name in backticks (or run with `python` in a code
block) must be in the checkout: PR 49 deleted three measuring stacks that
the README had gone on recommending for 25 PRs after the benchmark
replaced them. `ROADMAP.md` and `CHANGES.md` are history and are not held
to this."""

import fnmatch
import itertools
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", os.path.join(".claude", "skills", "verify", "SKILL.md"))

# a file of the repository as a document names it: `dir/file.ext`, a bare
# `file.py`, or a record at the root (`PERF.md`, `BENCHMARK.json`)
_EXTS = r"(?:py|json|jsonl|md|cpp|gz)"
_PATHLIKE = re.compile(
    rf"^(?:[\w.{{}},*-]+/)+[\w.{{}},*-]+\.{_EXTS}$"      # dir/file.ext
    rf"|^[\w{{}},*-]+\.py$"                               # file.py
    rf"|^[A-Z][A-Z0-9_]+\w*\.(?:json|jsonl|md)$")         # ROOT_RECORD.md
_RUN = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")    # not `python -m`


def _repo_files():
    try:
        listed = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"], cwd=REPO, capture_output=True,
            text=True, check=True).stdout.splitlines()
    except (OSError, subprocess.CalledProcessError):
        listed = []
    if listed:
        return [f for f in listed if os.path.exists(os.path.join(REPO, f))]
    found = []                  # not a git checkout: what is on disk
    for dirpath, dirnames, files in os.walk(REPO):
        dirnames[:] = [d for d in dirnames
                       if d not in (".git", "__pycache__")]
        found += [os.path.relpath(os.path.join(dirpath, f), REPO)
                  for f in files]
    return found


def _expand(token):
    """`models/{gpt,llama}.py` -> both paths."""
    parts = re.split(r"\{([^{}]*)\}", token)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def _named_paths(text):
    named = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for token in span.split():
            token = token.strip("()[],;:\"'").rstrip(".")
            token = token.split("::")[0]        # file.py::test_name
            if "<" in token or token.startswith(("/", "~", "-")):
                continue
            if _PATHLIKE.match(token):
                named.update(_expand(token))
    named.update(_RUN.findall(text))
    return named


def _exists(path, files):
    """Named from the root, or by the tail of its path as the README does
    (`comm_overlap/zero3.py`, `serving.py`); `*` matches as in a shell."""
    path = path[2:] if path.startswith("./") else path
    for f in files:
        if f == path or f.endswith("/" + path):
            return True
        if "*" in path and (fnmatch.fnmatch(f, path)
                            or fnmatch.fnmatch(f, "*/" + path)):
            return True
    return False


@pytest.mark.parametrize("doc", DOCS)
def test_the_account_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        named = _named_paths(f.read())
    assert len(named) > 5, f"{doc}: the scan found too few paths: {named}"
    files = _repo_files()
    dangling = sorted(p for p in named if not _exists(p, files))
    assert not dangling, f"{doc} names files that are not here: {dangling}"
