"""The documents an operator reads point at things that exist: every
repo-relative path in backticks is a file of the checkout, and every
test they cite by node id or ``-k`` expression is defined in the file
they name. Text search only; nothing is imported. ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` cite history and builders' scratch
files and are not held to this."""

import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
DOCS = ["README.md", "SERVING.md", "OBSERVABILITY.md", "RESILIENCE.md",
        ".claude/skills/verify/SKILL.md"]
# a file named without its directory ("`engine.py`" in a listing of
# `paddle_tpu/serving/`) is looked for under these
TREES = ["paddle_tpu", "benchmarks", "tests", "tools", "examples"]

_PATH = re.compile(r"^[\w./-]+\.(?:py|md|json)$")


def _git_ignored(paths):
    """Those of ``paths`` that git ignores (none outside a git checkout)."""
    try:
        out = subprocess.run(["git", "check-ignore", "--", *paths], cwd=REPO,
                             capture_output=True, text=True, timeout=60)
    except OSError:
        return set()
    return set(out.stdout.split())


def _find(path):
    """The file a document means by ``path``: at the root, under a tree
    (`serving/engine.py`), or, for a bare name, anywhere in one."""
    for base in [REPO, *(REPO / t for t in TREES)]:
        if (base / path).exists():
            return base / path
    if "/" not in path:
        for t in TREES:
            hit = next((REPO / t).rglob(path), None)
            if hit:
                return hit
    return None


def _defines(source, name, whole=True):
    """``source`` has a ``def`` or ``class`` called ``name`` (or, for a
    ``-k`` word, one whose name contains it)."""
    pat = re.escape(name) if whole else rf"\w*{re.escape(name)}\w*"
    return re.search(rf"\b(?:def|class)\s+{pat}\b", source) is not None


@pytest.mark.parametrize("doc", DOCS)
def test_paths_and_tests_a_document_cites_exist(doc):
    text = re.sub(r"```.*?```", " ", (REPO / doc).read_text(), flags=re.S)
    spans = re.findall(r"`([^`]+)`", " ".join(text.split()))
    problems = []

    # `path`, `path:line`, `path:line-line`, `path::Class::test`
    cited = {}
    for word in (w.rstrip(".,;:()") for span in spans for w in span.split()):
        path = re.sub(r":\d+(?:-\d+)?$", "", word.split("::")[0])
        if _PATH.match(path) and not path.startswith("/"):
            cited.setdefault(path, set()).add(word)
    ignored = _git_ignored(sorted(cited))
    for path, words in sorted(cited.items()):
        if path in ignored:
            continue
        found = _find(path)
        if found is None:
            problems.append(f"{path}: no such file")
            continue
        for word in sorted(words):
            for name in word.split("::")[1:]:
                if not _defines(found.read_text(), name.split("[")[0]):
                    problems.append(f"{word}: no {name} in {path}")

    # `python -m pytest tests/x.py -k "a or b" ...`
    for span in spans:
        m = re.search(r"pytest\s+(tests/\S+\.py)\b.*?-k\s+(\"[^\"]+\"|\S+)", span)
        if not m or not (REPO / m.group(1)).exists():
            continue
        source = (REPO / m.group(1)).read_text()
        for name in re.findall(r"\w+", m.group(2)):
            if name not in ("or", "and", "not") and \
                    not _defines(source, name, whole=False):
                problems.append(f"`{span}`: no test matches {name}")
    assert not problems, "\n".join(problems)
