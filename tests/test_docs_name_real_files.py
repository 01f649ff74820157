"""The two documents that tell a user what to run name files that exist.

``README.md`` and ``.claude/skills/verify/SKILL.md`` are read before anything
is built or run; a path in them that is gone sends the reader after a tool
the tree no longer has.  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories and may name what was deleted: not covered."""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", ".claude/skills/verify/SKILL.md"]

# a word that is a path into this repo, wherever it stands in the text
_REPO_PATH = re.compile(
    r"(?<![\w/.<>-])((?:tools|paddle_tpu|benchmark|tests|examples)/[\w./-]*)")
# back-ticked words with no directory: `name.py` is a file somewhere in the
# tree; an upper-case `NAME.md` / `NAME.json(l)` is one of the records at the
# root (lower-case `trace.json`, `manifest.json` are written at run time)
_BARE_PY = re.compile(r"^[\w.-]+\.py$")
_BARE_RECORD = re.compile(r"^[A-Z][A-Z0-9_]*(?:_r\d+)?\.(?:md|jsonl?)$")
_NOT_LITERAL = set("*<>{}$…")


def _ignored_dirs():
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        return {ln.strip().rstrip("/") for ln in fh
                if ln.strip().endswith("/")} | {".git"}


def _basenames():
    skip = _ignored_dirs()
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in skip]
        names.update(files)
    return names


def _clean(word):
    """`tools/x.py:12,40` and `tools/x.py.` name `tools/x.py`."""
    word = re.sub(r":[\d,–-]+.*$", "", word)
    return word.rstrip(".,;:)")


def _named_paths(text):
    for m in _REPO_PATH.finditer(text):
        rest = text[m.end():m.end() + 1]
        word = _clean(m.group(1))
        if (rest and rest in _NOT_LITERAL) or _NOT_LITERAL & set(word):
            continue  # a pattern (`tools/*.py`, `benchmark/<x>.py`)
        yield word


def _bare_words(text, pattern):
    for tick in re.findall(r"`([^`\n]+)`", text):
        for word in map(_clean, tick.split()):
            if pattern.match(word):
                yield word


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as fh:
        text = fh.read()
    missing = sorted({p for p in _named_paths(text)
                      if not os.path.exists(os.path.join(ROOT, p))})
    missing += sorted({w for w in _bare_words(text, _BARE_RECORD)
                       if not os.path.exists(os.path.join(ROOT, w))})
    names = _basenames()
    unknown = sorted({w for w in _bare_words(text, _BARE_PY)
                      if w not in names})
    assert (missing, unknown) == ([], []), (
        f"{doc} names paths that do not exist: {missing}; "
        f"and files no directory of the tree holds: {unknown}")
