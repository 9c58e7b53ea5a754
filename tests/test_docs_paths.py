"""Every file a document names exists.

A deletion PR that leaves `python old_script.py` in a README is a dangling
instruction; this holds the documents to the tree.  A *name* is what
stands in backticks or in a Markdown link and looks like a file of this
repo: it ends in ``.py`` / ``.md`` / ``.json`` / ``.jsonl`` / ``.sh`` or
starts with one of the repo's top directories.  It exists when some file
of the checkout is that path or ends with it (``generation/engine.py``
for ``deeplearning4j_tpu/generation/engine.py``)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("scripts/", "deeplearning4j_tpu/", "benchmark/", "tests/",
            "docs/")
DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md",
              "scripts/runtests.sh"]
             + sorted("docs/" + f for f in os.listdir(
                 os.path.join(REPO, "docs")) if f.endswith(".md")))

# StepProfiler writes these into each capture directory at run time
RUN_TIME = {"capture.json", "host_spans.trace.json"}

_TOKEN = re.compile(r"[A-Za-z0-9_.\-/]+")
_SPAN = re.compile(r"`([^`\n]+)`")
_LINK = re.compile(r"\]\(([^)\s]+)\)")


def _checkout_files():
    """Every file of the checkout as ``/<repo-relative path>``."""
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d == ".claude" or not (d.startswith(".")
                                             or d in ("__pycache__",
                                                      "chiprun_out",
                                                      "profiles"))]
        rel = os.path.relpath(root, REPO)
        out.extend("/" + os.path.normpath(os.path.join(rel, f))
                   .replace(os.sep, "/") for f in files)
    return out


def _looks_like_repo_file(tok: str) -> bool:
    if tok.startswith(("/", "http", "~")) or ".." in tok:
        return False
    if tok.startswith(TOP_DIRS) and not tok.endswith("/"):
        return "." in os.path.basename(tok)
    return bool(re.search(r"\.(py|md|jsonl?|sh)$", tok))


def named_files(text: str, is_markdown: bool):
    names = set()
    if is_markdown:
        for span in _SPAN.findall(text):
            # a word with a placeholder or a glob in it names no one file
            span = re.sub(r"\S*[<>*{}$]\S*", " ", span)
            names.update(_TOKEN.findall(span))
        for target in _LINK.findall(text):
            names.add(target.split("#")[0])
    else:
        names.update(_TOKEN.findall(text))
    out = set()
    for tok in names:
        tok = re.sub(r"(:\d+([-,]\d+)*)+$", "", tok.strip(".,:;"))
        tok = tok.split("::")[0]
        if tok.startswith("./"):
            tok = tok[2:]
        if (_looks_like_repo_file(tok)
                and os.path.basename(tok) not in RUN_TIME):
            out.add(tok)
    return sorted(out)


@pytest.fixture(scope="module")
def checkout():
    return _checkout_files()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_file_exists(document, checkout):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    names = named_files(text, document.endswith(".md"))
    assert names, f"{document} names no file: the extraction is broken"
    missing = [n for n in names
               if not any(f.endswith("/" + n) for f in checkout)]
    assert not missing, f"{document} names files that do not exist: {missing}"
