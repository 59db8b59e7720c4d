"""Doc gates: the generated API reference, DESIGN.md's section references,
and the repo paths the documents name must match the tree."""
import importlib.util
import itertools
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
# a repo path in a document is written from the root or from the package
BASES = (ROOT, ROOT / "src" / "repro")
PATH_EXTS = (".py", ".json", ".md", ".yml")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_doc_tool_{name}", ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_api_reference_is_current(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["gen_api_docs.py", "--check"])
    _tool("gen_api_docs").main()       # raises SystemExit(1) when stale


def test_design_section_references_resolve():
    assert _tool("check_doc_anchors").main() == 0


def _expand_braces(token):
    """``launch/{train,serve}.py`` -> both paths (one level is enough)."""
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        return [token]
    return list(itertools.chain.from_iterable(
        _expand_braces(token[:m.start()] + alt + token[m.end():])
        for alt in m.group(1).split(",")))


def named_paths(text):
    """Tokens of inline code and fenced blocks that name a repo file: a
    ``/`` inside, a known extension at the end; URLs and absolute paths
    are not repo paths."""
    spans = (re.findall(r"^```[^\n]*\n(.*?)^```", text, re.M | re.S)
             + re.findall(r"`([^`\n]+)`", text))
    for span in spans:
        for token in span.split():
            token = token.rstrip(",;:)")
            if ("/" not in token or "://" in token
                    or token.startswith(("/", "~"))):
                continue
            for path in _expand_braces(token):
                if path.endswith(PATH_EXTS):
                    yield path


def _exists(path):
    if "*" in path:
        return any(any(base.glob(path)) for base in BASES)
    return any((base / path).exists() for base in BASES)


@pytest.mark.parametrize("doc", ["README.md", "DESIGN.md", "docs/API.md"])
def test_named_repo_paths_exist(doc):
    missing = sorted({p for p in named_paths((ROOT / doc).read_text())
                      if not _exists(p)})
    assert not missing, f"{doc} names paths that do not exist: {missing}"
