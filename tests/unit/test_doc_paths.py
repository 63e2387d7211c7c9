"""The documents name only files that exist.

Every repo-relative path written in backticks in ``README.md``,
``PARITY.md``, ``docs/*.md`` and ``PERF.md`` sections 1-5 must resolve to a
file of this checkout, so a deleted file cannot live on in the prose.  A
path is a backticked token that ends in ``.py`` / ``.md`` / ``.json`` /
``.yml``; it resolves when some file's path ends with it (the documents
write ``inference/serving.py`` for ``deepspeed_tpu/inference/serving.py``
and ``test_opt.py`` for ``tests/unit/test_opt.py``).  Out of scope: the
reference's own paths (``PARITY.md``'s first table column and tokens
introduced by the word "reference"), placeholders (``<family>``), and the
names of files the program writes at run time.
"""

import glob
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
DOCS = ["README.md", "PARITY.md", "PERF.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))
EXTENSIONS = (".py", ".md", ".json", ".yml")
#: not part of a checkout: build and run leftovers, the parent commit's copy
SKIP_DIRS = {".git", "_parent", "_chip", "chiprun_out", ".jax_cache",
             "__pycache__", ".pytest_cache", ".hypothesis"}
#: files the program WRITES (the autotuner's results, an incident bundle's
#: manifest, the example name of a dumped trace): never committed
RUNTIME_OUTPUTS = {"best_config.json", "exps.json", "report.md",
                   "manifest.json", "serving_trace.json",
                   # what benchmarks/scope_trace.py writes
                   "tables.json", "by_scope.json", "by_scope.txt"}


def _checkout_files():
    out = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        rel = os.path.relpath(base, ROOT)
        out.extend(os.path.normpath(os.path.join(rel, f)) for f in files)
    return out


def _paths(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    if doc == "PERF.md":
        text = text.split("\n## 6.")[0]      # Findings on: history
    for line in text.splitlines():
        if doc == "PARITY.md" and line.startswith("|"):
            # first table column: the reference's component and its path
            line = line.split("|", 2)[2] if line.count("|") > 2 else ""
        for m in re.finditer(r"(?<!reference )`([^`\n]+)`", line):
            for tok in m.group(1).split():
                tok = tok.strip("(),;\"'").split("::")[0]
                tok = re.sub(r":[\d,\-]+$", "", tok)      # file.py:12-30
                if tok.endswith(EXTENSIONS) and "<" not in tok \
                        and not tok.startswith(("http", "deepspeed/")):
                    yield tok


def _expand(tok):
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py``."""
    m = re.search(r"\{([^{}]*)\}", tok)
    if not m:
        return [tok]
    return [t for alt in m.group(1).split(",")
            for t in _expand(tok[:m.start()] + alt + tok[m.end():])]


@pytest.fixture(scope="module")
def checkout():
    files = _checkout_files()
    return files, {os.path.basename(f) for f in files}


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc, checkout):
    files, basenames = checkout
    missing = []
    for tok in sorted(set(_paths(doc))):
        for path in _expand(tok):
            if "/" not in path:
                ok = path in basenames or path in RUNTIME_OUTPUTS
            elif "*" in path:
                ok = bool(glob.glob(os.path.join(ROOT, path)))
            else:
                tail = os.sep + os.path.normpath(path)
                ok = any((os.sep + f).endswith(tail) for f in files)
            if not ok:
                missing.append(path)
    assert not missing, f"{doc} names files that do not exist: {missing}"
