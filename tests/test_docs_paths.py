"""The repo's documents name only files that exist: README.md, docs/*.md,
the READMEs beside the benchmark and the examples, the verify skill.

Every back-quoted token that is a path of this repo (it starts with one
of the repo's top-level directories, or it is a bare ``*.py`` name) must
be in the tree; a bare name may sit anywhere. What a document says about
the reference tree it writes as ``/root/reference/...``. The records
(PERF.md, ROADMAP.md, CHANGES.md) are not cases: they name what was
removed.
"""
import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
TOP = ("benchmark", "docs", "example", "include", "mxnet_tpu", "src",
       "tests", "tools", ".claude")
# what building, testing and running leave behind (.gitignore)
_SKIP = {".git", "_parent", "_chip", "_archive_proof", "chiprun_out",
         ".jax_cache", ".bench_scratch", "__pycache__", ".pytest_cache",
         ".hypothesis"}
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for pat in ("docs/*.md", "benchmark/README.md", "example/*/README.md",
                ".claude/skills/*/SKILL.md")
    for p in glob.glob(os.path.join(ROOT, pat)))


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _dir, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP]
        names.update(files)
    return names


def _tokens(text):
    text = re.sub(r"^```.*?^```", "", text, flags=re.S | re.M)
    for tok in re.findall(r"`([^`]+)`", text):
        if not tok.strip():
            continue
        tok = tok.split()[0].split("::")[0].rstrip(".,;:)")
        tok = re.sub(r":\d+(-\d+)?$", "", tok)
        yield tok


def _missing(doc):
    names = _basenames()
    out = []
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        for tok in _tokens(f.read()):
            pattern = re.sub(r"<[^>]*>|\{[^}]*\}", "*", tok)
            if "/" in tok and tok.split("/")[0] in TOP:
                found = glob.glob(os.path.join(ROOT, pattern.rstrip("/")))
            elif "/" not in tok and tok.endswith(".py"):
                found = fnmatch.filter(names, pattern)
            else:
                continue
            if not found:
                out.append(tok)
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc):
    assert _missing(doc) == []
