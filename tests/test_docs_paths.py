"""The documents that describe the tree to a user name files that exist.

One case a document. A path under one of the tree's directories must be
there; a bare ``name.py`` must be a Python file of the tree, at the root
or, as shorthand (``check.py --all``), further down. A file of the
reference implementation is written with its directory
(``perceiver/model.py``). The records (``PERF.md``, ``ROADMAP.md``,
``CHANGES.md``) may name the past and are not read. Where a case fails,
the document is wrong: correct it, not this test.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PARITY.md", "scripts/configs/README.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(ROOT, "docs"))
    if name.endswith(".md"))


def python_files():
    """Names of the tree's Python files; no hidden directory and no
    output of a run is part of the tree."""
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "logs", "__pycache__")]
        names.update(f for f in files if f.endswith(".py"))
    return names


_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PATH = re.compile(
    r"^(?:(?:perceiver_tpu|scripts|benchmarks|tests|docs)/[\w./*-]*"
    r"|\w+\.py)")


def named_paths(text):
    """Every word in backticks (a span or a fenced block) that is a path
    under one of the tree's directories or a ``*.py`` at the root,
    without what follows the file's name (``:123``, ``::test``)."""
    found = set()
    for code in _CODE.findall(text):
        for word in code.strip("`").split():
            match = _PATH.match(word.lstrip("(\"'"))
            # a placeholder (<name>, {a,b}) stands for no one file
            if match and not re.match(r"[<{]", word[match.end():]):
                found.add(match.group().rstrip("."))
    return found


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        paths = named_paths(f.read())
    assert paths, "the reader found no path"
    bare = python_files()
    missing = sorted(
        p for p in paths
        if (p not in bare if "/" not in p
            else not glob.glob(os.path.join(ROOT, p))))
    assert not missing, f"{document} names what is not in the tree"


def test_the_reader_finds_what_it_should():
    text = ("run `python scripts/check.py --all`, see "
            "`tests/test_decode.py::test_x` and `ops/attention.py:12`;\n"
            "```bash\npython chip_smoke.py --chips 4\n```\n"
            "`benchmarks/layer_metrics/<name>.py`, `docs/`, `scripts/*.py`, "
            "`perceiver_tpu.cache`, `perceiver_tpu/cache/exec_cache.py:460`.")
    assert named_paths(text) == {
        "scripts/check.py", "tests/test_decode.py", "chip_smoke.py",
        "docs/", "scripts/*.py", "perceiver_tpu/cache/exec_cache.py"}
