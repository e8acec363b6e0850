"""The documents name files that exist.

In ``README.md``, ``PERF.md`` and ``docs/*.md`` every back-quoted path
that names a file (``dir/.../name.py|json|md``, with no placeholder in
it) exists, and every ``python[3] <script>`` of a fenced block names a
script that exists. ``ROADMAP.md`` and ``CHANGES.md`` are history and
are not checked. Text is parsed, nothing is run.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

#: prose names a file from the root or, for short, from one of these
BASES = ("", "tpu_syncbn", "chipbench", "tests", "docs", "benchmarks")

_QUOTED = re.compile(r"`([^`\n]+)`")
_FILE = re.compile(r"^[\w.\-]+(?:/[\w.\-]+)+\.(?:py|json|md)$")
_SUFFIX = re.compile(r"(?::\d+(?:-\d+)?(?:,\d+(?:-\d+)?)*|::[\w.]+)+$")
_FENCE = re.compile(r"^```.*?\n(.*?)^```", re.MULTILINE | re.DOTALL)
_SCRIPT = re.compile(r"\bpython3?\s+([\w.\-/]+\.py)\b")


def _exists(path: str) -> bool:
    return any(os.path.isfile(os.path.join(ROOT, base, path))
               for base in BASES)


def quoted_files(text: str) -> set[str]:
    """Back-quoted tokens that name a file under a directory, their
    ``:line`` and ``::symbol`` suffixes dropped."""
    found = set()
    for token in _QUOTED.findall(text):
        token = _SUFFIX.sub("", token.strip())
        if _FILE.match(token):
            found.add(token)
    return found


def fenced_scripts(text: str) -> set[str]:
    return {script for block in _FENCE.findall(text)
            for script in _SCRIPT.findall(block)}


def test_the_extractors_see_what_they_should():
    text = ("`ops/batch_norm.py:41-170`, `chipbench/run.py::main`, "
            "`workloads/<cell>.json`, `tests/contracts/*.json`, `a.py`, "
            "`[torch] nn/modules/batchnorm.py`\n"
            "```bash\npython3 chipbench/run.py --workload x\n"
            "python -m pytest tests/\n```\n")
    assert quoted_files(text) == {"ops/batch_norm.py", "chipbench/run.py"}
    assert fenced_scripts(text) == {"chipbench/run.py"}
    assert not _exists("benchmarks/artifacts/no_such_exhibit.json")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_files_exist(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = sorted(p for p in quoted_files(text) | fenced_scripts(text)
                     if not _exists(p))
    assert not missing, f"{document} names files that do not exist: {missing}"
