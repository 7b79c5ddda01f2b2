"""Documents name files that exist.

One case per document. A backticked token that reads as a path to a
``.py``, ``.md`` or ``.json`` file must name a file of the tree: the whole
repo-relative path, or the tail of one (documents name modules from their
package: ``runtime/engine.py``, ``cost/hardware.py``, ``chip_smoke.py``).
A token holding ``<``, ``>`` or ``*`` is a template, not a path. Inside
a fenced block only paths with a directory are read: such blocks list
generated files and commands with the user's own arguments.

The test fails the moment a document names a file a PR has deleted.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "examples/README.md",
             ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
SOURCE_DIRS = ("deepspeed_tpu", "tools", "tests", "benchmarks", "docs",
               "examples", "csrc", ".claude")
FENCED = re.compile(r"```.*?```", re.S)
INLINE = re.compile(r"`([^`\n]+)`")
FILE_PATH = re.compile(r"^(?:[\w.-]+/)*[\w.-]+\.(?:py|md|json)$")


@pytest.fixture(scope="module")
def tails():
    """Every file at the root and under the source directories, and every
    tail of its path."""
    found = [n for n in os.listdir(REPO)
             if os.path.isfile(os.path.join(REPO, n))]
    for top in SOURCE_DIRS:
        for where, _dirs, names in os.walk(os.path.join(REPO, top)):
            found += [os.path.relpath(os.path.join(where, n), REPO)
                      for n in names]
    out = set()
    for path in found:
        parts = path.split(os.sep)
        out.update("/".join(parts[i:]) for i in range(len(parts)))
    return out


def named_paths(text):
    """Every file path the text names."""
    spans = [(s, True) for s in FENCED.findall(text)]
    spans += [(s, False) for s in INLINE.findall(FENCED.sub("", text))]
    for span, fenced in spans:
        for token in span.split():
            token = token.strip("`()[],;:'\"")
            if FILE_PATH.match(token) and ("/" in token or not fenced):
                yield token


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document, tails):
    with open(os.path.join(REPO, document)) as f:
        names = set(named_paths(f.read()))
    assert names, f"{document} names no file: the reader found nothing"
    missing = sorted(names - tails)
    assert not missing, f"{document} names files not in the tree: {missing}"
