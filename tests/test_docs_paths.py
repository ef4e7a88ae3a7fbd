"""Every path a document quotes exists.

The documents that describe today's tree (README, the Makefile's comments,
``docs/``, the benchmark sweeps' README and the verify notes) name files in
backticks; a file that has since been moved or deleted leaves the sentence
pointing at nothing.  One case a document, so a dangling reference names
the document it is in.  History is exempt: CHANGES.md, ROADMAP.md, PERF.md,
SURVEY.md, BASELINE.md, PAPER*.md and ADVICE.md quote the tree as it was.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a backticked token is a path of this repo when it starts with one of these
ROOTS = ("kungfu_tpu/", "tests/", "scripts/", "examples/", "benchmarks/",
         "kfbench/", "docs/")

DOCUMENTS = sorted(
    ["README.md", "Makefile", "benchmarks/README.md",
     ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, REPO)
       for p in glob.glob(os.path.join(REPO, "docs", "*.md"))])

_TOKEN = re.compile(r"`([^`\n]+)`")
#: globs, ``{a,b}`` sets, ``<placeholders>`` and ``...``: not one path;
#: ``*.so`` is built on first use and git-ignored, so a checkout lacks it
_NOT_ONE_PATH = re.compile(r"[*{}<>$]|\.\.\.|\.so$")


def quoted_paths(text):
    """The repo paths ``text`` quotes in backticks, suffixes stripped."""
    out = []
    for token in _TOKEN.findall(text):
        # a command in backticks: the path is one of its words
        for word in token.split():
            if not word.startswith(ROOTS) or _NOT_ONE_PATH.search(word):
                continue
            word = word.split("::")[0]
            word = re.sub(r":\d+([-–,]\d+)*$", "", word)
            out.append(word.rstrip(".,;:)"))
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_quoted_paths_exist(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = sorted({p for p in quoted_paths(text)
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{document} quotes paths that do not exist: {missing}"


def test_the_rule_reads_suffixes_and_skips_what_is_not_one_path():
    text = ("`tests/test_zero.py::TestX::test_y` `kungfu_tpu/peer.py:12` "
            "`python examples/mnist_slp.py --n 1` `docs/*.md` "
            "`kungfu_tpu/monitor/{detect,kfhist}.py` `kfbench/metrics/<name>.py` "
            "`adapt.py` `kungfu_tpu/ops/schedules.py:165–175` "
            "`kungfu_tpu/native/libkfnative.so`.")
    assert quoted_paths(text) == [
        "tests/test_zero.py", "kungfu_tpu/peer.py", "examples/mnist_slp.py",
        "kungfu_tpu/ops/schedules.py"]
