"""A document that names a file of this repository names one that exists.

README.md and docs/*.md point operators at scripts, modules and tests by
path; a PR that deletes or moves a file must take its mentions with it
(ISSUE 50 removed the ladder harness and found 19 of them). Chronicles
(SCALING.md, CHANGES.md, ROADMAP.md) name what once was and are not read
here; a document that must name a removed file says so without its path."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: a repository path as the documents write them: under one of the source
#: trees, or one of the root's scripts
_PATH = re.compile(
    r"(?<![\w/.\-])((?:scripts|rtap_tpu|tests|benchmark)/[\w/]+\.(?:py|sh|c)"
    r"|bench\.py|chip_smoke\.py|__graft_entry__\.py)")

DOCS = ["README.md", *sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md")))]


@pytest.mark.parametrize("doc", DOCS)
def test_every_repository_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        named = sorted(set(_PATH.findall(f.read())))
    missing = [p for p in named if not os.path.exists(os.path.join(REPO, p))]
    assert not missing, f"{doc} names files that are not in the tree: {missing}"


def test_the_pattern_bites():
    assert _PATH.findall("run `python bench.py` or scripts/roofline.py, see "
                         "rtap_tpu/ops/tm_tpu.py:wide_rows") == [
        "bench.py", "scripts/roofline.py", "rtap_tpu/ops/tm_tpu.py"]
    assert not _PATH.findall("scripts/ingest_bench.json, obs/selfbench.py, "
                             "`hw_session.py`, once under scripts/")
