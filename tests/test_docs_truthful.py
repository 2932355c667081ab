"""The documents name files that exist: ``test_api_surface.py``'s rule
("stays truthful") for ``README.md`` and ``docs/``.

A citation is a back-ticked path that ends in a source, document or
record suffix and starts at the repository's root or at
``dlrover_tpu/``; a ``::test`` or ``:line`` suffix is cut off, and a
``*`` has to match something. A path that starts nowhere in the tree
(a user's own script, a file under ``/tmp``) is not a citation.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = (REPO, os.path.join(REPO, "dlrover_tpu"))
DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md"))

_CITED = re.compile(
    r"`((?!/)[\w./*-]+\.(?:py|md|jsonl|json|toml|yaml))(?:::?[^`\s]*)?`")


def cited_paths(text):
    """Every citation in ``text``, as written."""
    return [
        path for path in _CITED.findall(text)
        if "*" in path.split("/")[0] or any(
            os.path.lexists(os.path.join(root, path.split("/")[0]))
            for root in ROOTS)]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as fh:
        cited = cited_paths(fh.read())
    assert cited, f"{document} cites no file: the scan has gone blind"
    missing = sorted({
        path for path in cited
        if not any(glob.glob(os.path.join(root, path)) for root in ROOTS)})
    assert not missing, f"{document} names files that are gone: {missing}"
