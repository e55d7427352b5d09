"""A document that names a file of this repo names one that is there.

Every backticked `*.py` / `*.md` / `*.json` name or directory (`name/`) in
README.md and doc/*.md must be a file or directory of the checkout: given
from the root (`perfbench/run.py`), from inside it (`observability/compare.py`,
`compare.py`), or beside the document. A deleted instrument that the prose
still sends a reader to is the failure this catches. Jax-free.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    f"doc/{f}" for f in os.listdir(os.path.join(REPO, "doc")) if f.endswith(".md"))

# names the documents give to what a RUN writes, and to the reference's tree
NOT_IN_THE_CHECKOUT = {
    "oom_report.json", "crash_report.json", "hang_report.json",
    "serve_hang_report.json", "MANIFEST.json", "host-N.json", "fleet_status/",
    "pserver/", "predefined_net.py",
}
# what building and running leave in the checkout (.gitignore)
SKIP_DIRS = {"build", "output", "chiprun_out", "perfbench_out", "dist"}

_TOKEN = re.compile(r"`([^`\n]+)`")
_NAME = re.compile(r"^[\w.][\w./-]*$")


@functools.lru_cache(maxsize=None)
def _checkout():
    """Every file and directory of the checkout, as '/'-joined paths from
    the root with a leading '/', directories with a trailing '/'."""
    found = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "__pycache__" and d not in SKIP_DIRS]
        rel = os.path.relpath(root, REPO).replace(os.sep, "/")
        base = "/" if rel == "." else f"/{rel}/"
        found.update(base + f for f in files)
        found.update(base + d + "/" for d in dirs)
    return found


def _named(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    names = set()
    for token in _TOKEN.findall(text):
        for word in token.split():
            # `tests/test_x.py::test_y`, `trainer.py:143-150`, `bench.py,`
            word = re.sub(r":\d+(-\d+)?$", "", word.split("::")[0].rstrip(".,:;)"))
            if _NAME.match(word) and word.endswith((".py", ".md", ".json", "/")):
                names.add(word)
    return sorted(names - NOT_IN_THE_CHECKOUT)


@pytest.mark.parametrize("doc", DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    checkout = _checkout()
    beside = os.path.dirname(doc)
    missing = [
        name for name in _named(doc)
        if not any(path.endswith("/" + name) for path in checkout)
        and not os.path.exists(os.path.join(REPO, beside, name))
    ]
    assert not missing, f"{doc} names what is not in the checkout: {missing}"
