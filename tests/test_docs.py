"""The documents name files that exist.

Every word of a code span or a fenced block that looks like a file of this
repository (``llmss_tpu/…``, ``tools/…``, ``tests/…``, ``docs/…``,
``benchmark/…``, ``scenarios/…``, or a bare ``*.py`` / ``*.json`` / ``*.md``)
must be there; a bare name may be a file of any directory. ``path:line``,
``path: name`` and ``path::test`` are checked for the path; ``path@commit``
names history and is skipped, as is a pattern (``*``, ``<name>``, ``…``).
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "PERF.md"] + sorted(
    f"docs/{p.name}" for p in (ROOT / "docs").glob("*.md")
)

_DIRS = ("llmss_tpu", "tools", "tests", "docs", "benchmark", "scenarios")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PATH = re.compile(
    r"^(?:(?:" + "|".join(_DIRS) + r")/[\w./-]*"
    r"|[\w.-]+\.(?:py|json|md))$"
)
# A checkpoint's files and what the documents' examples write.
_NOT_OURS = {"config.json", "workload.json", "req.trace.json"}


def _named_paths(text: str) -> set[str]:
    out = set()
    for span in _CODE.findall(text):
        for word in span.strip("`").split():
            if "@" in word or any(c in word for c in "*<>{}…$"):
                continue
            word = re.split(r"::|:\d|:$", word.strip("`'\"()[],;"))[0]
            word = word.rstrip(".,:")
            if _PATH.match(word) and word not in _NOT_OURS:
                out.add(word)
    return out


@pytest.fixture(scope="module")
def basenames():
    """Names of the files at the root and anywhere under the directories a
    document may name (not under what a run leaves beside them)."""
    names = {p.name for p in ROOT.iterdir() if p.is_file()}
    for d in _DIRS:
        names.update(p.name for p in (ROOT / d).rglob("*") if p.is_file())
    return names


@pytest.mark.parametrize("doc", DOCS)
def test_named_files_exist(doc, basenames):
    missing = sorted(
        p for p in _named_paths((ROOT / doc).read_text())
        if not ((ROOT / p).exists() if "/" in p else p in basenames)
    )
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
