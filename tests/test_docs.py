"""The paths the two hand-written guides name exist.

``README.md`` and ``.claude/skills/verify/SKILL.md`` tell a reader what to
run and where to look; a file they name that is gone sends the reader
nowhere.  Checked: every path under ``tools/``, ``tests/``, ``tests_tpu/``,
``benchmark/``, ``src/`` or the package (written from the repo root or from
the package, as in ``parallel/pipeline.py``), every bare ``*.py`` (by its
base name, anywhere in this repo), and every bare ``*.json`` that starts
with a capital (the repo root's records).  Not checked: ``path:line`` citations, which
point into the reference repository, and lower-case ``*.json`` names, which
are files a run writes.
"""

import itertools
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
PACKAGE = ROOT / "distributed_training_comparison_tpu"
DOCS = ["README.md", ".claude/skills/verify/SKILL.md"]
ROOTED = ("tools/", "tests/", "tests_tpu/", "benchmark/", "src/", PACKAGE.name + "/")
TOKEN = re.compile(r"[\w./{},*-]+\.(?:py|json|sh)\b(?!:\d)")
# names that are not this repo's files: what the supervisor writes into a
# run's directory, a usage line's placeholder, and the reference's model
# file in the component map's "reference" column
NOT_THIS_REPOS = {"GOODPUT.json", "OTHER.json", "net.py"}


def _expand(token: str) -> list[str]:
    """``src/{a,b}/run_*.sh`` -> one glob pattern per alternative."""
    parts = re.split(r"\{([^{}]*)\}", token)
    choices = [
        p.split(",") if i % 2 else [p] for i, p in enumerate(parts)
    ]
    return ["".join(c) for c in itertools.product(*choices)]


def _missing(doc: str) -> list[str]:
    text = (ROOT / doc).read_text()
    subpackages = tuple(
        d.name + "/" for d in PACKAGE.iterdir()
        if d.is_dir() and not d.name.startswith("_")
    )
    basenames = {
        p.name for top in ("tools", "tests", "tests_tpu", "benchmark", "src",
                           PACKAGE.name)
        for p in (ROOT / top).rglob("*.py")
    } | {p.name for p in ROOT.glob("*.py")}
    missing = []
    for token in sorted(set(TOKEN.findall(text))):
        token = token.lstrip("./")
        if token.startswith(ROOTED):
            base = ROOT
        elif token.startswith(subpackages):
            base = PACKAGE
        elif "/" in token or "*" in token:
            continue  # a run's own layout (version-*/trace.json, fleet/...)
        elif token.endswith(".py"):
            if token not in basenames | NOT_THIS_REPOS:
                missing.append(token)
            continue
        elif token.endswith(".json") and token[0].isupper():
            if token not in NOT_THIS_REPOS and not (ROOT / token).is_file():
                missing.append(token)
            continue
        else:
            continue
        if not all(any(base.glob(p)) for p in _expand(token)):
            missing.append(token)
    return missing


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_guide_names_exists(doc):
    assert _missing(doc) == []
