"""scripts/gate.sh names only what exists.

One case a stage: every script, test path and Python module the stage
runs or imports is in the checkout, and the whole script parses
(``bash -n``).  A file deleted without its gate step fails here, not at
the next gate run."""

import pathlib
import re
import subprocess

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
GATE = REPO / "scripts" / "gate.sh"

_HEADER = re.compile(r'^\s*echo "== (gate|nightly) (\d+)/(\d+): .*==', re.M)
_PATH = re.compile(
    r"(?<![\w./-])((?:[\w-]+/)+|(?:[\w-]+/)*[\w-]+\.(?:py|sh))(?![\w.-])"
)
_IMPORT = re.compile(r'(?:^|[\s";])(?:from ([\w.]+) import|import ([\w.]+))', re.M)


def _stages() -> "dict[str, str]":
    """The script's stages by id (``gate-3``, ``nightly-1``): the text
    from a stage's header to the next header or the final line."""
    text = GATE.read_text()
    heads = list(_HEADER.finditer(text))
    end = text.index('echo "gate: ALL GREEN"')
    return {
        f"{m.group(1)}-{m.group(2)}": text[m.end():(
            heads[i + 1].start() if i + 1 < len(heads) else end
        )]
        for i, m in enumerate(heads)
    }


def _named(body: str) -> "list[str]":
    """What a stage names: paths (``scripts/x.py``, ``tests/``) on its
    command lines and the modules its inline Python imports, each as the
    file or directory that must exist."""
    code = "\n".join(
        line for line in body.splitlines() if not line.lstrip().startswith("#")
    )
    names = [p for p in _PATH.findall(code) if not p.startswith("/")]
    for frm, imp in _IMPORT.findall(code):
        mod = (frm or imp).replace(".", "/")
        names.append(mod if (REPO / mod).is_dir() else mod + ".py")
    return names


STAGES = _stages()


def test_the_stage_counts_agree():
    text = GATE.read_text()
    for kind in ("gate", "nightly"):
        heads = [
            (int(n), int(of)) for k, n, of in _HEADER.findall(text) if k == kind
        ]
        assert [n for n, _ in heads] == list(range(1, len(heads) + 1)), kind
        assert {of for _, of in heads} == {len(heads)}, kind


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_every_stage_names_only_files_that_exist(stage):
    named = _named(STAGES[stage])
    assert named, f"{stage} names nothing to run"
    missing = [n for n in named if not (REPO / n).exists()]
    assert not missing, f"{stage} names missing files: {missing}"
    subprocess.run(["bash", "-n", str(GATE)], check=True)
