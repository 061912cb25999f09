"""Docs integrity gate: the documentation must execute.

Three legs:

* **doctests** — the audited public compiler surface carries runnable
  examples in its docstrings; `pytest --doctest-modules` cannot import
  the `repro` namespace package, so the modules are run through
  `doctest.testmod` explicitly (and asserted non-empty, so silently
  dropping the examples fails loudly).
* **fenced blocks** — every ```` ```python ```` block in `README.md`
  and `docs/*.md` is extracted and executed.  Blocks within one file
  share a namespace, literate-style, so a guide can build on its own
  earlier snippets; illustrative non-runnable sketches use plain
  fences.
* **links** — every relative markdown link in those files must resolve
  to an existing file (web-relative links that escape the repo are
  skipped — they point at the forge, not the tree).
"""
import doctest
import importlib
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOC_FILES = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]

DOCTEST_MODULES = [
    "repro.compiler.program",
    "repro.compiler.lowering",
    "repro.compiler.schedule",
    "repro.distributed.sharding",
    "repro.filters.bank",
]


@pytest.mark.parametrize("name", DOCTEST_MODULES)
def test_module_doctests(name):
    mod = importlib.import_module(name)
    res = doctest.testmod(
        mod,
        optionflags=doctest.NORMALIZE_WHITESPACE | doctest.ELLIPSIS,
        verbose=False,
    )
    assert res.attempted > 0, f"{name} lost its doctest examples"
    assert res.failed == 0, f"{name}: {res.failed} doctest(s) failed"


_BLOCK_RE = re.compile(r"```python\n(.*?)```", re.DOTALL)


@pytest.mark.parametrize("path", DOC_FILES, ids=lambda p: p.name)
def test_fenced_python_blocks_execute(path):
    text = path.read_text()
    blocks = [
        (text[: m.start()].count("\n") + 2, m.group(1))
        for m in _BLOCK_RE.finditer(text)
    ]
    ns: dict = {}
    for line, code in blocks:
        try:
            exec(compile(code, f"{path.name}:{line}", "exec"), ns)
        except Exception as e:  # pragma: no cover - failure reporting
            raise AssertionError(
                f"{path.name}: fenced python block at line {line} "
                f"failed: {e!r}"
            ) from e


_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def test_no_dead_relative_links():
    dead = []
    for path in DOC_FILES:
        for target in _LINK_RE.findall(path.read_text()):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            resolved = (path.parent / rel).resolve()
            if not resolved.is_relative_to(ROOT):
                continue  # web-relative (e.g. ../../actions/...): not ours
            if not resolved.exists():
                dead.append(f"{path.name}: {target}")
    assert not dead, f"dead relative links: {dead}"
