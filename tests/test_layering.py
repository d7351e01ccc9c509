"""Which package may import which: the layers, held by parsing imports.

``utils/`` is the bottom layer and ``obs/`` a leaf that knows no
trainer, engine or model: a lower layer that imports a higher one makes
every feature land twice (``obs/phases.py`` once rebuilt each train step
out of the trainers' privates). The source is parsed with ``ast``, so an
import inside a function counts like one at the top of a module, and
nothing is imported to find out.

Not held here, named as debts in ``ROADMAP.md``: ``models/moe.py`` ->
``obs.metrics`` and ``parallel/`` -> ``train/``.
"""

from __future__ import annotations

import ast
from pathlib import Path

import pytest

PACKAGE = "cs744_pytorch_distributed_tutorial_tpu"
ROOT = Path(__file__).resolve().parent.parent / PACKAGE

RULES = [
    (layer, banned)
    for layer, forbidden in (
        ("utils", ("obs", "train", "serve", "models")),
        ("obs", ("train", "serve", "models", "ops", "data")),
    )
    for banned in forbidden
]


def _subpackages_imported(path: Path) -> set[tuple[str, int]]:
    """``(subpackage, line)`` for every import in ``path`` that reaches a
    first-level subpackage of the package, absolute or relative."""
    here = (PACKAGE, *path.relative_to(ROOT).parent.parts)
    found: set[tuple[str, int]] = set()
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if isinstance(node, ast.Import):
            targets = [alias.name.split(".") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = list(here[: len(here) - node.level + 1]) if node.level else []
            base += node.module.split(".") if node.module else []
            # "from <package> import obs" names the subpackage in the alias
            targets = [base + [alias.name] for alias in node.names]
        else:
            continue
        for parts in targets:
            if parts[0] == PACKAGE and len(parts) > 1:
                found.add((parts[1], node.lineno))
    return found


@pytest.mark.parametrize("layer,banned", RULES)
def test_layer_does_not_import(layer, banned):
    modules = sorted((ROOT / layer).rglob("*.py"))
    assert modules, f"no modules under {ROOT / layer}"
    offenders = [
        f"{PACKAGE}/{path.relative_to(ROOT)}:{line}"
        for path in modules
        for sub, line in sorted(_subpackages_imported(path))
        if sub == banned
    ]
    assert not offenders, f"{layer}/ imports {banned}/ at {offenders}"
