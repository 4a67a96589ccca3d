"""Package import direction: the planning core and the runtime hooks sit
below the DVFS tier and never import upward (configs/hw <- core <-
runtime <- dvfs <- {parallel, fleet, train}).  Lazy imports inside
functions count too."""
import ast
import pathlib

import pytest

import repro

SRC = pathlib.Path(next(iter(repro.__path__)))


def _imported_subpackages(source: str, pkg: list) -> set:
    """The ``repro.<subpackage>`` names one module of package ``pkg``
    (e.g. ``["repro", "core"]``) imports, anywhere in its source."""
    out = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            targets = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg[:len(pkg) - node.level + 1]
                if node.module:
                    targets = [".".join(base + [node.module])]
                else:
                    targets = [".".join(base + [a.name]) for a in node.names]
            else:
                targets = [node.module]
        else:
            continue
        for t in targets:
            parts = t.split(".")
            if parts[0] == "repro" and len(parts) > 1:
                out.add(parts[1])
    return out


@pytest.mark.parametrize("package,forbidden", [
    ("core", {"dvfs", "runtime", "parallel", "fleet", "serve", "train"}),
    ("runtime", {"dvfs"}),
])
def test_lower_tier_never_imports_upward(package, forbidden):
    bad = {}
    for path in sorted((SRC / package).rglob("*.py")):
        pkg = ["repro", *path.relative_to(SRC).parent.parts]
        hit = _imported_subpackages(path.read_text(), pkg) & forbidden
        if hit:
            bad[str(path.relative_to(SRC))] = sorted(hit)
    assert not bad, f"upward imports: {bad}"


def test_import_resolver_sees_relative_and_lazy_imports():
    """The resolver itself, so the check above cannot pass vacuously:
    absolute, relative, bare-package and function-local imports all
    resolve to their subpackage."""
    source = ("import numpy\n"
              "from ..configs.base import ModelConfig\n"
              "import repro.serve.engine\n"
              "from .freq import AUTO\n"
              "def f():\n"
              "    from ..hw.hlo_parse import analyze_hlo\n"
              "    from .. import dvfs\n")
    assert _imported_subpackages(source, ["repro", "core"]) \
        == {"configs", "serve", "core", "hw", "dvfs"}
