"""The package layering points one way: the model stack (core, kernels,
layers, models, configs) never imports the launchers or the serving
engine above it.  Choices a layer makes arrive through its signature,
not through a module it would have to reach up to."""
import ast
from pathlib import Path

import pytest

import repro

SRC = Path(repro.__file__).parent
UPPER = ("repro.launch", "repro.serving")


def _imported_modules(path: Path, package: str):
    """Every module an .py file imports, at any depth (function-level
    imports too), relative imports resolved against `package`."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package.split(".")
                base = base[:len(base) - node.level + 1]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            yield mod
            # `from repro import launch` names the module in the alias
            for alias in node.names:
                yield f"{mod}.{alias.name}"


def _is_upper(mod: str) -> bool:
    return any(mod == u or mod.startswith(u + ".") for u in UPPER)


@pytest.mark.parametrize(
    "pkg", ["core", "kernels", "layers", "models", "configs"]
)
def test_no_upward_imports(pkg):
    root = SRC / pkg
    files = sorted(root.rglob("*.py"))
    assert files, root
    bad = []
    for f in files:
        rel = f.relative_to(SRC)
        package = ".".join(["repro", *rel.parts[:-1]])
        for mod in _imported_modules(f, package):
            if _is_upper(mod):
                bad.append(f"{f.relative_to(SRC)}: {mod}")
    assert not bad, bad
