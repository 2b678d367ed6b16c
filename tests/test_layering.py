"""The package's import layering: each package imports nothing above it.

An AST scan of ``photon_ml_tpu/`` (imports inside functions included;
``__main__`` modules are entry points and are left out).  ``LAYERS`` lists
the packages bottom to top; the three leaf modules that every layer uses sit
at the bottom in their own right, so an import of one through its package
(``from photon_ml_tpu.analysis import sanitizers``) is an edge to the leaf.
An import from a layer to one above it is refused unless ``ALLOWED`` names
it, with the ROADMAP debt that removes it."""

import ast
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parents[1] / "photon_ml_tpu"

LAYERS = (
    # leaf modules (ROADMAP D14 moves them under utils/)
    "chaos.core", "chaos.breaker", "analysis.sanitizers",
    "native", "telemetry", "evaluation", "utils", "ops", "data", "models",
    "optim", "parallel", "solvers", "game", "io", "diagnostics",
    "hyperparameter", "drivers", "tuning", "serving", "freshness",
    "cluster", "chaos", "analysis",
)
RANK = {layer: i for i, layer in enumerate(LAYERS)}

#: (importing module, imported module) → the ROADMAP debt that removes it.
ALLOWED = {
    ("chaos.core", "telemetry"): "D14a",
    ("analysis.sanitizers", "telemetry"): "D14a",
    ("telemetry.lint", "analysis.engine"): "D14b",
    ("telemetry.lint", "analysis.rules_registry"): "D14b",
    ("utils.device_report", "ops.sparse_pallas"): "D14c",
    ("data.game_reader", "io.avro"): "D14d",
    ("game.estimator", "serving.kernels"): "D14e",
    ("serving.swap", "freshness.delta"): "D14f",
}


def _module_name(path: pathlib.Path) -> str:
    parts = list(path.relative_to(PKG).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def _exists(module: str) -> bool:
    base = PKG.joinpath(*module.split("."))
    return base.with_suffix(".py").exists() or (base / "__init__.py").exists()


def _imports(path: pathlib.Path):
    """Every photon_ml_tpu module ``path`` imports, without the prefix: for
    ``from a import b`` the submodule ``a.b`` where there is one."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("photon_ml_tpu."):
                    yield alias.name.split(".", 1)[1]
        elif isinstance(node, ast.ImportFrom) and node.module and (
                node.module == "photon_ml_tpu"
                or node.module.startswith("photon_ml_tpu.")):
            base = node.module.split(".", 1)[1] if "." in node.module else ""
            for alias in node.names:
                sub = f"{base}.{alias.name}" if base else alias.name
                yield sub if _exists(sub) else base


def _layer(module: str):
    """The layer a module belongs to: a leaf's own, else its package's."""
    while module:
        if module in RANK:
            return module
        module = module.rpartition(".")[0]
    return None


def _edges():
    edges = set()
    for path in sorted(PKG.rglob("*.py")):
        src = _module_name(path)
        if path.name == "__main__.py" or not src:
            # entry points, and the package's own face, sit above it all
            continue
        for dst in _imports(path):
            if dst:
                edges.add((src, dst))
    return edges


EDGES = _edges()


def _upward(layer):
    return {
        (src, dst) for src, dst in EDGES
        if _layer(src) == layer and _layer(dst) is not None
        and RANK[_layer(dst)] > RANK[layer]
    }


@pytest.mark.parametrize("layer", LAYERS)
def test_imports_nothing_above_it(layer):
    allowed = {e for e in ALLOWED if _layer(e[0]) == layer}
    assert _upward(layer) - allowed == set()


def test_every_package_has_a_layer():
    packages = {p.parent.name for p in PKG.glob("*/__init__.py")}
    assert packages <= set(LAYERS)
    assert {_layer(src) for src, _ in EDGES} <= set(LAYERS)


def test_every_allowance_is_still_an_upward_edge():
    assert set(ALLOWED) <= set().union(*map(_upward, LAYERS))


def test_the_solver_layers_do_not_import_solvers():
    assert not {(s, d) for s, d in EDGES
                if _layer(s) in ("optim", "parallel")
                and _layer(d) == "solvers"}
