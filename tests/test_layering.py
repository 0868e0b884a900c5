"""Imports point down: the order of the package's layers, asserted.

    events <- (ops/, params, cache) <- capture <- planner <- fusion <- circuits
        <- (segments, gradients/, resilience/segmented) <- engine/
        <- (sampling/request, trajectories/ensemble)

(``events`` imports nothing of the package; the kernels' host-side zone fold
in ``ops/pallas_gates.py`` reads its algebra.)

One case a module: every ``import`` of the module is parsed with ``ast``
(imports inside functions too; ``if TYPE_CHECKING:`` blocks are annotations
and are left out) and none may name a module of a HIGHER layer. ``engine/``
is the one box every arrow leaves from and none enters: beside the layers
above it, only the package's ``__init__`` and the two checkers that audit it
import it. The facade methods that reach up (``Circuit.gradient`` and the
like) are listed by name in ``FACADES``; each must be an import inside its
function, and each is a debt (ROADMAP C1), not a pattern to copy.
"""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "quest_tpu"

#: lowest first; a name is a module or a package (with everything in it)
LAYERS = (
    ("events",),
    ("ops", "params", "cache"),
    ("capture",),
    ("planner",),
    ("fusion",),
    ("circuits",),
    ("segments", "gradients", "resilience.segmented"),
    ("engine",),
    ("sampling.request", "trajectories.ensemble"),
)

#: who may import ``engine`` beside ``engine/`` itself and the layer above it
ENGINE_CLIENTS = ("sampling.request", "trajectories.ensemble",
                  "analysis.concheck", "analysis.surface", "")

#: (importer, imported layer member) -> the method that reaches up
FACADES = {
    ("circuits", "gradients"): "Circuit.gradient",
    ("circuits", "segments"): "Circuit.fused (stamp_plan), "
                              "compiled_segments, compiled_request",
    ("circuits", "resilience.segmented"): "Circuit.run_segmented",
    ("gradients.shift", "sampling.request"): "parameter_shift "
                                             "(shot-based shifts)",
}


def _modules():
    out = {}
    for path in sorted(ROOT.rglob("*.py")):
        parts = list(path.relative_to(ROOT).with_suffix("").parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        out[".".join(parts)] = path
    return out


MODULES = _modules()


def _member(name, member):
    return name == member or name.startswith(member + ".")


def _layer(name):
    """(index, member) of the layer ``name`` lies in, or (None, None)."""
    best = (None, None)
    for i, layer in enumerate(LAYERS):
        for member in layer:
            if _member(name, member) and (
                    best[1] is None or len(member) > len(best[1])):
                best = (i, member)
    return best


def _imports(name):
    """``(target module, lineno, inside a function)`` for every import of a
    module of this package that ``name`` makes."""
    path = MODULES[name]
    package = name if path.name == "__init__.py" else name.rpartition(".")[0]
    found = []

    def resolve(node, alias):
        if isinstance(node, ast.Import):
            target = alias.name
            if not target.startswith("quest_tpu"):
                return None
            target = target[len("quest_tpu"):].lstrip(".")
        else:
            if node.level == 0:
                if not (node.module or "").startswith("quest_tpu"):
                    return None
                base = node.module[len("quest_tpu"):].lstrip(".")
            else:
                up = package.split(".") if package else []
                up = up[:len(up) - (node.level - 1)]
                base = ".".join(up + ([node.module] if node.module else []))
            target = f"{base}.{alias.name}" if base else alias.name
            if target not in MODULES:       # a name of the module ``base``
                target = base
        while target and target not in MODULES:
            target = target.rpartition(".")[0]
        return target

    def visit(node, in_function):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.If) and "TYPE_CHECKING" in ast.dump(
                    child.test):
                continue
            if isinstance(child, (ast.Import, ast.ImportFrom)):
                for alias in child.names:
                    target = resolve(child, alias)
                    if target is not None:
                        found.append((target, child.lineno, in_function))
            visit(child, in_function or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)))

    visit(ast.parse(path.read_text()), False)
    return found


@pytest.mark.parametrize("name", sorted(MODULES),
                         ids=lambda n: n or "quest_tpu")
def test_imports_point_down(name):
    mine, _ = _layer(name)
    problems, used = [], set()
    for target, lineno, in_function in _imports(name):
        theirs, member = _layer(target)
        if _member(target, "engine") and not _member(name, "engine") \
                and name not in ENGINE_CLIENTS:
            problems.append(f"line {lineno}: imports {target}: engine/ is "
                            "imported by its clients alone")
        if mine is None or theirs is None or theirs <= mine:
            continue
        if (name, member) in FACADES:
            used.add((name, member))
            if not in_function:
                problems.append(
                    f"line {lineno}: {target} is above {name}: the facade "
                    f"{FACADES[(name, member)]} imports it where it uses it")
            continue
        problems.append(f"line {lineno}: imports {target}, a layer above")
    stale = [key for key in FACADES if key[0] == name and key not in used]
    assert not problems and not stale, (name, problems, stale)
