"""``paddle_tpu/`` stands alone and its layers point one way: read by ``ast``,
no module imports what lives beside the package in the checkout, loads a
module by a file path or builds a path that climbs out of the package; and
the arrows between its sub-packages that still point the wrong way are held
by name, so that paying one means striking it here (``ROADMAP.md`` D23)."""

import ast
import functools
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "paddle_tpu")

# what lies beside the package in a checkout, and in no installed wheel
_OUTSIDE = {"bench", "benchmark", "tools", "chip_smoke"}
# calls that load a module from a file path, or make one importable from it
_BY_PATH = {"spec_from_file_location", "SourceFileLoader", "run_path",
            "load_source"}
# paths above the package that are not reads of a checkout's files
_ALLOWED_CLIMBS = {
    # the persistent compile cache's default, <checkout>/.jax_cache: a
    # deployment path, which JAX_COMPILATION_CACHE_DIR places elsewhere
    ("compile_cache.py", "_DEFAULT_DIR"),
    # the directory the package is imported from, which the router hands
    # its worker children as PYTHONPATH; it opens nothing there
    (os.path.join("serving", "router.py"), "_REPO_ROOT"),
}

_UNITS = ["core", "layers", "ops", "parallel", "models", "serving",
          "streaming", "analysis", "obs", "reliability", "data", "dygraph",
          "contrib", "distributed", "<top level>"]


def _modules(unit=None):
    """(path relative to the package, parsed module) of every module of a
    sub-package; of the package's top-level modules for ``<top level>``; of
    the whole package for None."""
    if unit == "<top level>":
        files = [f for f in sorted(os.listdir(_PKG)) if f.endswith(".py")]
    else:
        top = os.path.join(_PKG, unit) if unit else _PKG
        assert os.path.isdir(top), top
        files = [os.path.relpath(os.path.join(d, f), _PKG)
                 for d, _, names in sorted(os.walk(top))
                 for f in sorted(names) if f.endswith(".py")]
    for rel in files:
        with open(os.path.join(_PKG, rel)) as f:
            yield rel, ast.parse(f.read(), rel)


def _imports(rel, tree):
    """(absolute module, imported name or None) of every import statement,
    relative ones resolved against the module's own package."""
    pkg = ["paddle_tpu"] + rel.split(os.sep)[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            for alias in node.names:
                yield mod, alias.name
        elif isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None


def _called(node):
    func = node.func
    return func.attr if isinstance(func, ast.Attribute) else getattr(
        func, "id", None)


def _own(stmt):
    """The nodes of a statement's own expressions: not those of the
    statements nested in it."""
    todo = [c for c in ast.iter_child_nodes(stmt)]
    while todo:
        node = todo.pop()
        if not isinstance(node, ast.stmt):
            yield node
            todo.extend(ast.iter_child_nodes(node))


def _climb(node, known):
    """How many directories above its own file the path ``node`` builds
    names (1: the module's directory), or None where ``__file__`` is not
    in it. ``known``: names already bound to such a path."""
    if isinstance(node, ast.Name):
        return 0 if node.id == "__file__" else known.get(node.id)
    if isinstance(node, ast.Attribute):
        up = _climb(node.value, known)
        return up if up is None or node.attr != "parent" else up + 1
    if isinstance(node, ast.Subscript):  # Path(__file__).parents[k]
        base = node.value
        if isinstance(base, ast.Attribute) and base.attr == "parents":
            up = _climb(base.value, known)
            if up is not None and isinstance(node.slice, ast.Constant):
                return up + node.slice.value + 1
        return None
    if not isinstance(node, ast.Call):
        return None
    ups = [u for u in (_climb(a, known) for a in node.args) if u is not None]
    if isinstance(node.func, ast.Attribute):  # Path(__file__).resolve()
        ups += [u for u in [_climb(node.func.value, known)] if u is not None]
    if not ups:
        return None
    if _called(node) == "dirname":
        return max(ups) + 1
    pardirs = sum(1 for a in node.args if (
        isinstance(a, ast.Constant) and a.value == "..") or (
        isinstance(a, ast.Attribute) and a.attr == "pardir"))
    return max(ups) + pardirs


def _reaches_out(rel, tree):
    """What a module does that needs the checkout round the package, each
    as a line that names it."""
    found = []
    for mod, name in _imports(rel, tree):
        if mod.split(".")[0] in _OUTSIDE:
            found.append("%s imports %s" % (rel, mod))
    inside = len(rel.split(os.sep))  # dirnames that reach the package
    known = {}
    stmts = [n for n in ast.walk(tree) if isinstance(n, ast.stmt)]
    for stmt in sorted(stmts, key=lambda n: n.lineno):
        own = list(_own(stmt))
        for call in (n for n in own if isinstance(n, ast.Call)):
            if _called(call) in _BY_PATH:
                found.append("%s:%d loads a module by file path (%s)"
                             % (rel, call.lineno, _called(call)))
            if _called(call) in ("import_module", "__import__") and any(
                    isinstance(a, ast.Constant) and str(a.value).split(".")[
                        0] in _OUTSIDE for a in call.args):
                found.append("%s:%d imports from outside the package by "
                             "name" % (rel, call.lineno))
        ups = [u for u in (_climb(n, known) for n in own) if u is not None]
        target = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            target = stmt.targets[0].id
        if not ups or max(ups) <= inside:
            if target and _climb(stmt.value, known) is not None:
                # a path inside the package: a later dirname of it counts
                known[target] = _climb(stmt.value, known)
        elif (rel, target) not in _ALLOWED_CLIMBS:
            found.append("%s:%d builds a path %d above paddle_tpu/ (%s)"
                         % (rel, stmt.lineno, max(ups) - inside,
                            target or "unnamed"))
    return found


@pytest.mark.parametrize("unit", _UNITS)
def test_no_module_reaches_out_of_the_package(unit):
    found = [line for rel, tree in _modules(unit)
             for line in _reaches_out(rel, tree)]
    assert not found, "\n".join(found)


def test_the_rule_sees_what_it_forbids():
    """The reader above, held to the forms it exists to refuse."""
    rel = os.path.join("analysis", "x.py")
    for src, want in [
        ("import os\ndef repo_root():\n    return os.path.dirname("
         "os.path.dirname(os.path.dirname(os.path.abspath(__file__))))",
         "builds a path 1 above"),
        ("import os\nhere = os.path.dirname(__file__)\n"
         "up = os.path.join(here, '..', '..', 'bench.py')",
         "builds a path 1 above"),
        ("from pathlib import Path\nr = Path(__file__).resolve().parents[2]",
         "builds a path 1 above"),
        ("import importlib.util as u\nu.spec_from_file_location('b', p)",
         "loads a module by file path"),
        ("import bench", "imports bench"),
        ("from benchmark import harness", "imports benchmark"),
        ("import importlib\nimportlib.import_module('tools.trace_view')",
         "by name"),
    ]:
        found = _reaches_out(rel, ast.parse(src))
        assert len(found) == 1 and want in found[0], (src, found)
    ok = ("import os\nd = os.path.dirname(os.path.dirname("
          "os.path.abspath(__file__)))\nf = os.path.join(d, 'core')")
    assert _reaches_out(rel, ast.parse(ok)) == []


@functools.lru_cache(maxsize=None)
def _arrows():
    """The rule: every import of ``paddle_tpu.analysis`` from outside
    ``analysis/`` (the package's own ``__init__``, which lists every
    sub-package, apart), every import of ``core`` from ``ops/``, and every
    ``_private`` name one module of ``ops/`` imports from another."""
    found = set()
    for rel, tree in _modules():
        top = rel.split(os.sep)[0]
        for mod, name in _imports(rel, tree):
            parts = (mod + ("." + name if name else "")).split(".")
            if parts[0] != "paddle_tpu" or rel == "__init__.py":
                continue
            to = parts[1:]
            if to[:1] == ["analysis"] and top != "analysis":
                found.add((rel, ".".join(to)))
            if top == "ops" and to[:1] == ["core"]:
                found.add((rel, ".".join(to)))
            if top == "ops" and to[:1] == ["ops"] and (
                    name or "").startswith("_"):
                found.add((rel, ".".join(to[1:])))
    return frozenset(found)


def test_models_import_nothing_from_analysis():
    reached = sorted(a for a in _arrows() if a[0].startswith("models"))
    assert reached == []


def test_the_arrows_that_still_point_the_wrong_way_are_these():
    """``ROADMAP.md`` D23, by name. A repair strikes its line here; a new
    one is refused."""
    j = os.path.join
    assert _arrows() == {
        (j("core", "epilogue_fusion.py"), "analysis.dataflow.build_region"),
        # the verifier, lazily, behind PADDLE_TPU_VERIFY
        (j("core", "executor.py"), "analysis.verify_program"),
        (j("core", "executor.py"), "analysis.resources.check_resources"),
        ("debugger.py", "analysis.dataflow.build_region"),
        (j("parallel", "sharded_embedding.py"),
         "analysis.cost.comm_bytes_model"),
        (j("serving", "engine.py"),
         "analysis.resources.decode_cache_verdict"),
        (j("serving", "decode_batcher.py"),
         "analysis.resources.decode_cache_verdict"),
        (j("ops", "scatter.py"), "core.op_registry.merge_sparse_rows"),
        (j("ops", "cache_attention.py"), "sparse_latent._block"),
    }
