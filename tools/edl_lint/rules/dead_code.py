"""dead-code: unused imports and unreferenced module-level symbols.

Per-file pass: an import binding never used anywhere in its module
(`__init__.py` files are exempt — their imports ARE the re-export
surface; a name quoted in `__all__` counts as used).

Whole-program pass: a module-level function or class in elasticdl_tpu/
whose name is referenced NOWHERE else across the library, tools/ and
tests/ — not as a Name, not as an attribute, not inside
any string literal (covers getattr-by-name, model-zoo lookup strings,
and doc references). Decorated definitions are exempt (registration
side effects), as are dunders and `main`.
"""

import ast
import os
import re

from tools.edl_lint.core import Finding, Rule

_WORD_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _import_bindings(node):
    """[(binding_name, lineno, shown_as)] for an import statement."""
    out = []
    if isinstance(node, ast.Import):
        for alias in node.names:
            if alias.asname:
                out.append((alias.asname, node.lineno, alias.name))
            else:
                out.append(
                    (alias.name.split(".")[0], node.lineno, alias.name)
                )
    elif isinstance(node, ast.ImportFrom):
        if node.module == "__future__":
            return out
        for alias in node.names:
            if alias.name == "*":
                continue
            out.append(
                (alias.asname or alias.name, node.lineno, alias.name)
            )
    return out


class DeadCodeRule(Rule):
    name = "dead-code"
    doc = (
        "No unused imports; no module-level functions/classes that "
        "nothing in the repo references."
    )

    def check(self, project):
        yield from self._unused_imports(project)
        yield from self._dead_symbols(project)

    # -- per-file: unused imports ----------------------------------------

    def _unused_imports(self, project):
        zoo_prefix = os.path.join("elasticdl_tpu", "models") + os.sep
        for sf in project.iter_files("elasticdl_tpu"):
            if sf.rel.endswith("__init__.py"):
                continue
            if sf.rel.startswith(zoo_prefix):
                # Model-zoo modules export by ATTRIBUTE PRESENCE: the
                # loader getattr()s feed/loss/optimizer/... off the
                # module, so `from .common import feed` with no local
                # use is the zoo's re-export surface, not dead code.
                continue
            imports = []
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.Import, ast.ImportFrom)):
                    imports.extend(_import_bindings(node))
            if not imports:
                continue
            used = set()
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Name):
                    used.add(node.id)
                elif isinstance(node, ast.Constant) and isinstance(
                    node.value, str
                ):
                    # __all__, docstring references, annotations-as-str
                    used.update(_WORD_RE.findall(node.value))
            for binding, lineno, shown in imports:
                if binding not in used:
                    yield Finding(
                        self.name,
                        sf.rel,
                        lineno,
                        f"unused import `{shown}`"
                        + (
                            f" (as `{binding}`)"
                            if binding != shown
                            else ""
                        ),
                        key=f"unused-import:{binding}",
                    )

    # -- whole-program: dead module-level symbols ------------------------

    def _dead_symbols(self, project):
        # Identifier usage index across the whole repo (plus tests/,
        # which the default Project roots exclude for other rules).
        usage = {}

        def count_file(sf):
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Name):
                    usage[node.id] = usage.get(node.id, 0) + 1
                elif isinstance(node, ast.Attribute):
                    usage[node.attr] = usage.get(node.attr, 0) + 1
                elif isinstance(node, (ast.Import, ast.ImportFrom)):
                    # Import statements reference symbols WITHOUT Name
                    # nodes — `from m import get_at as _ga` must count
                    # as a use of get_at or aliased imports read as dead.
                    for alias in node.names:
                        for part in alias.name.split("."):
                            usage[part] = usage.get(part, 0) + 1
                elif isinstance(node, ast.Constant) and isinstance(
                    node.value, str
                ):
                    for word in _WORD_RE.findall(node.value):
                        usage[word] = usage.get(word, 0) + 1

        for sf in project.files.values():
            count_file(sf)
        tests_dir = os.path.join(project.root, "tests")
        if os.path.isdir(tests_dir):
            import types

            for dirpath, dirnames, filenames in os.walk(tests_dir):
                dirnames[:] = [
                    d for d in dirnames if d != "__pycache__"
                ]
                for name in sorted(filenames):
                    if not name.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, name)
                    try:
                        with open(path) as f:
                            source = f.read()
                        tree = ast.parse(source)
                    except (OSError, SyntaxError):
                        continue
                    count_file(types.SimpleNamespace(tree=tree))

        for sf in project.iter_files("elasticdl_tpu"):
            if sf.rel.endswith("__init__.py"):
                continue
            for node in sf.tree.body:
                if not isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)
                ):
                    continue
                name = node.name
                if (
                    name.startswith("__")
                    or name == "main"
                    or node.decorator_list
                ):
                    continue
                # The definition itself is not a Name/Attribute node, so
                # any usage count at all means a live reference.
                if usage.get(name, 0) == 0:
                    kind = (
                        "class"
                        if isinstance(node, ast.ClassDef)
                        else "function"
                    )
                    yield Finding(
                        self.name,
                        sf.rel,
                        node.lineno,
                        f"{kind} `{name}` is referenced nowhere in the "
                        f"repo (library, tools, tests) — delete "
                        f"it or wire it in",
                        key=f"dead:{name}",
                    )
