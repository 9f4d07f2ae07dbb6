"""Scope/attribute resolution shared by the rules.

ModuleInfo answers "what does this Name/Attribute chain actually refer
to" inside one module: import aliases are expanded to dotted targets
(`jnp.dot` -> `jax.numpy.dot`, a bare `shard_map` imported from jax ->
`jax.shard_map`), module-level
string constants are tracked for env-key resolution, and logger bindings
(`logger = get_logger(...)`) are recognized for the jit-purity pass.

Resolver layers the whole-program view on top: a class index across every
module, dotted-module -> file mapping, and cross-module constant lookup
(`observability.OBS_DIR_ENV` resolved through the import graph).
"""

import ast


class ModuleInfo:
    def __init__(self, sf, package):
        self.sf = sf
        self.package = package  # dotted package for relative imports
        self.imports = {}  # local alias -> dotted target
        self.constants = {}  # NAME -> str value (module-level)
        self.loggers = set()  # names bound to logger factories
        self.classes = {}  # name -> ClassDef
        self.functions = {}  # name -> FunctionDef (module level)
        self._scan()

    def _scan(self):
        for node in ast.walk(self.sf.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else name
                    self.imports[name] = target
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = self.package.split(".") if self.package else []
                    parts = parts[: len(parts) - (node.level - 1)]
                    base = ".".join(parts + ([base] if base else []))
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    name = alias.asname or alias.name
                    self.imports[name] = (
                        f"{base}.{alias.name}" if base else alias.name
                    )
        for node in self.sf.tree.body:
            if isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
                if not isinstance(target, ast.Name):
                    continue
                if isinstance(node.value, ast.Constant) and isinstance(
                    node.value.value, str
                ):
                    self.constants[target.id] = node.value.value
                elif isinstance(node.value, ast.Call):
                    dotted = self.dotted(node.value.func) or ""
                    if dotted.endswith("get_logger") or dotted.endswith(
                        "logging.getLogger"
                    ):
                        self.loggers.add(target.id)

    def dotted(self, node):
        """Dotted name for a Name/Attribute chain with the leading alias
        expanded through this module's imports; None for anything else."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        head = self.imports.get(node.id, node.id)
        parts.append(head)
        return ".".join(reversed(parts))


class Resolver:
    """Whole-program indexes, built lazily from the Project file cache."""

    def __init__(self, project):
        self.project = project
        self._modules = {}
        self.dotted_to_rel = {}
        self.class_index = {}
        for rel, sf in project.files.items():
            dotted = project.module_name(rel)
            if dotted:
                self.dotted_to_rel[dotted] = rel
        for rel in project.files:
            minfo = self.module(rel)
            for name in minfo.classes:
                self.class_index.setdefault(name, []).append(rel)

    def module(self, rel):
        minfo = self._modules.get(rel)
        if minfo is None:
            dotted = self.project.module_name(rel) or ""
            package = dotted.rsplit(".", 1)[0] if "." in dotted else ""
            if rel.endswith("__init__.py"):
                package = dotted
            minfo = ModuleInfo(self.project.files[rel], package)
            self._modules[rel] = minfo
        return minfo

    def resolve_constant(self, dotted):
        """The string value of a fully-dotted module constant
        (`elasticdl_tpu.observability.OBS_DIR_ENV` -> "ELASTICDL_OBS_DIR"),
        or None."""
        if not dotted or "." not in dotted:
            return None
        module_part, attr = dotted.rsplit(".", 1)
        rel = self.dotted_to_rel.get(module_part)
        if rel is None:
            return None
        return self.module(rel).constants.get(attr)

    def resolve_str(self, node, minfo):
        """Static string value of an expression: literal, same-module
        constant, or imported-module constant. None when unknown."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            value = minfo.constants.get(node.id)
            if value is not None:
                return value
            return self.resolve_constant(minfo.imports.get(node.id, ""))
        if isinstance(node, ast.Attribute):
            return self.resolve_constant(minfo.dotted(node))
        return None

    def find_class(self, name):
        """[(rel, ClassDef)] for every definition of a class name."""
        return [
            (rel, self.module(rel).classes[name])
            for rel in self.class_index.get(name, ())
        ]
