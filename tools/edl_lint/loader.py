"""Shared module loader: walk the repo ONCE, parse every Python file
ONCE, and hand the same AST/source/suppression cache to every rule.
Rules never touch the filesystem themselves — per-file passes iterate
`project.files`, whole-program passes use the cross-file indexes built
lazily by resolver.Resolver."""

import ast
import os

from tools.edl_lint.core import parse_suppressions

# The lint plane itself hosts pattern literals (forbidden-call regexes,
# fixture snippets) that would self-trigger textual rules.
_SKIP_DIRS = {"__pycache__"}
_SKIP_PREFIXES = (os.path.join("tools", "edl_lint"),)


class SourceFile:
    __slots__ = ("rel", "path", "source", "lines", "tree", "suppressions")

    def __init__(self, rel, path, source, tree):
        self.rel = rel
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self.suppressions = parse_suppressions(self.lines)


class Project:
    """Every parsed source file plus repo metadata, shared by all rules."""

    def __init__(self, root, files, parse_errors):
        self.root = root
        self.files = files  # rel -> SourceFile
        self.parse_errors = parse_errors  # [(rel, lineno, message)]
        self._resolver = None

    @classmethod
    def load(cls, root, roots=("elasticdl_tpu", "tools"),
             extra_files=("__graft_entry__.py",)):
        files = {}
        parse_errors = []

        def add(path):
            rel = os.path.relpath(path, root)
            if rel.startswith(_SKIP_PREFIXES):
                return
            try:
                with open(path) as f:
                    source = f.read()
            except OSError:
                return
            try:
                tree = ast.parse(source, filename=rel)
            except SyntaxError as e:
                parse_errors.append((rel, e.lineno or 0, str(e)))
                return
            files[rel] = SourceFile(rel, path, source, tree)

        for top in roots:
            for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, top)
            ):
                dirnames[:] = sorted(
                    d for d in dirnames if d not in _SKIP_DIRS
                )
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        add(os.path.join(dirpath, name))
        for name in extra_files:
            path = os.path.join(root, name)
            if os.path.exists(path):
                add(path)
        return cls(root, files, parse_errors)

    @property
    def resolver(self):
        if self._resolver is None:
            from tools.edl_lint.resolver import Resolver

            self._resolver = Resolver(self)
        return self._resolver

    def iter_files(self, prefix=None):
        for rel in sorted(self.files):
            if prefix is None or rel.startswith(prefix):
                yield self.files[rel]

    def module_name(self, rel):
        """Dotted module name for a repo-relative path, or None for
        scripts outside an importable package."""
        if not rel.endswith(".py"):
            return None
        parts = rel[:-3].split(os.sep)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)
