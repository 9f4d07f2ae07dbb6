"""env-knobs: every ELASTICDL_* environment read goes through the
central registry.

common/knobs.py declares every knob once (name/type/default/doc); this
rule enforces the contract statically:

1. an `os.environ[...]` / `os.environ.get` / `os.getenv` READ whose key
   resolves to an `ELASTICDL_*` string anywhere outside common/knobs.py
   is an error (writes — seeding child environments — stay legal);
2. a `knobs.get_*/raw/is_set` call naming an undeclared knob is an
   error, as is a `knobs.declare()` outside the registry module;
3. duplicate `declare()` calls for one name with conflicting
   type/default are errors;
4. docs/KNOBS.md must match the table generated from the registry
   (`python -m tools.edl_lint --write-knob-docs` refreshes it);
5. a knob the registry declares and no module under elasticdl_tpu/ or
   tools/ reads through an accessor is an error: its last reader went,
   and the declaration goes with it.

Key names are resolved through literals, module constants, and imported
constants (`observability.OBS_DIR_ENV`); an unresolvable dynamic key is
not flagged, and counts for clause 5 as a read of every knob name its
file spells.
"""

import ast
import os

from tools.edl_lint.core import Finding, Rule

_KNOBS_REL = os.path.join("elasticdl_tpu", "common", "knobs.py")
_ACCESSORS = {"get_str", "get_int", "get_float", "raw", "is_set"}
_DOCS_REL = os.path.join("docs", "KNOBS.md")

KNOB_DOCS_HEADER = """\
# Environment knobs

Every `ELASTICDL_*` environment variable the framework reads, generated
from the central registry in `elasticdl_tpu/common/knobs.py` by
`python -m tools.edl_lint --write-knob-docs`. Do not edit by hand — the
`env-knobs` lint rule fails when this table drifts from the registry.

"""


def render_knob_docs():
    from elasticdl_tpu.common import knobs

    return KNOB_DOCS_HEADER + knobs.docs_table()


def _declared_names():
    from elasticdl_tpu.common import knobs

    return {k.name for k in knobs.all_knobs()}


def _knobs_call(node, minfo):
    """`declare`, `get_int`, ... for a call on the knobs module, else
    None."""
    dotted = minfo.dotted(node.func) or ""
    if dotted.startswith(("elasticdl_tpu.common.knobs.", "knobs.")):
        return dotted.rsplit(".", 1)[-1]
    return None


def _accessor_calls(sf, minfo, resolver):
    """(node, accessor, name) for every `knobs.get_*/raw/is_set` call of
    one file; name is None where the key does not resolve to a string."""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call) and node.args:
            tail = _knobs_call(node, minfo)
            if tail in _ACCESSORS:
                yield node, tail, resolver.resolve_str(node.args[0], minfo)


class EnvKnobsRule(Rule):
    name = "env-knobs"
    doc = (
        "ELASTICDL_* environment reads must go through the "
        "common/knobs.py registry; accessor names must be declared "
        "and declared names read; docs/KNOBS.md must match the registry."
    )

    def check(self, project):
        declared = _declared_names()
        resolver = project.resolver
        for sf in project.iter_files("elasticdl_tpu"):
            if sf.rel == _KNOBS_REL:
                continue
            minfo = resolver.module(sf.rel)
            yield from self._check_file(sf, minfo, resolver, declared)
        yield from self._check_declarations(project)
        yield from self._check_docs(project)

    def read_names(self, project):
        """Every knob name that a module under elasticdl_tpu/ or tools/
        (the registry apart) hands to an accessor, resolved as clause 2
        resolves it. A file that hands an accessor a key it computes (a
        loop over names, a helper's parameter) reads every ELASTICDL_*
        name it spells: unresolvable is not unread."""
        resolver = project.resolver
        read = set()
        for root in ("elasticdl_tpu", "tools"):
            for sf in project.iter_files(root):
                if sf.rel == _KNOBS_REL:
                    continue
                keys = {
                    key for _, _, key in _accessor_calls(
                        sf, resolver.module(sf.rel), resolver
                    )
                }
                if None in keys:
                    keys |= {
                        node.value for node in ast.walk(sf.tree)
                        if isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and node.value.startswith("ELASTICDL_")
                    }
                read |= keys - {None}
        return read

    # -- raw environ reads ----------------------------------------------

    def _check_file(self, sf, minfo, resolver, declared):
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Subscript):
                if (
                    isinstance(node.ctx, ast.Load)
                    and (minfo.dotted(node.value) or "")
                    .endswith("os.environ")
                ):
                    key = resolver.resolve_str(node.slice, minfo)
                    if key and key.startswith("ELASTICDL_"):
                        yield self._raw_read(sf, node, key)
            elif isinstance(node, ast.Call):
                dotted = minfo.dotted(node.func) or ""
                if dotted.endswith("os.environ.get") or dotted.endswith(
                    "os.getenv"
                ):
                    if node.args:
                        key = resolver.resolve_str(node.args[0], minfo)
                        if key and key.startswith("ELASTICDL_"):
                            yield self._raw_read(sf, node, key)
                elif _knobs_call(node, minfo) == "declare":
                    yield Finding(
                        self.name,
                        sf.rel,
                        node.lineno,
                        "knobs.declare() outside common/knobs.py — "
                        "declarations live centrally so defaults "
                        "cannot diverge",
                        key="declare-outside-registry",
                    )
        for node, tail, key in _accessor_calls(sf, minfo, resolver):
            if key is not None and key not in declared:
                yield Finding(
                    self.name,
                    sf.rel,
                    node.lineno,
                    f"knobs.{tail}({key!r}) reads an UNDECLARED knob — "
                    f"declare it in common/knobs.py",
                    key=f"undeclared:{key}",
                )

    def _raw_read(self, sf, node, key):
        return Finding(
            self.name,
            sf.rel,
            node.lineno,
            f"direct environment read of {key} — go through "
            f"elasticdl_tpu.common.knobs (get_str/get_int/get_float/"
            f"raw) so the knob is declared, typed, and documented",
            key=f"raw-read:{key}",
        )

    # -- registry self-consistency ---------------------------------------

    def _check_declarations(self, project):
        sf = project.files.get(_KNOBS_REL)
        if sf is None:
            yield Finding(
                self.name, _KNOBS_REL, 0,
                "common/knobs.py registry is missing", key="no-registry",
            )
            return
        seen = {}
        for node in ast.walk(sf.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "declare"
            ):
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant):
                continue
            name = node.args[0].value
            signature = ast.dump(
                ast.Tuple(elts=list(node.args[1:3]), ctx=ast.Load())
            )
            prior = seen.get(name)
            if prior is None:
                seen[name] = (signature, node.lineno)
            elif prior[0] != signature:
                yield Finding(
                    self.name,
                    sf.rel,
                    node.lineno,
                    f"knob {name} declared twice with conflicting "
                    f"type/default (first at line {prior[1]})",
                    key=f"duplicate:{name}",
                )
        read = self.read_names(project)
        for name, (_, lineno) in seen.items():
            if name not in read:
                yield Finding(
                    self.name,
                    sf.rel,
                    lineno,
                    f"knob {name} is declared and never read: no module "
                    f"under elasticdl_tpu/ or tools/ hands it to an "
                    f"accessor — delete the declaration",
                    key=f"unread:{name}",
                )

    # -- generated docs freshness ----------------------------------------

    def _check_docs(self, project):
        path = os.path.join(project.root, _DOCS_REL)
        expected = render_knob_docs()
        try:
            with open(path) as f:
                current = f.read()
        except FileNotFoundError:
            current = None
        if current != expected:
            yield Finding(
                self.name,
                _DOCS_REL,
                1,
                "docs/KNOBS.md is stale relative to the knob registry — "
                "run `python -m tools.edl_lint --write-knob-docs`",
                key="stale-docs",
            )
