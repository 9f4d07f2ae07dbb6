"""Interprocedural dataflow engine for the edl-lint plane.

Layers a whole-program view on top of loader.Project + resolver.Resolver
so rules can reason ACROSS function and module boundaries instead of one
statement at a time:

- **Call graph** (`Engine.callees`): direct calls of module-level
  functions (import aliases expanded), `self.method(...)` dispatch
  (own class, then bases via the class index), `super().method(...)`,
  collaborator-field dispatch (`self._ps.pull(...)` resolved through the
  field's inferred class), and calls on locals constructed from a known
  class. Functions passed as ARGUMENTS to `tracked_jit`/`jax.jit`,
  `threading.Thread(target=...)`, and executor `submit(...)` are
  recorded as *deferred* edges: they run later, usually on another
  thread, so hot-path reachability excludes them while escape analyses
  can include them.
- **Jit-binding index** (`Engine.jit_sites`): every
  `tracked_jit`/`jax.jit`/`pjit` construction, the binding it lands in
  (a local, `self.attr = ...`, or `self.attr = self._build_x()` where
  `_build_x` returns the construction), and every call site of that
  binding. This is how the donation and hot-path-sync rules connect a
  jit's declaration to the arguments that actually flow through it.
- **Summary propagation** (`propagate_facts`): the iterative fixpoint
  the concurrency rule introduced for transitive lock acquisition,
  generalized — facts attach to (class, qualname) nodes and flow from
  callee to caller until stable. NOT a memoized DFS: a DFS cycle cutoff
  caches truncated sets for mutually-recursive methods.

Stdlib-only, AST-level; nothing here imports jax (tier-1-enforced).
"""

import ast

# Constructors whose function argument runs LATER (another thread, a
# trace, an interceptor chain) rather than inline at the call site.
_DEFERRED_TAILS = {
    "jit", "pjit", "tracked_jit", "shard_map", "Thread", "Timer",
    "submit", "map", "add_done_callback", "intercept_channel",
}

_JIT_TAILS = {"jit", "pjit", "tracked_jit"}


def _is_jit_construction(dotted):
    if not dotted:
        return False
    tail = dotted.rsplit(".", 1)[-1]
    if tail not in _JIT_TAILS:
        return False
    return "jax" in dotted or "profiling" in dotted or tail == "tracked_jit"


def self_attr(node):
    """'X' when node is `self.X`, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def self_attr_chain(node):
    """The self attribute at the ROOT of an attribute/subscript chain:
    `self._stubs[i].push.future` -> '_stubs'. None when the chain does
    not bottom out at `self.<attr>`."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        attr = self_attr(node)
        if attr is not None:
            return attr
        node = node.value
    return None


def _returns_name(fn_node, name):
    """True when the function returns the local `name`, alone or as an
    element of a returned tuple."""
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Return) or node.value is None:
            continue
        for elt in getattr(node.value, "elts", [node.value]):
            if isinstance(elt, ast.Name) and elt.id == name:
                return True
    return False


class FunctionInfo:
    """One analyzable function: module file, qualified name, AST node."""

    __slots__ = ("rel", "qualname", "node", "class_name", "minfo")

    def __init__(self, rel, qualname, node, class_name, minfo):
        self.rel = rel
        self.qualname = qualname
        self.node = node
        self.class_name = class_name
        self.minfo = minfo

    @property
    def key(self):
        return (self.rel, self.qualname)

    @property
    def name(self):
        return self.qualname.rsplit(".", 1)[-1]


class CallEdge:
    __slots__ = ("caller", "callee", "line", "call", "deferred")

    def __init__(self, caller, callee, line, call, deferred=False):
        self.caller = caller  # key
        self.callee = callee  # key
        self.line = line
        self.call = call  # the ast.Call (None for deferred fn refs)
        self.deferred = deferred


class JitSite:
    """One tracked_jit/jax.jit construction plus its resolved binding and
    call sites."""

    __slots__ = (
        "rel", "call", "owner", "wrapped", "jit_name", "donate",
        "binding", "call_sites",
    )

    def __init__(self, rel, call, owner, wrapped, jit_name, donate):
        self.rel = rel
        self.call = call  # the construction ast.Call
        self.owner = owner  # FunctionInfo containing the construction
        self.wrapped = wrapped  # FunctionDef/Lambda or None
        self.jit_name = jit_name  # name= kwarg value (str) or wrapped name
        self.donate = donate  # donate kwarg ast node or None
        self.binding = None  # ("attr", class, attrname) | ("local", fn-key, name)
        self.call_sites = []  # [(FunctionInfo, ast.Call)]

    @property
    def line(self):
        return self.call.lineno

    @property
    def display(self):
        return self.jit_name or "<anonymous>"


def iter_functions(tree):
    """(qualname, class_name, node) for every module-level function and
    every method of a module-level class (nested defs belong to their
    parent's body and are analyzed in place)."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, None, node
        elif isinstance(node, ast.ClassDef):
            for stmt in node.body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{node.name}.{stmt.name}", node.name, stmt


def propagate_facts(direct, callees):
    """Iterative fixpoint: each node's fact set grows by its callees'
    until stable. `direct`: {key: set}; `callees`: {key: iterable of
    callee keys}. Returns the saturated {key: set} (inputs unmodified)."""
    facts = {key: set(v) for key, v in direct.items()}
    changed = True
    while changed:
        changed = False
        for key, called in callees.items():
            mine = facts.setdefault(key, set())
            for callee in called:
                extra = facts.get(callee, ())
                if not mine.issuperset(extra):
                    mine |= extra
                    changed = True
    return facts


class Engine:
    """The whole-program indexes, built once per Project and shared by
    every dataflow rule (Project caches the instance)."""

    def __init__(self, project, prefixes=("elasticdl_tpu",)):
        self.project = project
        self.resolver = project.resolver
        self.functions = {}  # key -> FunctionInfo
        self._by_class_method = {}  # (class, method) -> [key]
        self._by_module_func = {}  # (rel, name) -> key
        self._class_rel = {}  # class name -> [rel]
        self._lower_classes = {}  # lowercased class name -> class name
        self._bases = {}  # class name -> [base class names]
        self.field_classes = {}  # (class name) -> {field: class name}
        self.edges = []  # [CallEdge]
        self._out = {}  # key -> [CallEdge]
        self.jit_sites = []
        self._jit_attr_bindings = {}  # (class, attr) -> [JitSite]
        self._jit_local_bindings = {}  # (fn-key, local) -> [JitSite]
        self._jit_returning = {}  # key -> JitSite (method returns the binding)

        for sf in project.iter_files():
            if not sf.rel.startswith(tuple(prefixes)):
                continue
            minfo = self.resolver.module(sf.rel)
            for qualname, class_name, node in iter_functions(sf.tree):
                info = FunctionInfo(sf.rel, qualname, node, class_name, minfo)
                self.functions[info.key] = info
                if class_name:
                    self._by_class_method.setdefault(
                        (class_name, info.name), []
                    ).append(info.key)
                else:
                    self._by_module_func[(sf.rel, info.name)] = info.key
            for name, classdef in minfo.classes.items():
                self._class_rel.setdefault(name, []).append(sf.rel)
                self._lower_classes.setdefault(name.lower(), name)
                self._bases[name] = [
                    b.id for b in classdef.bases if isinstance(b, ast.Name)
                ] + [
                    b.attr
                    for b in classdef.bases
                    if isinstance(b, ast.Attribute)
                ]

        self._infer_field_classes()
        for info in list(self.functions.values()):
            self._scan_function(info)
        self._resolve_jit_bindings()

    # -- class/field inference -------------------------------------------

    def _known_class(self, name):
        """A class-index name matching `name` case-insensitively (the
        snake_case->CamelCase round trip loses interior capitalization:
        ps_client -> PsClient, but the class is PSClient)."""
        if name in self._class_rel:
            return name
        return self._lower_classes.get(name.lower())

    def _camel(self, snake):
        return self._known_class(
            "".join(p.title() for p in snake.split("_") if p)
        )

    def _infer_field_classes(self):
        """self.<field> -> class name, from constructor calls and from
        snake_case parameter/variable naming (`self._ps = ps_client`)."""
        for info in self.functions.values():
            if not info.class_name:
                continue
            fields = self.field_classes.setdefault(info.class_name, {})
            for node in ast.walk(info.node):
                if not (
                    isinstance(node, ast.Assign) and len(node.targets) == 1
                ):
                    continue
                attr = self_attr(node.targets[0])
                if not attr:
                    continue
                value = node.value
                target_class = None
                if isinstance(value, ast.Call):
                    dotted = info.minfo.dotted(value.func) or ""
                    target_class = self._known_class(
                        dotted.rsplit(".", 1)[-1]
                    )
                elif isinstance(value, ast.Name):
                    target_class = self._camel(value.id)
                if target_class:
                    fields.setdefault(attr, target_class)

    def _method_candidates(self, class_name, method):
        """Keys of `method` on class_name, walking base classes through
        the class index when the class itself doesn't define it."""
        seen = set()
        frontier = [class_name]
        while frontier:
            cls = frontier.pop(0)
            if cls in seen or cls is None:
                continue
            seen.add(cls)
            keys = self._by_class_method.get((cls, method))
            if keys:
                return keys
            frontier.extend(self._bases.get(cls, ()))
        return []

    # -- per-function scan -----------------------------------------------

    def _scan_function(self, info):
        minfo = info.minfo
        local_classes = {}  # local name -> class (constructed in fn)
        for node in ast.walk(info.node):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
            ):
                dotted = minfo.dotted(node.value.func) or ""
                cls = self._known_class(dotted.rsplit(".", 1)[-1])
                if cls:
                    local_classes[node.targets[0].id] = cls
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            self._record_call(info, node, local_classes)
            self._record_deferred(info, node, minfo)
            self._maybe_jit_site(info, node, minfo)

    def _record_call(self, info, call, local_classes):
        minfo = info.minfo
        func = call.func
        targets = []
        if isinstance(func, ast.Name):
            # Module-level function in this module, or imported from a
            # project module.
            key = self._by_module_func.get((info.rel, func.id))
            if key:
                targets = [key]
            else:
                dotted = minfo.imports.get(func.id)
                if dotted and "." in dotted:
                    mod, name = dotted.rsplit(".", 1)
                    rel = self.resolver.dotted_to_rel.get(mod)
                    if rel:
                        key = self._by_module_func.get((rel, name))
                        if key:
                            targets = [key]
        elif isinstance(func, ast.Attribute):
            base = func.value
            method = func.attr
            if isinstance(base, ast.Name) and base.id == "self":
                if info.class_name:
                    targets = self._method_candidates(
                        info.class_name, method
                    )
            elif (
                isinstance(base, ast.Call)
                and isinstance(base.func, ast.Name)
                and base.func.id == "super"
            ):
                for parent in self._bases.get(info.class_name or "", ()):
                    targets = self._method_candidates(parent, method)
                    if targets:
                        break
            elif isinstance(base, ast.Name) and base.id in local_classes:
                targets = self._method_candidates(
                    local_classes[base.id], method
                )
            else:
                # Collaborator field: self.<field>.method(...), possibly
                # through a subscript (self._stubs[i].method).
                field = self_attr_chain(base)
                if field and info.class_name:
                    cls = self.field_classes.get(info.class_name, {}).get(
                        field
                    )
                    if cls:
                        targets = self._method_candidates(cls, method)
                else:
                    # module.func(...) through an import alias
                    dotted = minfo.dotted(func)
                    if dotted and "." in dotted:
                        mod, name = dotted.rsplit(".", 1)
                        rel = self.resolver.dotted_to_rel.get(mod)
                        if rel:
                            key = self._by_module_func.get((rel, name))
                            if key:
                                targets = [key]
        for target in targets:
            edge = CallEdge(info.key, target, call.lineno, call)
            self.edges.append(edge)
            self._out.setdefault(info.key, []).append(edge)

    def _record_deferred(self, info, call, minfo):
        """Functions passed as values to thread/executor/jit/interceptor
        constructors: deferred edges."""
        dotted = minfo.dotted(call.func) or ""
        tail = dotted.rsplit(".", 1)[-1]
        if tail not in _DEFERRED_TAILS:
            return
        candidates = list(call.args)
        candidates.extend(
            kw.value
            for kw in call.keywords
            if kw.arg in ("target", "fun", "f", "fn")
        )
        for expr in candidates:
            target_keys = []
            if isinstance(expr, ast.Name):
                key = self._by_module_func.get((info.rel, expr.id))
                if key:
                    target_keys = [key]
            else:
                attr = self_attr(expr)
                if attr and info.class_name:
                    target_keys = self._method_candidates(
                        info.class_name, attr
                    )
            for target in target_keys:
                edge = CallEdge(
                    info.key, target, call.lineno, call, deferred=True
                )
                self.edges.append(edge)
                self._out.setdefault(info.key, []).append(edge)

    # -- jit-binding index -----------------------------------------------

    def _maybe_jit_site(self, info, call, minfo):
        dotted = minfo.dotted(call.func)
        if not _is_jit_construction(dotted):
            return
        wrapped = None
        if call.args:
            expr = call.args[0]
            if isinstance(expr, ast.Lambda):
                wrapped = expr
            elif isinstance(expr, ast.Name):
                # A def in the same (enclosing) function body or module.
                for node in ast.walk(info.node):
                    if (
                        isinstance(node, ast.FunctionDef)
                        and node.name == expr.id
                    ):
                        wrapped = node
                        break
                if wrapped is None:
                    key = self._by_module_func.get((info.rel, expr.id))
                    if key:
                        wrapped = self.functions[key].node
            else:
                attr = self_attr(expr)
                if attr and info.class_name:
                    for key in self._method_candidates(
                        info.class_name, attr
                    ):
                        wrapped = self.functions[key].node
                        break
        jit_name = None
        donate = None
        for kw in call.keywords:
            if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                jit_name = kw.value.value
            elif kw.arg in ("donate_argnums", "donate_argnames"):
                donate = kw.value
        if jit_name is None and isinstance(wrapped, ast.FunctionDef):
            jit_name = wrapped.name
        site = JitSite(info.rel, call, info, wrapped, jit_name, donate)
        self.jit_sites.append(site)

    def _resolve_jit_bindings(self):
        # Pass 1: construction -> binding. A construction assigned to a
        # local/attr binds there; a construction whose value reaches a
        # `return` of its owner method marks the METHOD as jit-returning.
        for site in self.jit_sites:
            owner = site.owner
            parents = {}
            for node in ast.walk(owner.node):
                for child in ast.iter_child_nodes(node):
                    parents[id(child)] = node
            parent = parents.get(id(site.call))
            bound_local = None
            if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
                target = parent.targets[0]
                attr = self_attr(target)
                if attr and owner.class_name:
                    site.binding = ("attr", owner.class_name, attr)
                    self._jit_attr_bindings.setdefault(
                        (owner.class_name, attr), []
                    ).append(site)
                    continue
                if isinstance(target, ast.Name):
                    bound_local = target.id
            if isinstance(parent, ast.Return) or (
                bound_local and _returns_name(owner.node, bound_local)
            ):
                self._jit_returning[owner.key] = site
                continue
            if bound_local:
                site.binding = ("local", owner.key, bound_local)
                self._jit_local_bindings.setdefault(
                    (owner.key, bound_local), []
                ).append(site)

        # Pass 2: bindings THROUGH builders, to a fixpoint over the call
        # graph — `self._train_step = self._build_train_step()` where the
        # builder returns a construction, and chains of them: the sharded
        # step is constructed in `step_plan.jit_step`, handed up by
        # `build_step` inside a tuple, unpacked and returned by
        # `_sharded_step_for`, and called in `_run_sharded_step`.
        callees = {}
        for edge in self.edges:
            if not edge.deferred:
                callees.setdefault(id(edge.call), []).append(edge.callee)
        assigned_calls = [
            (info, node)
            for info in self.functions.values()
            for node in ast.walk(info.node)
            if isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and id(node.value) in callees
        ]
        changed = True
        while changed:
            changed = False
            for info, node in assigned_calls:
                sites = [
                    self._jit_returning[key]
                    for key in callees[id(node.value)]
                    if key in self._jit_returning
                ]
                if not sites:
                    continue
                target = node.targets[0]
                # A tuple target binds every element: a builder's other
                # results are never called, so it costs nothing.
                for elt in getattr(target, "elts", [target]):
                    changed |= self._bind_built(info, elt, sites)

        # Pass 3: call sites of every binding.
        for info in self.functions.values():
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                attr = self_attr(func)
                if attr and info.class_name:
                    for site in self._jit_attr_bindings.get(
                        (info.class_name, attr), ()
                    ):
                        site.call_sites.append((info, node))
                elif isinstance(func, ast.Name):
                    for site in self._jit_local_bindings.get(
                        (info.key, func.id), ()
                    ):
                        site.call_sites.append((info, node))

    def _bind_built(self, info, target, sites):
        """Bind `target` (an attr or a local of `info`) to the jit sites a
        builder call returned; True when `info` thereby became a builder
        itself (it returns that local)."""
        attr = self_attr(target)
        if attr and info.class_name:
            index, key = self._jit_attr_bindings, (info.class_name, attr)
            binding = ("attr",) + key
        elif isinstance(target, ast.Name):
            index, key = self._jit_local_bindings, (info.key, target.id)
            binding = ("local",) + key
        else:
            return False
        bound = index.setdefault(key, [])
        for site in sites:
            if site.binding is None:
                site.binding = binding
            if site not in bound:
                bound.append(site)
        if (
            binding[0] == "local"
            and info.key not in self._jit_returning
            and _returns_name(info.node, target.id)
        ):
            self._jit_returning[info.key] = sites[0]
            return True
        return False

    # -- queries ---------------------------------------------------------

    def callees(self, key, include_deferred=False):
        for edge in self._out.get(key, ()):
            if edge.deferred and not include_deferred:
                continue
            yield edge

    def callee_map(self, include_deferred=False):
        return {
            key: {
                e.callee
                for e in self.callees(key, include_deferred)
            }
            for key in self.functions
        }

    def jit_call_returns(self, info):
        """ast.Call nodes in `info` whose callee is a jit binding (the
        device-value taint sources for hot-path-sync)."""
        out = set()
        for site in self.jit_sites:
            for caller, call in site.call_sites:
                if caller.key == info.key:
                    out.add(id(call))
        return out


def get_engine(project):
    """The per-Project Engine, built once and cached on the project."""
    engine = getattr(project, "_dataflow_engine", None)
    if engine is None:
        engine = Engine(project)
        project._dataflow_engine = engine
    return engine
