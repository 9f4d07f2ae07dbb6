"""metric-names: the metric namespace stays coherent.

Walks every registration call
site (`<registry>.counter/gauge/histogram("name", ...)`) via the shared
AST cache and enforces the scheme docs/OBSERVABILITY.md promises:

1. every metric name starts with `edl_`;
2. counter names end in `_total`, histogram names do not;
3. one name is never registered with two different kinds or label sets
   anywhere in the tree (identical re-registrations are the registry's
   documented shared-family pattern).
"""

import ast

from tools.edl_lint.core import Finding, Rule

_KINDS = ("counter", "gauge", "histogram")


def _labelnames(call):
    value = None
    for kw in call.keywords:
        if kw.arg == "labelnames":
            value = kw.value
    if value is None and len(call.args) >= 3:
        value = call.args[2]
    if value is None:
        return ()
    if isinstance(value, (ast.Tuple, ast.List)):
        names = []
        for elt in value.elts:
            if not (
                isinstance(elt, ast.Constant)
                and isinstance(elt.value, str)
            ):
                return None
            names.append(elt.value)
        return tuple(names)
    return None


class MetricNamesRule(Rule):
    name = "metric-names"
    doc = (
        "Metric registrations keep the edl_ prefix, counter/_total "
        "suffix convention, and a conflict-free namespace."
    )

    def check(self, project):
        by_name = {}
        for sf in project.iter_files("elasticdl_tpu"):
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if not (
                    isinstance(func, ast.Attribute)
                    and func.attr in _KINDS
                ):
                    continue
                if not node.args:
                    continue
                first = node.args[0]
                if not (
                    isinstance(first, ast.Constant)
                    and isinstance(first.value, str)
                ):
                    continue
                name, kind = first.value, func.attr
                labels = _labelnames(node)
                if not name.startswith("edl_"):
                    yield Finding(
                        self.name, sf.rel, node.lineno,
                        f"metric {name!r} must carry the edl_ prefix",
                        key=f"prefix:{name}",
                    )
                if kind == "counter" and not name.endswith("_total"):
                    yield Finding(
                        self.name, sf.rel, node.lineno,
                        f"counter {name!r} must end in _total",
                        key=f"suffix:{name}",
                    )
                if kind == "histogram" and name.endswith("_total"):
                    yield Finding(
                        self.name, sf.rel, node.lineno,
                        f"histogram {name!r} must not end in _total "
                        f"(scrapers infer counters from the suffix)",
                        key=f"suffix:{name}",
                    )
                prior = by_name.get(name)
                where = f"{sf.rel}:{node.lineno}"
                if prior is None:
                    by_name[name] = (kind, labels, where)
                else:
                    p_kind, p_labels, p_where = prior
                    same = p_kind == kind and (
                        labels is None
                        or p_labels is None
                        or tuple(labels) == tuple(p_labels)
                    )
                    if not same:
                        yield Finding(
                            self.name, sf.rel, node.lineno,
                            f"metric {name!r} re-registered as "
                            f"{kind}{labels} — conflicts with "
                            f"{p_kind}{p_labels} at {p_where} (the "
                            f"runtime registry will raise on whichever "
                            f"loads second)",
                            key=f"conflict:{name}",
                        )
