"""rpc-deadlines: no call site escapes the deadline/retry plane.

Two invariants:

1. every method of every ServiceSpec has an explicit entry in
   rpc.METHOD_POLICIES with a positive deadline;
2. no file outside common/rpc.py constructs a raw channel/server/stub
   (grpc.insecure_channel / grpc.intercept_channel / grpc.server /
   .unary_unary) — any of these would bypass the interceptor stack,
   including the chaos injectors.

Imports common/rpc (grpc + stdlib, no jax) for the policy table; the
textual scan rides the shared file cache.
"""

import os
import re

from tools.edl_lint.core import Finding, Rule

_FORBIDDEN = (
    re.compile(r"grpc\.insecure_channel\s*\("),
    re.compile(r"grpc\.secure_channel\s*\("),
    re.compile(r"grpc\.intercept_channel\s*\("),
    re.compile(r"grpc\.server\s*\("),
    re.compile(r"\.unary_unary\s*\("),
)

_ALLOWED = {
    os.path.join("elasticdl_tpu", "common", "rpc.py"),
}


class RpcDeadlinesRule(Rule):
    name = "rpc-deadlines"
    doc = (
        "Every RPC method needs an explicit deadline policy; no raw "
        "grpc construction outside common/rpc.py."
    )

    def check(self, project):
        from elasticdl_tpu.common import rpc

        for spec in (
            rpc.MASTER_SERVICE,
            rpc.PSERVER_SERVICE,
            rpc.COLLECTIVE_SERVICE,
        ):
            for method in spec.methods:
                policy = rpc.METHOD_POLICIES.get(method)
                if policy is None:
                    yield Finding(
                        self.name,
                        os.path.join("elasticdl_tpu", "common", "rpc.py"),
                        1,
                        f"{spec.name}/{method}: no entry in "
                        f"rpc.METHOD_POLICIES (every method needs an "
                        f"explicit deadline default)",
                        key=f"no-policy:{spec.name}/{method}",
                    )
                elif policy.deadline <= 0:
                    yield Finding(
                        self.name,
                        os.path.join("elasticdl_tpu", "common", "rpc.py"),
                        1,
                        f"{spec.name}/{method}: non-positive deadline "
                        f"{policy.deadline!r}",
                        key=f"bad-deadline:{spec.name}/{method}",
                    )

        for sf in project.iter_files():
            if sf.rel in _ALLOWED:
                continue
            for lineno, line in enumerate(sf.lines, 1):
                if line.strip().startswith("#"):
                    continue
                for pattern in _FORBIDDEN:
                    if pattern.search(line):
                        yield Finding(
                            self.name,
                            sf.rel,
                            lineno,
                            f"raw grpc construction "
                            f"({pattern.pattern}) bypasses the rpc "
                            f"deadline/retry plane — go through "
                            f"common/rpc.build_channel or rpc.serve",
                            key=f"raw-grpc:{pattern.pattern}",
                        )
