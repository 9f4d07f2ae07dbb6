"""wire-codec: tensor byte codecs live in common/tensor_utils.py only.

The zero-copy transport PR moved every tensor-bytes encode/decode —
`content=arr.tobytes()` proto assembly, `np.frombuffer` views over
received payloads, the int8 block-scaled codec, packed span
offsets — into common/tensor_utils.py, which owns both sides of the
wire format. A raw `.tobytes()` / `frombuffer()` in any other module
that touches the proto surface is how copy-per-tensor serialization
silently comes back: someone builds
one more message by hand instead of packing a span. This rule flags
every such call in modules that import the generated proto module;
modules that never touch protos (binary file readers like
data/gen/mnist_idx.py) are out of scope — their bytes never ride the
wire.
"""

import ast
import os

from tools.edl_lint.core import Finding, Rule

# The one module allowed to speak raw bytes on the proto surface.
_CODEC_HOME = os.path.join("elasticdl_tpu", "common", "tensor_utils.py")

_PB_MARKER = "_pb2"


def _imports_proto(minfo):
    return any(_PB_MARKER in target for target in minfo.imports.values())


class WireCodecRule(Rule):
    name = "wire-codec"
    doc = (
        "modules that import the generated proto module must route "
        "tensor bytes through common/tensor_utils.py (pack/unpack "
        "spans, ids_to_bytes/ids_from_bytes) — raw .tobytes()/"
        "frombuffer() there reintroduces copy-per-tensor serialization."
    )

    def check(self, project):
        resolver = project.resolver
        for sf in project.iter_files():
            if not sf.rel.startswith("elasticdl_tpu" + os.sep):
                continue
            if sf.rel == _CODEC_HOME:
                continue
            minfo = resolver.module(sf.rel)
            if not _imports_proto(minfo):
                continue
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Attribute):
                    tail = func.attr
                elif isinstance(func, ast.Name):
                    # `from numpy import frombuffer` style bare calls.
                    tail = minfo.imports.get(
                        func.id, func.id
                    ).rsplit(".", 1)[-1]
                else:
                    continue
                if tail == "tobytes":
                    yield Finding(
                        self.name, sf.rel, node.lineno,
                        "raw .tobytes() in a proto-facing module — "
                        "assemble tensor bytes through "
                        "common/tensor_utils.py (pack_tensor_span / "
                        "ids_to_bytes) so the wire stays zero-copy and "
                        "single-format",
                        key="tobytes",
                        fix_hint="use tensor_utils.pack_tensor_span / "
                        "ids_to_bytes",
                    )
                elif tail == "frombuffer":
                    yield Finding(
                        self.name, sf.rel, node.lineno,
                        "raw frombuffer() in a proto-facing module — "
                        "decode received tensor bytes through "
                        "common/tensor_utils.py (unpack_tensor_span / "
                        "ids_from_bytes) so range checks and dtype "
                        "views stay in one place",
                        key="frombuffer",
                        fix_hint="use tensor_utils.unpack_tensor_span / "
                        "ids_from_bytes",
                    )
