"""The unified static-analysis plane (tools/edl_lint).

Per-rule positive + negative fixtures on synthetic project trees, the
inline-suppression and baseline workflows, the knob registry, and the
acceptance invariant that the whole lint lane runs clean on THIS repo
without ever importing jax. Everything here is AST-level — no jax, no
processes beyond one subprocess for the no-jax proof — so the file
lands comfortably inside the tier-1 window."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.edl_lint import core  # noqa: E402
from tools.edl_lint.loader import Project  # noqa: E402
from tools.edl_lint.rules import (  # noqa: E402
    ALL_RULES,
    rule_by_name,
)
from tools.edl_lint.rules.proto_drift import parse_proto  # noqa: E402

from elasticdl_tpu.common import knobs  # noqa: E402


# ---------------------------------------------------------------------------
# fixture-project helpers
# ---------------------------------------------------------------------------


def make_project(tmp_path, files):
    """A Project over {relpath: source} written under tmp_path."""
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return Project.load(str(tmp_path))


def run_rule(project, name):
    """Rule findings with inline suppressions applied (what the CLI
    reports before baselining)."""
    out = []
    for f in rule_by_name(name)().check(project):
        sf = project.files.get(f.path)
        if sf is not None and core.is_suppressed(f, sf.suppressions):
            continue
        out.append(f)
    return out


def keys(findings):
    return {f.key for f in findings}


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------

_RACY_CLASS = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0  # init writes never count as unguarded

        def bump(self):
            with self._lock:
                self._n += 1

        def reset(self):
            self._n = 0  # unguarded write -> finding
"""


def test_concurrency_flags_mixed_guard_writes(tmp_path):
    project = make_project(
        tmp_path, {"elasticdl_tpu/master/racy.py": _RACY_CLASS}
    )
    found = run_rule(project, "concurrency")
    assert "guard:Counter._n" in keys(found), found


def test_concurrency_negative_and_locked_convention(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/master/clean.py": """
            import threading

            class Clean:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    with self._lock:
                        self._bump_locked()

                def _bump_locked(self):
                    # *_locked suffix: analyzed as called under the lock.
                    self._n += 1
            """
        },
    )
    assert run_rule(project, "concurrency") == []


def test_concurrency_lock_ordering_cycle(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/master/pair.py": """
            import threading

            class Alpha:
                def __init__(self, beta):
                    self._lock = threading.Lock()
                    self._beta = beta

                def poke(self):
                    with self._lock:
                        self._beta.poke()

            class Beta:
                def __init__(self, alpha):
                    self._lock = threading.Lock()
                    self._alpha = alpha

                def poke(self):
                    with self._lock:
                        self._alpha.poke()
            """
        },
    )
    found = run_rule(project, "concurrency")
    assert any(k.startswith("cycle:") for k in keys(found)), found


def test_concurrency_cycle_through_mutual_recursion(tmp_path):
    """Regression: transitive lock acquisition is a whole-graph fixpoint,
    not a memoized DFS — a DFS cycle cutoff would cache a truncated set
    for the mutually-recursive pair and miss the edge from Outer."""
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/master/recur.py": """
            import threading

            class Ping:
                def __init__(self, pong):
                    self._lock = threading.Lock()
                    self._pong = pong

                def f(self):
                    with self._lock:
                        self._pong.g()

            class Relay:
                def __init__(self, ping):
                    self._lock = threading.Lock()  # owned, never held
                    self._ping = ping

                def pass_through(self):
                    # No direct acquisition: the Pong->Ping leg exists
                    # only if transitive sets propagate through this
                    # method — the case a truncated DFS cache loses.
                    self._ping.f()

            class Pong:
                def __init__(self, relay):
                    self._lock = threading.Lock()
                    self._relay = relay

                def g(self):
                    with self._lock:
                        self._relay.pass_through()
            """
        },
    )
    found = run_rule(project, "concurrency")
    cycle_keys = [k for k in keys(found) if k.startswith("cycle:")]
    # Ping._lock -> (g) Pong._lock and Pong._lock -> (pass_through -> f)
    # Ping._lock: a 2-cycle whose second edge is purely transitive,
    # through the recursion Ping.f -> Pong.g -> Relay -> Ping.f.
    assert any("Ping._lock" in k and "Pong._lock" in k
               for k in cycle_keys), found


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

_IMPURE_JIT = """
    import time
    import jax
    import numpy as np

    acc = []

    class Trainer:
        def _step(self, x):
            self.calls = 1
            time.time()
            acc.append(x)
            y = np.asarray(x)
            return float(x) + y

        def build(self):
            return jax.jit(self._step)
"""


def test_jit_purity_positive(tmp_path):
    project = make_project(
        tmp_path, {"elasticdl_tpu/worker/impure.py": _IMPURE_JIT}
    )
    got = keys(run_rule(project, "jit-purity"))
    assert "_step:self.calls" in got
    assert "_step:time:time.time" in got
    assert "_step:closure:acc" in got
    assert "_step:sync:numpy.asarray" in got
    assert "_step:cast:float" in got


def test_jit_purity_negative_and_debug_exemption(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/pure.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            _MASK = np.arange(8)  # module constant: asarray on it is fine

            def step(params, batch):
                jax.debug.print("loss {x}", x=batch)
                mask = np.asarray(_MASK)
                return jnp.dot(params, batch) * mask.sum()

            compiled = jax.jit(step)
            """
        },
    )
    assert run_rule(project, "jit-purity") == []


def test_jit_purity_unhashable_static_args(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/parallel/static_args.py": """
            import jax

            def f(a, shape):
                return a.reshape(shape)

            g = jax.jit(f, static_argnums=(1,))
            out = g(x, [2, 3])
            """
        },
    )
    got = keys(run_rule(project, "jit-purity"))
    assert "g:staticcall:1" in got


# ---------------------------------------------------------------------------
# env-knobs
# ---------------------------------------------------------------------------


def test_env_knobs_flags_raw_reads_and_undeclared(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/knobby.py": """
            import os

            from elasticdl_tpu.common import knobs

            OBS = "ELASTICDL_OBS_DIR"

            a = os.environ.get("ELASTICDL_OBS_DIR", "")
            b = os.environ[OBS]
            c = os.getenv("ELASTICDL_ROLE")
            d = os.environ.get("HOME")  # non-ELASTICDL: ignored
            e = knobs.get_str("ELASTICDL_NOT_A_KNOB")
            f = knobs.get_str("ELASTICDL_ROLE")  # declared: fine
            os.environ["ELASTICDL_ROLE"] = "x"  # write: fine
            """
        },
    )
    got = keys(run_rule(project, "env-knobs"))
    assert "raw-read:ELASTICDL_OBS_DIR" in got
    assert "raw-read:ELASTICDL_ROLE" in got
    assert "undeclared:ELASTICDL_NOT_A_KNOB" in got
    # The write and the non-ELASTICDL read produced nothing.
    assert not any(k.startswith("raw-read:HOME") for k in got)


def test_env_knobs_negative(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/clean_knobs.py": """
            from elasticdl_tpu.common import knobs

            patience = knobs.get_float("ELASTICDL_MASTER_PATIENCE_SECONDS")
            """
        },
    )
    got = keys(run_rule(project, "env-knobs"))
    # Fixture tree has no registry/docs; only those structural findings
    # may appear — no read violations.
    assert got <= {"no-registry", "stale-docs"}, got


_FIXTURE_REGISTRY = """
    def declare(name, kind, default, doc):
        pass

    declare("ELASTICDL_ROLE", "str", "", "read below, through a constant")
    declare("ELASTICDL_OBS_DIR", "str", "", "read by a tool")
    declare("ELASTICDL_MFU", "int", 1, "its last reader went")
    declare("ELASTICDL_CHAOS", "str", "", "spelt beside a computed key")
"""


def test_env_knobs_flags_a_declared_knob_nothing_reads(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/knobs.py": _FIXTURE_REGISTRY,
            "elasticdl_tpu/worker/reader.py": """
            from elasticdl_tpu.common import knobs

            ROLE_ENV = "ELASTICDL_ROLE"
            role = knobs.get_str(ROLE_ENV)
            mfu = "ELASTICDL_MFU"  # spelt, handed to no accessor
            """,
            "tools/a_tool.py": """
            from elasticdl_tpu.common import knobs

            where = knobs.raw("ELASTICDL_OBS_DIR")
            """,
            "elasticdl_tpu/worker/looper.py": """
            from elasticdl_tpu.common import knobs

            for env in ("ELASTICDL_CHAOS",):
                knobs.raw(env)
            """,
        },
    )
    got = {k for k in keys(run_rule(project, "env-knobs"))
           if k.startswith("unread:")}
    assert got == {"unread:ELASTICDL_MFU"}, got


def test_env_knobs_a_read_knob_is_not_unread(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/knobs.py": _FIXTURE_REGISTRY,
            "elasticdl_tpu/worker/reader.py": """
            from elasticdl_tpu.common import knobs
            from elasticdl_tpu.worker.names import CHAOS_ENV

            a = knobs.get_str("ELASTICDL_ROLE")
            b = knobs.is_set("ELASTICDL_OBS_DIR")
            c = knobs.get_int("ELASTICDL_MFU")
            d = knobs.raw(CHAOS_ENV)
            """,
            "elasticdl_tpu/worker/names.py": """
            CHAOS_ENV = "ELASTICDL_CHAOS"
            """,
        },
    )
    got = keys(run_rule(project, "env-knobs"))
    assert not any(k.startswith("unread:") for k in got), got


def test_knob_registry_semantics(monkeypatch):
    with pytest.raises(ValueError):
        knobs.declare("ELASTICDL_ROLE", "int", 3, "conflicting re-decl")
    with pytest.raises(KeyError):
        knobs.get_str("ELASTICDL_NEVER_DECLARED")
    monkeypatch.setenv("ELASTICDL_METRICS_PORT", "91")
    assert knobs.get_int("ELASTICDL_METRICS_PORT") == 91
    monkeypatch.setenv("ELASTICDL_METRICS_PORT", "not-a-number")
    assert knobs.get_int("ELASTICDL_METRICS_PORT") == 0  # default
    monkeypatch.delenv("ELASTICDL_METRICS_PORT")
    assert knobs.get_int("ELASTICDL_METRICS_PORT") == 0
    # The generated docs table carries every declared knob.
    table = knobs.docs_table()
    for knob in knobs.all_knobs():
        assert knob.name in table


# ---------------------------------------------------------------------------
# proto-drift
# ---------------------------------------------------------------------------

_PROTO_SRC = """
    syntax = "proto3";
    package demo;

    message Thing {
      reserved 3, 10 to 12;
      reserved "legacy";
      int32 id = 1;
      repeated string names = 2;
      map<string, int64> counts = 4;
    }

    enum Kind {
      A = 0;
      B = 1;
    }
"""


def test_proto_parser_reads_fields_reserved_and_enums():
    messages, enums = parse_proto(textwrap.dedent(_PROTO_SRC))
    thing = messages["Thing"]
    assert thing.fields == {
        "id": (1, False),
        "names": (2, True),
        "counts": (4, True),  # map<> implies repeated
    }
    assert thing.reserved_numbers == {3, 10, 11, 12}
    assert thing.reserved_names == {"legacy"}
    assert enums["Kind"] == {"A": 0, "B": 1}


def _write_pb2(tmp_path, fdp):
    rel = "elasticdl_tpu/proto/elasticdl_tpu_pb2.py"
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(
        "DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(\n"
        f"    {fdp.SerializeToString()!r}\n)\n"
    )


def _demo_fdp(number=1):
    from google.protobuf import descriptor_pb2

    fdp = descriptor_pb2.FileDescriptorProto(name="demo.proto")
    msg = fdp.message_type.add(name="Thing")
    msg.field.add(name="id", number=number, label=1, type=5)
    return fdp


def test_proto_drift_positive_and_negative(tmp_path):
    proto = """
        syntax = "proto3";
        message Thing {
          int32 id = 1;
        }
    """
    (tmp_path / "elasticdl_tpu/proto").mkdir(parents=True)
    (tmp_path / "elasticdl_tpu/proto/elasticdl_tpu.proto").write_text(
        textwrap.dedent(proto)
    )
    _write_pb2(tmp_path, _demo_fdp(number=1))
    project = Project.load(str(tmp_path))
    assert run_rule(project, "proto-drift") == []

    _write_pb2(tmp_path, _demo_fdp(number=7))  # field number drift
    project = Project.load(str(tmp_path))
    got = keys(run_rule(project, "proto-drift"))
    assert "number-drift:Thing.id" in got


def test_proto_drift_real_pb2_matches_real_proto():
    project = Project.load(REPO)
    assert run_rule(project, "proto-drift") == []


# ---------------------------------------------------------------------------
# wire-codec
# ---------------------------------------------------------------------------


def test_wire_codec_flags_raw_bytes_in_proto_facing_modules(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/ps/sneaky.py": """
            import numpy as np
            from numpy import frombuffer

            from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

            def encode(arr):
                return pb.Tensor(content=arr.tobytes())

            def decode(request):
                a = np.frombuffer(request.content, dtype=np.float32)
                b = frombuffer(request.ids_bytes, dtype=np.int64)
                return a, b
            """,
        },
    )
    got = keys(run_rule(project, "wire-codec"))
    assert got == {"tobytes", "frombuffer"}
    # Both frombuffer spellings (np.frombuffer + the bare import) flag.
    lines = [
        f.line
        for f in run_rule(project, "wire-codec")
        if f.key == "frombuffer"
    ]
    assert len(lines) == 2, lines


def test_wire_codec_exempts_codec_home_and_non_proto_modules(tmp_path):
    project = make_project(
        tmp_path,
        {
            # The codec home itself is the ONE sanctioned location.
            "elasticdl_tpu/common/tensor_utils.py": """
            import numpy as np

            from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

            def ids_to_bytes(ids):
                return np.ascontiguousarray(ids).tobytes()

            def ids_from_bytes(buf):
                return np.frombuffer(buf, dtype=np.int64)
            """,
            # Binary file IO far from the proto surface stays legal.
            "elasticdl_tpu/data/gen/reader.py": """
            import numpy as np

            def load(raw):
                return np.frombuffer(raw, dtype=np.uint8)
            """,
            # Proto-facing code that routes through tensor_utils: clean.
            "elasticdl_tpu/worker/fine.py": """
            from elasticdl_tpu.common import tensor_utils
            from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

            def encode(ids):
                return pb.PullEmbeddingVectorsRequest(
                    ids_bytes=tensor_utils.ids_to_bytes(ids)
                )
            """,
        },
    )
    assert run_rule(project, "wire-codec") == []


def test_wire_codec_suppression(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/master/special.py": """
            import numpy as np

            from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

            def checksum(arr):
                # edl-lint: disable=wire-codec
                return hash(arr.tobytes())
            """,
        },
    )
    assert run_rule(project, "wire-codec") == []


def test_wire_codec_real_tree_clean():
    project = Project.load(REPO)
    assert run_rule(project, "wire-codec") == []


# ---------------------------------------------------------------------------
# rpc-deadlines / metric-names (ported rules)
# ---------------------------------------------------------------------------


def test_rpc_deadlines_flags_raw_grpc(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/sneaky.py": """
            import grpc

            channel = grpc.insecure_channel("localhost:1")
            """,
            "elasticdl_tpu/worker/fine.py": """
            from elasticdl_tpu.common import rpc

            channel = rpc.build_channel("localhost:1")
            """,
        },
    )
    found = run_rule(project, "rpc-deadlines")
    raw = [f for f in found if f.path.endswith("sneaky.py")]
    assert raw, found
    assert not [f for f in found if f.path.endswith("fine.py")]


def test_metric_names_positive_and_negative(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/observability/bad_metrics.py": """
            from elasticdl_tpu.observability.metrics import default_registry

            _REG = default_registry()
            A = _REG.counter("bad_name", "no prefix")
            B = _REG.counter("edl_things", "no _total suffix")
            C = _REG.gauge("edl_height", "fine")
            D = _REG.counter("edl_height", "kind conflict")
            """
        },
    )
    got = keys(run_rule(project, "metric-names"))
    assert "prefix:bad_name" in got
    assert "suffix:edl_things" in got
    assert "conflict:edl_height" in got
    assert not any(k.endswith("edl_height_ok") for k in got)


# ---------------------------------------------------------------------------
# dead-code
# ---------------------------------------------------------------------------


def test_dead_code_positive_and_negative(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/junk.py": """
            import json
            import math  # unused -> finding

            def used_helper():
                return json.dumps({})

            def orphan():
                return 1
            """,
            "elasticdl_tpu/common/caller.py": """
            from elasticdl_tpu.common.junk import used_helper

            def run():
                return used_helper()
            """,
            "elasticdl_tpu/common/__init__.py": """
            import math  # __init__ re-exports are exempt
            """,
        },
    )
    got = keys(run_rule(project, "dead-code"))
    assert "unused-import:math" in got
    assert "dead:orphan" in got
    assert "dead:used_helper" not in got
    assert "dead:run" in got  # nothing calls run() in the fixture tree


def test_dead_code_counts_aliased_imports_as_usage(tmp_path):
    """Regression: `from m import f as _f` references f without a Name
    node; the usage index must still count it or aliased re-imports read
    as dead symbols."""
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/provider.py": """
            def get_thing(tree):
                return tree
            """,
            "elasticdl_tpu/common/consumer.py": """
            from elasticdl_tpu.common.provider import get_thing as _gt

            def use():
                return _gt({})
            """,
            "elasticdl_tpu/common/use2.py": """
            from elasticdl_tpu.common.consumer import use

            x = use()
            """,
        },
    )
    got = keys(run_rule(project, "dead-code"))
    assert "dead:get_thing" not in got


# ---------------------------------------------------------------------------
# suppressions + baseline round-trip
# ---------------------------------------------------------------------------


def test_inline_suppression_same_line_and_preceding_line(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/sup.py": """
            import json  # edl-lint: disable=dead-code
            # edl-lint: disable=dead-code
            import math

            def live():
                return 0
            """,
            "elasticdl_tpu/common/use.py": """
            from elasticdl_tpu.common.sup import live

            x = live()
            """,
        },
    )
    got = keys(run_rule(project, "dead-code"))
    assert "unused-import:json" not in got
    assert "unused-import:math" not in got


def test_suppression_is_rule_scoped(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/common/scoped.py": """
            import json  # edl-lint: disable=jit-purity
            """
        },
    )
    # Wrong rule name in the comment: the dead-code finding survives.
    got = keys(run_rule(project, "dead-code"))
    assert "unused-import:json" in got


def test_baseline_round_trip(tmp_path):
    findings = [
        core.Finding("dead-code", "a/b.py", 3, "msg one", key="dead:f"),
        core.Finding("concurrency", "c.py", 9, "msg two", key="guard:X.y"),
    ]
    path = tmp_path / "baseline.txt"
    written = core.write_baseline(str(path), findings)
    assert written == sorted(f.baseline_key for f in findings)
    loaded = core.load_baseline(str(path))
    assert loaded == set(written)
    # Keys are line-free: re-linting after unrelated edits still matches.
    moved = core.Finding("dead-code", "a/b.py", 77, "msg one", key="dead:f")
    assert moved.baseline_key in loaded
    # Missing baseline file = empty set, not an error.
    assert core.load_baseline(str(tmp_path / "nope.txt")) == set()


# ---------------------------------------------------------------------------
# acceptance: the real repo lints clean, fast, without jax
# ---------------------------------------------------------------------------


def test_repo_lints_clean_without_importing_jax():
    """`python -m tools.edl_lint` on THIS repo: exit 0, all rule families
    run, never imports jax (the whole point of an AST plane — `make
    lint` works on boxes with no accelerator stack warm-up)."""
    check = (
        "import sys, json\n"
        "from tools.edl_lint.cli import run\n"
        "rc = run(['--json'])\n"
        "assert 'jax' not in sys.modules, 'lint imported jax'\n"
        "sys.exit(rc)\n"
    )
    env = dict(os.environ)
    env.pop("ELASTICDL_CHAOS", None)
    load_before = os.getloadavg()[0]
    proc = subprocess.run(
        [sys.executable, "-c", check],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["stale_baseline"] == []
    assert set(payload["rules"]) == {cls.name for cls in ALL_RULES}
    # The lint lane's timing budget: the WHOLE 12-rule pass, dataflow
    # engine included, in under 10 s (it runs before every test lane).
    # Only enforced when the box isn't already saturated — a loaded
    # 1-core host stretches wall time severalfold with no regression
    # (the flake class the ROADMAP says not to chase).
    # "Saturated" is relative to the cores this process may use: pinned
    # to two, a load of 3 already doubles every wall time.
    if load_before < 0.5 * len(os.sched_getaffinity(0)):
        assert payload["seconds"] < 10, payload["seconds"]
    # Per-rule timings ride the payload (surfaced by `make ci`).
    assert set(payload["rule_seconds"]) == set(payload["rules"])


def test_cli_list_rules_covers_all_families(capsys):
    from tools.edl_lint.cli import run

    assert run(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for cls in ALL_RULES:
        assert cls.name in out


# ---------------------------------------------------------------------------
# compile-tracker
# ---------------------------------------------------------------------------


def test_compile_tracker_flags_direct_jit_in_trainer_paths(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/untracked.py": """
            import jax
            from jax.experimental.pjit import pjit

            def build(step):
                a = jax.jit(step)
                b = pjit(step)
                return a, b
            """,
        },
    )
    got = keys(run_rule(project, "compile-tracker"))
    assert "direct-jit:jax.jit" in got
    assert any(k.endswith("pjit") for k in got), got


def test_compile_tracker_allows_tracked_and_out_of_scope(tmp_path):
    project = make_project(
        tmp_path,
        {
            # tracked_jit is the sanctioned entrypoint; shard_map is not
            # a compile boundary on its own.
            "elasticdl_tpu/worker/tracked.py": """
            from elasticdl_tpu.observability.profiling import tracked_jit
            from jax import shard_map

            def build(step, mesh):
                inner = shard_map(step, mesh=mesh)
                return tracked_jit(inner, name="step")
            """,
            # observability/ itself (and anywhere outside worker/
            # parallel/ps) may jit directly — mfu's AOT analysis, tests.
            "elasticdl_tpu/observability/free.py": """
            import jax

            analyze = jax.jit(lambda x: x)
            """,
        },
    )
    assert run_rule(project, "compile-tracker") == []


def test_compile_tracker_suppression(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/ps/special.py": """
            import jax

            def build(step):
                # edl-lint: disable=compile-tracker
                return jax.jit(step)
            """,
        },
    )
    assert run_rule(project, "compile-tracker") == []


def test_jit_purity_covers_tracked_jit(tmp_path):
    """Moving trainers to tracked_jit must not remove them from the
    purity analysis — the wrapped function is traced all the same."""
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/tracked_impure.py": """
            import time
            from elasticdl_tpu.observability.profiling import tracked_jit

            class T:
                def _step(self, x):
                    time.time()
                    return x

                def build(self):
                    return tracked_jit(self._step, name="step")
            """,
        },
    )
    assert "_step:time:time.time" in keys(
        run_rule(project, "jit-purity")
    )


# ---------------------------------------------------------------------------
# donation (dataflow engine: jit-binding index + call-site flow)
# ---------------------------------------------------------------------------

_DONATION_TRAINER = """
    from elasticdl_tpu.observability.profiling import tracked_jit

    class T:
        def _build_step(self):
            def step(variables, opt_state, batch):
                return variables, opt_state, 0.0

            return tracked_jit(step, name="step", key_argnums=(2,)%s)

        def setup(self):
            self._step = self._build_step()

        def train(self, batch):
            self._variables, self._opt_state, loss = self._step(
                self._variables, self._opt_state, batch
            )
            return loss
"""


def test_donation_flags_state_consuming_step_without_donate(tmp_path):
    project = make_project(
        tmp_path,
        {"elasticdl_tpu/worker/t.py": _DONATION_TRAINER % ""},
    )
    assert "missing-donation:step" in keys(run_rule(project, "donation"))


def test_donation_negative_when_donated_or_not_replaced(tmp_path):
    project = make_project(
        tmp_path,
        {
            # Donated: clean.
            "elasticdl_tpu/worker/t.py": _DONATION_TRAINER
            % ", donate_argnums=(0, 1)",
            # Forward pattern: state flows in but is NOT replaced, so no
            # donation is demanded (the buffers must stay alive).
            "elasticdl_tpu/worker/fwd.py": """
            from elasticdl_tpu.observability.profiling import tracked_jit

            class F:
                def _build(self):
                    def forward(variables, batch):
                        return batch

                    return tracked_jit(forward, name="forward")

                def setup(self):
                    self._fwd = self._build()

                def evaluate(self, batch):
                    out = self._fwd(self._variables, batch)
                    return out
            """,
        },
    )
    assert run_rule(project, "donation") == []


def test_donation_use_after_donate(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/u.py": """
            from elasticdl_tpu.observability.profiling import tracked_jit

            class U:
                def _build(self):
                    def apply(params, grads):
                        return params

                    return tracked_jit(
                        apply, name="apply", donate_argnums=(0,)
                    )

                def setup(self):
                    self._apply = self._build()

                def train(self, grads):
                    params = self.make()
                    new_params = self._apply(params, grads)
                    self._params = new_params
                    return params
            """
        },
    )
    assert "use-after-donate:apply:params" in keys(
        run_rule(project, "donation")
    )


def test_donation_suppression_and_baseline_round_trip(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/t.py": (_DONATION_TRAINER % "").replace(
                "            return tracked_jit(",
                "            # edl-lint: disable=donation\n"
                "            return tracked_jit(",
            )
        },
    )
    assert run_rule(project, "donation") == []
    # Baseline keys are line-free and survive reload.
    finding = core.Finding(
        "donation", "elasticdl_tpu/worker/t.py", 9, "msg",
        key="missing-donation:step",
    )
    path = tmp_path / "b.txt"
    core.write_baseline(str(path), [finding])
    assert finding.baseline_key in core.load_baseline(str(path))


# ---------------------------------------------------------------------------
# hot-path-sync (dataflow engine: interprocedural device-value taint)
# ---------------------------------------------------------------------------

_SYNC_TRAINER = """
    import jax
    import numpy as np

    from elasticdl_tpu.observability.profiling import tracked_jit

    class Trainer:
        def _build(self):
            def step(params, batch):
                return params, 0.0

            return tracked_jit(step, name="step")

        def setup(self):
            self._step = self._build()

        def _log(self, loss):
            return float(loss)

        def train_minibatch(self, features, labels):
            self._params, loss = self._step(self._params, features)
            v = np.asarray(loss)
            self._log(loss)
            return v
"""


def test_hot_path_sync_flags_syncs_interprocedurally(tmp_path):
    project = make_project(
        tmp_path, {"elasticdl_tpu/worker/s.py": _SYNC_TRAINER}
    )
    got = keys(run_rule(project, "hot-path-sync"))
    assert "sync:Trainer.train_minibatch:numpy:loss" in got
    # float() sits in a HELPER the step loop calls — only reachable
    # through the call graph.
    assert "sync:Trainer._log:cast:loss" in got


def test_hot_path_sync_device_get_sanitizes(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/clean.py": """
            import jax
            import numpy as np

            from elasticdl_tpu.observability.profiling import tracked_jit

            class Trainer:
                def _build(self):
                    def step(params, batch):
                        return params, 0.0

                    return tracked_jit(step, name="step")

                def setup(self):
                    self._step = self._build()

                def train_minibatch(self, features, labels):
                    self._params, loss = self._step(
                        self._params, features
                    )
                    host = jax.device_get(loss)
                    # host values are fair game: the transfer already
                    # happened, batched, at a deliberate boundary.
                    np.asarray(features)
                    return float(host)
            """
        },
    )
    assert run_rule(project, "hot-path-sync") == []


def test_hot_path_sync_suppression(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/worker/s.py": _SYNC_TRAINER.replace(
                "            v = np.asarray(loss)",
                "            # edl-lint: disable=hot-path-sync\n"
                "            v = np.asarray(loss)",
            ).replace(
                "            return float(loss)",
                "            return float(loss)"
                "  # edl-lint: disable=hot-path-sync",
            )
        },
    )
    assert run_rule(project, "hot-path-sync") == []


# ---------------------------------------------------------------------------
# blocking-under-lock (lock events + dataflow fixpoint)
# ---------------------------------------------------------------------------

_BLOCKING_TREE = {
    "elasticdl_tpu/master/holder.py": """
    import threading
    import time

    class Holder:
        def __init__(self):
            self._lock = threading.Lock()

        def poke(self):
            with self._lock:
                time.sleep(1.0)

        def fine(self):
            time.sleep(1.0)  # no lock held: legal backoff
    """,
    "elasticdl_tpu/master/transitive.py": """
    import threading

    class Client:
        def __init__(self, stub):
            self._stub = stub

        def fetch(self):
            return self._stub.get_thing(1)

    class Cache:
        def __init__(self, client):
            self._lock = threading.Lock()
            self._client = client

        def refresh(self):
            with self._lock:
                self._client.fetch()
    """,
}


def test_blocking_under_lock_direct_and_transitive(tmp_path):
    project = make_project(tmp_path, _BLOCKING_TREE)
    got = keys(run_rule(project, "blocking-under-lock"))
    assert any(
        k.startswith("block:Holder.poke:_lock") for k in got
    ), got
    # Cache.refresh never blocks ITSELF — the RPC lives two hops away
    # in Client.fetch, reached through the propagated summary.
    assert any(
        k.startswith("block:Cache.refresh:_lock") for k in got
    ), got
    # The un-locked sleep produced nothing.
    assert not any("Holder.fine" in k for k in got)


def test_blocking_under_lock_negative_and_suppression(tmp_path):
    project = make_project(
        tmp_path,
        {
            "elasticdl_tpu/master/clean.py": """
            import queue
            import threading
            import time

            class Clean:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def snapshot_then_wait(self):
                    with self._lock:
                        items = list(self._pending)
                    # Blocking AFTER the lock released: the pattern the
                    # fix hint prescribes.
                    time.sleep(0.1)
                    return self._q.get(), items
            """,
            "elasticdl_tpu/master/sup.py": """
            import threading
            import time

            class Sup:
                def __init__(self):
                    self._lock = threading.Lock()

                def poke(self):
                    with self._lock:
                        # edl-lint: disable=blocking-under-lock
                        time.sleep(0.01)
            """,
        },
    )
    assert run_rule(project, "blocking-under-lock") == []


# ---------------------------------------------------------------------------
# mesh-spec-consistency
# ---------------------------------------------------------------------------

_MESH_TREE_OK = {
    # Constructions live in parallel/mesh.py — the one module the
    # spec-API check exempts (everywhere else a Mesh birth is flagged).
    "elasticdl_tpu/parallel/mesh.py": """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def build(devices):
        return Mesh(devices, axis_names=("data", "model"))

    def spec(axis="data"):
        return P(axis, None)
    """,
}


def test_mesh_spec_clean_tree(tmp_path):
    project = make_project(tmp_path, dict(_MESH_TREE_OK))
    assert run_rule(project, "mesh-spec-consistency") == []


def test_mesh_spec_flags_unknown_axis(tmp_path):
    files = dict(_MESH_TREE_OK)
    files["elasticdl_tpu/parallel/typo.py"] = """
    from jax.sharding import PartitionSpec as P

    def spec():
        return P("data", "modle")
    """
    project = make_project(tmp_path, files)
    assert "unknown-axis:modle" in keys(
        run_rule(project, "mesh-spec-consistency")
    )


def test_mesh_spec_flags_class_level_drift(tmp_path):
    files = dict(_MESH_TREE_OK)
    files["elasticdl_tpu/worker/owner.py"] = """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel.mesh import make_mesh

    class Owner:
        def make(self):
            self._mesh = make_mesh({"data": 8})

        def shard(self):
            # "model" is declared SOMEWHERE (build.py) but not by any
            # mesh this class can construct: the spec can never match
            # the mesh it flows into.
            return NamedSharding(self._mesh, P("model"))
    """
    project = make_project(tmp_path, files)
    assert "axis-drift:Owner:model" in keys(
        run_rule(project, "mesh-spec-consistency")
    )


def test_mesh_spec_incremental_dict_and_suppression(tmp_path):
    files = dict(_MESH_TREE_OK)
    # Incremental axis dict (the old _make_world_mesh idiom, now inside
    # the spec API module itself) declares the axis; and a suppressed
    # typo in a consumer module stays quiet.
    files["elasticdl_tpu/parallel/mesh.py"] = (
        files["elasticdl_tpu/parallel/mesh.py"]
        + """
    def make_mesh(axes=None):
        return Mesh((), axis_names=tuple(axes or {"data": 1}))

    def build_incr(tp):
        axes = {"data": -1}
        if tp > 1:
            axes["seq"] = tp
        return make_mesh(axes)
    """
    )
    files["elasticdl_tpu/worker/incr.py"] = """
    from jax.sharding import PartitionSpec as P

    def spec():
        return P("seq")

    def odd():
        # edl-lint: disable=mesh-spec-consistency
        return P("weird")
    """
    project = make_project(tmp_path, files)
    assert run_rule(project, "mesh-spec-consistency") == []


def test_mesh_spec_flags_construction_outside_spec_api(tmp_path):
    files = dict(_MESH_TREE_OK)
    files["elasticdl_tpu/worker/rogue.py"] = """
    from elasticdl_tpu.parallel.mesh import make_mesh

    def build_my_own():
        return make_mesh({"data": 8})
    """
    project = make_project(tmp_path, files)
    assert "mesh-outside-api:build_my_own" in keys(
        run_rule(project, "mesh-spec-consistency")
    )


def test_mesh_spec_construction_outside_api_suppressible(tmp_path):
    files = dict(_MESH_TREE_OK)
    files["elasticdl_tpu/worker/rogue.py"] = """
    from elasticdl_tpu.parallel.mesh import make_mesh

    def build_my_own():
        # edl-lint: disable=mesh-spec-consistency
        return make_mesh({"data": 8})
    """
    project = make_project(tmp_path, files)
    assert run_rule(project, "mesh-spec-consistency") == []


# ---------------------------------------------------------------------------
# real-defect pins: the speed-arc fixes stay fixed
# ---------------------------------------------------------------------------


def test_real_tree_clean_under_the_dataflow_rules():
    """Each fixed defect re-fires its rule if regressed: donation on
    ps_step/ps_local_apply/allreduce_step, the sync-mode float(loss),
    the per-table D2H in _push_payload, and the MoE 'expert' axis
    drift."""
    project = Project.load(REPO)
    for rule in (
        "donation",
        "hot-path-sync",
        "blocking-under-lock",
        "mesh-spec-consistency",
    ):
        assert run_rule(project, rule) == [], rule


def test_the_sharded_step_is_followed_from_its_builder_to_its_call():
    """`allreduce_step` is constructed in `step_plan.jit_step`, handed up
    by `build_step` inside a tuple and returned by `_sharded_step_for`:
    the engine follows the builders to the one call, so the donation and
    hot-path-sync rules see the step's results as device values."""
    from tools.edl_lint.dataflow import get_engine

    engine = get_engine(Project.load(REPO))
    (site,) = [
        s for s in engine.jit_sites if s.jit_name == "allreduce_step"
    ]
    assert site.rel == "elasticdl_tpu/parallel/step_plan.py"
    assert [caller.key[1] for caller, _ in site.call_sites] == [
        "AllReduceTrainer._run_sharded_step"
    ]


def test_real_defect_pins_source_level():
    """Belt-and-braces pins on the exact fixes (the rules above are the
    behavioral pin; these catch a rule being weakened instead)."""
    ps = open(
        os.path.join(REPO, "elasticdl_tpu/worker/ps_trainer.py")
    ).read()
    assert "donate_argnums=(1, 2)" in ps  # ps_step: state + emb_rows
    assert "donate_argnums=(0, 1)" in ps  # ps_local_apply
    assert "float(loss)" not in ps  # sync path returns the lazy loss
    ar = open(
        os.path.join(REPO, "elasticdl_tpu/parallel/step_plan.py")
    ).read()
    assert "donate_argnums=donate" in ar
    moe = open(os.path.join(REPO, "elasticdl_tpu/layers/moe.py")).read()
    assert 'expert_axis="expert"' not in moe


# ---------------------------------------------------------------------------
# CLI satellites: stale baseline, json schema, analysis cache
# ---------------------------------------------------------------------------


def test_stale_baseline_fails_and_write_baseline_prunes(
    tmp_path, monkeypatch, capsys
):
    from tools.edl_lint import cli

    baseline = tmp_path / "baseline.txt"
    baseline.write_text("dead-code|nowhere.py|dead:ghost\n")
    monkeypatch.setattr(cli, "BASELINE_PATH", str(baseline))
    rc = cli.run(["--changed", "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1  # clean tree, but the ghost entry is stale debt
    assert payload["stale_baseline"] == [
        "dead-code|nowhere.py|dead:ghost"
    ]
    assert cli.run(["--write-baseline"]) == 0
    assert "ghost" not in baseline.read_text()


def test_finding_json_schema_carries_fix_hint():
    f = core.Finding(
        "donation", "a.py", 3, "msg", key="k", fix_hint="do the thing"
    )
    d = f.as_dict()
    assert set(d) == {
        "rule", "path", "line", "message", "key", "fix_hint"
    }
    assert d["fix_hint"] == "do the thing"
    # Default hint is the empty string, never absent.
    assert core.Finding("r", "p", 1, "m").as_dict()["fix_hint"] == ""


def test_lint_changed_reuses_cached_analysis():
    """`make lint-changed` budget: with an unchanged tree the analysis
    products are reloaded from the digest-keyed cache instead of being
    recomputed, keeping the changed-files path under 3 s."""
    env = dict(os.environ)
    env.pop("ELASTICDL_CHAOS", None)
    first = subprocess.run(
        [sys.executable, "-m", "tools.edl_lint", "--format=json"],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env,
    )
    assert first.returncode == 0, first.stdout[-2000:]
    load_before = os.getloadavg()[0]
    second = subprocess.run(
        [sys.executable, "-m", "tools.edl_lint", "--changed",
         "--format=json"],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env,
    )
    assert second.returncode == 0, second.stdout[-2000:]
    payload = json.loads(second.stdout)
    assert payload["cache"] is True
    # Budget enforced only off a saturated box (see the timing note in
    # test_repo_lints_clean_without_importing_jax).
    if load_before < 0.5 * len(os.sched_getaffinity(0)):
        assert payload["seconds"] < 3, payload["seconds"]
