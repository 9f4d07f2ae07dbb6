"""The repository's shape: every lane runs a module that exists, every
document sends its reader to something that exists, every declared knob
has a reader. What a deletion can break without any other test noticing.
No jax, no processes: text, `ast` and `importlib.util.find_spec`."""

import glob
import importlib.util
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from elasticdl_tpu.common import knobs  # noqa: E402
from tools.edl_lint.loader import Project  # noqa: E402
from tools.edl_lint.rules.env_knobs import EnvKnobsRule  # noqa: E402


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _runs_with_dash_m(module):
    """What `python -m <module>` needs: the module, or a package's
    `__main__`."""
    try:
        spec = importlib.util.find_spec(module)
        if spec is not None and spec.submodule_search_locations is not None:
            spec = importlib.util.find_spec(module + ".__main__")
    except ModuleNotFoundError:
        return False
    return spec is not None


# ---------------------------------------------------------------------------
# the Makefile's lanes
# ---------------------------------------------------------------------------


def _make_targets():
    """{target: (prerequisites, recipe text)} of the Makefile."""
    targets, current = {}, None
    for line in _read("Makefile").splitlines():
        head = re.match(r"^([A-Za-z][\w-]*):(?!=)(.*)$", line)
        if head:
            current = head.group(1)
            targets[current] = (head.group(2).split(), [])
        elif line.startswith("\t") and current is not None:
            targets[current][1].append(line)
        elif line.strip() and not line.startswith("#"):
            current = None
    return {t: (pre, "\n".join(rec)) for t, (pre, rec) in targets.items()}


MAKE_TARGETS = _make_targets()


@pytest.mark.parametrize("target", sorted(MAKE_TARGETS))
def test_a_make_target_runs_what_exists(target):
    prerequisites, recipe = MAKE_TARGETS[target]
    sub_makes = re.findall(r"\$\(MAKE\)(?:\s+--?[\w-]+)*\s+([A-Za-z][\w-]*)",
                           recipe)
    for name in prerequisites + sub_makes:
        assert name in MAKE_TARGETS, f"`{target}` runs no target `{name}`"
    for module in re.findall(r"python3?\s+-m\s+([\w.]+)", recipe):
        assert _runs_with_dash_m(module), (
            f"`{target}` runs `python -m {module}`, which is not there")
    for path in re.findall(r"\b((?:tools|tests)/[\w/.-]*\.py)\b", recipe):
        assert os.path.exists(os.path.join(REPO, path)), (
            f"`{target}` names {path}, which is not there")


# ---------------------------------------------------------------------------
# the documents
# ---------------------------------------------------------------------------

DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs",
                                                             "*.md")))
_PATH_ROOTS = ("elasticdl_tpu/", "tools/", "tests/", "benchmark/", "docs/")
DECLARED = sorted(k.name for k in knobs.all_knobs())


def _code_of(text):
    """The text a document sets as code: fenced blocks and inline spans."""
    fenced = re.findall(r"^```.*?^```", text, flags=re.S | re.M)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"^```.*?^```", "", text,
                                               flags=re.S | re.M))
    return fenced, inline


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_what_exists(document):
    text = _read(document)
    fenced, inline = _code_of(text)
    wrong = []
    for code in fenced + inline:
        # `make` where a command starts, not the verb of a comment.
        for name in re.findall(r"(?:^|[;&|(]|\$)\s*make\s+([a-z][\w-]*)",
                               code, flags=re.M):
            if name not in MAKE_TARGETS:
                wrong.append(f"`make {name}`: no such target")
    for module in re.findall(
            r"python3?\s+-m\s+((?:elasticdl_tpu|tools)[\w.]*\w)", text):
        if not _runs_with_dash_m(module):
            wrong.append(f"`python -m {module}`: no such module")
    for name, star in re.findall(r"\b(ELASTICDL_[A-Z0-9_]+)(\*?)", text):
        if star or name.endswith("_"):  # a family: `ELASTICDL_POLICY_*`
            if not any(k.startswith(name) for k in DECLARED):
                wrong.append(f"{name}*: no declared knob begins so")
        elif name not in DECLARED:
            wrong.append(f"{name}: not declared in common/knobs.py")
    for span in inline:
        path = span.split()[0]
        if not path.startswith(_PATH_ROOTS):
            continue
        path = re.sub(r"[:#][\w,:–-]*$", "", path).rstrip(".,;")
        if not glob.glob(os.path.join(REPO, path)):
            wrong.append(f"`{span}`: no such path")
    if wrong:
        pytest.fail(f"{document} names what is not there:\n"
                    + "\n".join(sorted(set(wrong))), pytrace=False)


# ---------------------------------------------------------------------------
# the knobs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def read_names():
    return EnvKnobsRule().read_names(Project.load(REPO))


@pytest.mark.parametrize("knob", DECLARED)
def test_a_declared_knob_has_a_reader(knob, read_names):
    assert knob in read_names, (
        f"{knob} is declared in common/knobs.py and no module under "
        f"elasticdl_tpu/ or tools/ hands it to a knobs accessor")
