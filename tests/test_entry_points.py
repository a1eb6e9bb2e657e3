"""No entry point names a file that is gone.

The ``Makefile`` and ``.github/workflows/ci.yml`` are how a person and
the CI reach the repository's programs, and nothing else runs them: a
target whose recipe names a deleted script fails only on the day someone
types it. One case per ``Makefile`` target and per ``ci.yml`` step whose
command names something of this repository — a ``.py`` path, a
``python -m`` module, an example by its ``test_example_runs[...]`` id,
another ``make`` target or a prerequisite — and every such name must be
there.
"""

import functools
import re
from pathlib import Path

import pytest
import yaml

REPO = Path(__file__).resolve().parent.parent
_PY_PATH = re.compile(r"(?<![\w\[./-])([\w./-]+\.py)\b")
_EXAMPLE = re.compile(r"test_example_runs\[([\w.-]+\.py)\]")
_MODULE = re.compile(r"(?:\bpython[0-9.]*|\$\(PY\))\s+-m\s+([\w.]+)")
_MAKE = re.compile(r"(?:^|[\s;&|])make\s+([a-z][\w-]*)")
#: An inline program's text is prose and imports, not an entry point.
_INLINE = re.compile(r'''-c\s+"(?:[^"\\]|\\.)*"''', flags=re.S)


@functools.cache
def _makefile():
    """{target: (prerequisites, recipe text)} of the root Makefile."""
    targets, name = {}, None
    for line in (REPO / "Makefile").read_text().splitlines():
        head = re.match(r"^([a-z][\w-]*):(?!=)([^#]*)", line)
        if head:
            name = head[1]
            targets[name] = (head[2].split(), [])
        elif line.startswith("\t") and name:
            targets[name][1].append(line)
        elif not line.strip():
            name = None
    return {t: (pre, "\n".join(body)) for t, (pre, body) in targets.items()}


def _ci_steps():
    """[(id, command)] for every ``run:`` of the workflow."""
    flow = yaml.safe_load((REPO / ".github/workflows/ci.yml").read_text())
    for job, spec in flow["jobs"].items():
        for i, step in enumerate(spec.get("steps", [])):
            if "run" in step:
                yield f"{job}/{step.get('name', i)}", step["run"]


def _names(command: str):
    """What a command names of this repository: (kind, name) pairs."""
    command = _INLINE.sub("", command)
    for path in _PY_PATH.findall(command):
        yield "path", path
    for example in _EXAMPLE.findall(command):
        yield "path", f"examples/{example}"
    for module in _MODULE.findall(command):
        if (REPO / module.split(".")[0]).is_dir():
            yield "module", module
    for target in _MAKE.findall(command):
        yield "target", target


def _cases():
    for target, (pre, recipe) in _makefile().items():
        names = [("target", p) for p in pre] + list(_names(recipe))
        if names:
            yield pytest.param(names, id=f"make-{target}")
    for step, command in _ci_steps():
        names = list(_names(command))
        if names:
            yield pytest.param(
                names, id="ci-" + re.sub(r"[^\w/-]+", "_", step)[:60])


@pytest.mark.parametrize("names", list(_cases()))
def test_what_an_entry_point_names_is_there(names):
    targets = _makefile()
    for kind, name in names:
        if kind == "path":
            assert (REPO / name).is_file(), f"{name}: no such file"
        elif kind == "module":
            base = REPO.joinpath(*name.split("."))
            assert (base.with_suffix(".py").is_file()
                    or (base / "__main__.py").is_file()), \
                f"python -m {name}: no {base}.py and no {base}/__main__.py"
        else:
            assert name in targets, f"make {name}: no such target"


def test_phony_lists_targets_that_exist():
    text = (REPO / "Makefile").read_text().replace("\\\n", " ")
    (phony,) = re.findall(r"^\.PHONY:(.*)$", text, flags=re.M)
    assert set(phony.split()) <= set(_makefile())


def test_the_reader_finds_the_names_it_is_for():
    """The parser above is the test: held to a line of each kind, so a
    regex that stopped matching cannot turn every case into a pass."""
    got = set(_names(
        'JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_x.py -q -m "not slow"\n'
        'python3 -m ratelimiter_tpu.serving --port 0; python tools/lint.py\n'
        '"tests/test_examples.py::test_example_runs[11_mesh_serving.py]"\n'
        "run: make verify"))
    assert got == {("path", "tests/test_x.py"),
                   ("module", "ratelimiter_tpu.serving"),
                   ("path", "tools/lint.py"),
                   ("path", "tests/test_examples.py"),
                   ("path", "examples/11_mesh_serving.py"),
                   ("target", "verify")}
    assert {"test", "lint", "smoke", "verify"} <= set(_makefile())
    assert _makefile()["check"][0] == ["lint", "test"]
    assert any("tools/lint.py" in c for _, c in _ci_steps())
