"""Every per-layer reader against an OLDER program: the driver lays a
PR's benchmark files over the parent's checkout, so a reader written for
spans and counters the parent lacks must answer None there — a number
where it can, never an exception (a traced run that fails refuses the
PR). Sources are cut from a real run of such a program
(data/old_program/README.txt)."""

import ast
import functools
import inspect
import json
import os

import pytest

from chipbench import layers, promtext, runner

DATA = os.path.join(os.path.dirname(__file__), "data", "old_program")
CELLS = ["wide-hashed-sat", "bucket-hashed-sat", "mesh4-hashed-mixed",
         "wide-string-rpc"]
READERS = layers.load()
#: What this PR's readers are named for: none of it is in the fixture.
NEW_FAMILIES = ("rate_limiter_door_stage_seconds_total",
                "rate_limiter_door_dispatches_total")
NEW_STAGES = ("enter", "hash", "prep", "place", "step", "finish", "leave")


def _read(name: str) -> str:
    with open(os.path.join(DATA, name)) as fh:
        return fh.read()


def _scaled(samples: dict, factor: float) -> dict:
    return {key: value * factor for key, value in samples.items()}


END = promtext.parse(_read("metrics_end.txt"))
#: The window's first scrape: a server that had served half as much, one
#: that had served nothing, and one that served nothing IN the window
#: (every delta zero: the division a reader must not make).
STARTS = {"half": _scaled(END, 0.5), "empty": {}, "same": dict(END)}


with open(os.path.join(runner.ROOT, "chipbench", "peaks.json")) as _fh:
    PEAKS = json.load(_fh)["TPU v5 lite"]
_cell = functools.lru_cache(maxsize=None)(runner.load_cell)


def _sources(cell_name: str, start: str, traced: bool) -> dict:
    return {"cell": _cell(cell_name),
            "loadgen": json.loads(_read("loadgen.json")),
            "trace": json.loads(_read("trace_reduced.json")) if traced
            else None,
            "peaks": PEAKS,
            "server_log": _read("server_stderr.txt"),
            "metrics_start": STARTS[start], "metrics_end": END,
            "scrape_s": 20.4}


def test_the_fixture_is_an_older_program():
    families = {name for name, _ in END}
    assert not families & set(NEW_FAMILIES)
    stages = {dict(labels).get("stage") for name, labels in END
              if name.startswith("rate_limiter_stage_seconds")}
    assert stages == {"io", "dispatch", "device", "complete"}
    assert not stages & set(NEW_STAGES)


@pytest.mark.parametrize("traced", [False, True], ids=["no-trace", "trace"])
@pytest.mark.parametrize("start", list(STARTS))
@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("reader", READERS,
                         ids=[r.META["name"] for r in READERS])
def test_reader_gives_a_number_or_none(reader, cell_name, start, traced):
    sources = _sources(cell_name, start, traced)
    facts = {k: sources["cell"][k]
             for k in ("name", "chips", "config", "traffic")}
    assert reader.META["applies"](facts) in (True, False)
    value = reader.read(sources)        # whether it applies or not
    assert value is None or (isinstance(value, (int, float))
                             and value == value)      # no NaN


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.META["name"] for r in READERS])
def test_reader_imports_nothing_of_the_program(reader):
    """The runner holds no chip and must not import JAX; a reader runs
    in it, against whatever program the checkout has."""
    tree = ast.parse(inspect.getsource(reader))
    imported = {alias.name.split(".")[0]
                for node in ast.walk(tree) if isinstance(node, ast.Import)
                for alias in node.names}
    imported |= {(node.module or "").split(".")[0]
                 for node in ast.walk(tree)
                 if isinstance(node, ast.ImportFrom)}
    assert not imported & {"ratelimiter_tpu", "jax", "jaxlib", "numpy"}


def test_the_manifests_new_metrics_are_absent_from_an_old_programs_line():
    """The whole of per_layer() on the old program: the result line of
    each cell simply lacks what the program cannot give."""
    new = {"dispatch_covered_pct", "dispatch_us_exact", "hash_us_per_dispatch",
           *(f"{s}_us_per_dispatch" for s in ("enter", "prep", "place",
                                              "step_enqueue", "finish",
                                              "leave"))}
    for cell_name in CELLS:
        sources = _sources(cell_name, "half", True)
        got = runner.per_layer(sources["cell"], sources)
        assert got, cell_name
        assert not {n for n in got if n.removesuffix("_open") in new}, got
