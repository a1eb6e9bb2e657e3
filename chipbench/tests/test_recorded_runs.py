"""Traced runs recorded on the chip (data/pr32_*_traced/README.txt): the
sources a run hands its readers, and the result line it printed.
``runner.per_layer`` on the sources must give the printed metrics back
digit for digit — the whole reduction, every reader of the cell, on a
run of the current program — and, where the parent's values are pinned
beside them (the collective run under its own cell, the one-chip run
under the parent's four other cells), every reader must still return
the parent's."""

import json
import os

import pytest

from chipbench import layers, promtext, runner
from chipbench.tests.test_corrected_readers import CORRECTED, held_to_parent

DATA = os.path.join(os.path.dirname(__file__), "data")
RUNS = {"pr32_c3_traced": "c3-hashed-sat",
        "pr32_coll_traced": "mesh4-coll-mixed"}


def _read(run: str, name: str) -> str:
    with open(os.path.join(DATA, run, name)) as fh:
        return fh.read()


def sources_of(run: str, cell_name: str) -> dict:
    with open(os.path.join(runner.HERE, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    extra = (json.loads(_read(run, "sources.json"))
             if os.path.exists(os.path.join(DATA, run, "sources.json"))
             else {})
    return {"cell": runner.load_cell(cell_name),
            "loadgen": json.loads(_read(run, "loadgen.json")),
            "trace": json.loads(_read(run, "trace_reduced.json")),
            "peaks": peaks, "server_log": _read(run, "server_stderr.txt"),
            "metrics_start": promtext.parse(
                _read(run, "metrics_window_start.txt")),
            "metrics_end": promtext.parse(
                _read(run, "metrics_window_end.txt")),
            "scrape_s": extra.get("scrape_s", 20.0)}


@pytest.fixture(autouse=True)
def published(monkeypatch):
    """The published geometry, whatever JAX_PLATFORMS says here."""
    monkeypatch.setattr(runner, "REHEARSAL", False)


@pytest.mark.parametrize("run", list(RUNS))
def test_the_readers_give_back_the_line_the_run_printed(run):
    printed = json.loads(_read(run, "result_line.json"))
    assert printed["correct"] is True and printed["failed"] == 0
    assert printed["device"]["busy_s"] > 0
    sources = sources_of(run, RUNS[run])
    got = runner.per_layer(sources["cell"], sources)
    listed = {m["name"] for m in runner.cell_metrics(sources["cell"],
                                                     "per_layer")}
    assert set(printed["metrics"]) <= listed
    for name, entry in printed["metrics"].items():
        if run == "pr32_coll_traced" and name in CORRECTED:
            continue        # printed by the corrected readers: next test
        assert got[name] == (entry["value"], entry["unit"]), name
    assert set(got) == set(printed["metrics"])


def test_the_corrected_readers_before_and_after_on_the_collective_run():
    run = "pr32_coll_traced"
    printed = json.loads(_read(run, "result_line.json"))["metrics"]
    before = json.loads(_read(run, "readers_at_9bfe964.json"))[RUNS[run]]
    sources = sources_of(run, RUNS[run])
    got = runner.per_layer(sources["cell"], sources)
    for name in CORRECTED:
        assert got[name][0] == printed[name]["value"]
    # One chip's rows against one chip's time: a quarter of the batch's
    # bytes, the rotation's share unchanged.
    assert 0.25 < got["step_roofline"][0] / before["step_roofline"] < 0.35
    spans = {s: layers.stage_us(sources, s) for s in
             ("assemble", "writeback", "dispatch")}
    assert got["dispatch_covered_pct"][0] == pytest.approx(
        before["dispatch_covered_pct"]
        + 100.0 * (spans["assemble"] + spans["writeback"])
        / spans["dispatch"])


PARENT_CELLS = [(run, cell_name) for run in RUNS for cell_name in json.loads(
    _read(run, "readers_at_9bfe964.json"))]


@pytest.mark.parametrize("run, cell_name", PARENT_CELLS)
def test_every_reader_returns_the_parents_value(run, cell_name):
    """The parent's readers (commit 9bfe964) were run on these sources:
    the collective run's under its own cell, the one-chip run's under
    each of the parent's four other cells. Ours agree bit for bit, but
    for the two corrected ones under the collective router."""
    parent = json.loads(_read(run, "readers_at_9bfe964.json"))[cell_name]
    assert held_to_parent(parent, sources_of(run, cell_name),
                          cell_name == "mesh4-coll-mixed") > 25
