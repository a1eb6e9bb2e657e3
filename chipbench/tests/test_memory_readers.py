"""The three readers PR 42 added with ``exact-hashed-20m`` —
``state_resident_mb``, ``device_peak_over_state``,
``boundary_us_per_dispatch`` — on scrapes and traces made by hand, on the
traced run of the new cell recorded on the chip
(data/pr42_20m_traced/README.txt), which must give back the line that
run printed, and on the PR 33 recording of ``exact-hashed-sat``: a
program without the gauges gives the two counter readers nothing to
read (None, no raise), while the boundary's op group was in that trace
all along (the 246 us ROADMAP S3 quotes)."""

import json
import os

import pytest

from chipbench import promtext, runner
from chipbench.layers import (
    _directory,
    _memory,
    boundary_us_per_dispatch,
    device_peak_over_state,
    state_resident_mb,
)
from chipbench.tests.test_manifest import listed_entry
from chipbench.tests.test_recorded_runs import sources_of

CELL = "exact-hashed-20m"
RUN = "pr42_20m_traced"
DATA = os.path.join(os.path.dirname(__file__), "data", RUN)
READERS = (state_resident_mb, device_peak_over_state,
           boundary_us_per_dispatch)
RESIDENT_20M = 32 * (1 << 26) + 24


@pytest.fixture(autouse=True)
def published(monkeypatch):
    """The published geometry, whatever JAX_PLATFORMS says here."""
    monkeypatch.setattr(runner, "REHEARSAL", False)


def scrape(*lines) -> dict:
    return promtext.parse("\n".join(lines))


# ------------------------------------------------------- made by hand

def test_the_gauge_readers_on_a_scrape_made_by_hand():
    end = scrape(
        f'{_memory.RESIDENT}{{device="0",shard="0"}} {RESIDENT_20M}',
        f'{_memory.PEAK}{{device="0"}} 2840617472',
        'rate_limiter_device_bytes_in_use{device="0"} 2300000000')
    sources = {"metrics_end": end}
    assert state_resident_mb.read(sources) == RESIDENT_20M / 1e6
    assert device_peak_over_state.read(sources) == 2840617472 / RESIDENT_20M
    # A mesh: one series a chip, both sums.
    mesh = scrape(*(f'{_memory.RESIDENT}{{device="{d}",shard="0"}} 1000'
                    for d in range(4)),
                  *(f'{_memory.PEAK}{{device="{d}"}} 3000'
                    for d in range(4)))
    assert state_resident_mb.read({"metrics_end": mesh}) == 0.004
    assert device_peak_over_state.read({"metrics_end": mesh}) == 3.0
    # The CPU: the resident gauge and no sample of the peak.
    cpu = scrape(f'{_memory.RESIDENT}{{device="0",shard="0"}} 524312')
    assert state_resident_mb.read({"metrics_end": cpu}) == 0.524312
    assert device_peak_over_state.read({"metrics_end": cpu}) is None
    # A program before PR 42: neither.
    for reader in (state_resident_mb, device_peak_over_state):
        assert reader.read({"metrics_end": scrape("up 1")}) is None


def test_the_boundary_reader_on_a_trace_made_by_hand():
    def trace(ops, executions=100):
        return {"trace": {"step": {"module": "jit_dense_step",
                                   "executions": executions,
                                   "seconds": 1.0},
                          "device_ops": ops}}

    read = boundary_us_per_dispatch.read
    assert read(trace([["custom-call", 3.0], ["fusion", 0.1]])) == 30_000.0
    # The layout change took the table's passes out: the group is under
    # the tenth-largest and not listed. ~0, not None.
    assert read(trace([["fusion", 0.1], ["while", 0.05]])) == 0.0
    assert read({"trace": None}) is None
    assert read({}) is None
    assert read(trace([["custom-call", 3.0]], executions=0)) is None
    assert read({"trace": {"step": None, "device_ops": []}}) is None


# ------------------------------------------------- recorded on the chip

def test_each_reader_gives_a_number_on_the_recorded_run_of_the_new_cell():
    printed = json.load(open(os.path.join(DATA, "result_line.json")))
    assert printed["correct"] is True and printed["failed"] == 0
    sources = sources_of(RUN, CELL)
    got = runner.per_layer(sources["cell"], sources)
    for reader in READERS:
        name = reader.META["name"]
        value = reader.read(sources)
        assert isinstance(value, float) and value > 0, name
        assert got[name] == (value, reader.META["unit"])
        assert printed["metrics"][name] == {"value": value,
                                            "unit": reader.META["unit"]}
    # The whole line, digit for digit: every reader of the cell.
    assert {k: (v["value"], v["unit"])
            for k, v in printed["metrics"].items()} == got
    assert got["state_resident_mb"][0] == RESIDENT_20M / 1e6
    # The device's own figure beside the harness's (serve_child.py reads
    # the peak again when the server has shut down: never lower).
    peak = got["device_peak_over_state"][0] * RESIDENT_20M
    assert 1.0 < got["device_peak_over_state"][0] < 2.0
    assert peak <= printed["device"]["memory_peak_bytes"]
    # The boundary is nearly the whole step, and the step the whole cycle.
    assert 0.9 < (got["boundary_us_per_dispatch"][0]
                  / got["device_us_per_dispatch"][0]) < 1.0
    assert got["device_idle_pct"][0] < 1.0


def test_a_program_without_the_gauges_gives_nothing_and_does_not_raise():
    """The PR 33 recording of exact-hashed-sat, under either exact cell."""
    for cell in ("exact-hashed-sat", CELL):
        sources = sources_of("pr33_exact_traced", cell)
        assert state_resident_mb.read(sources) is None
        assert device_peak_over_state.read(sources) is None
        # 0.660078687 s of custom-call over 2,683 executions.
        assert boundary_us_per_dispatch.read(sources) == pytest.approx(
            246.02, abs=0.01)
        assert boundary_us_per_dispatch.read(dict(sources, trace=None)) \
            is None


# ------------------------------------------------------------ manifest

def test_their_manifest_entries_and_where_they_apply():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # A predicate over the cell, never a list of names: the dense
    # backend under a closed loop, whatever cells of it the manifest has.
    dense = [w["name"] for w in bench["workloads"]
             if _directory.dense_closed(runner.load_cell(w["name"]))]
    assert {"exact-hashed-sat", CELL} <= set(dense)
    for reader in READERS:
        entry, on = listed_entry(reader)
        assert on == dense and entry["moves"] == "decisions_per_s"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "exact-tb-20m",
                    "traffic": "hashed-sat-z099", "chips": 1}
    # Everything exact-hashed-sat is listed for, the new cell is too.
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "exact-hashed-sat" in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]
    cfg = runner.load_cell(CELL)["config"]
    assert cfg["capacity"] == 1 << 26 and cfg["key_population"] == 20_000_000
    assert cfg["reduced"] == ["key_population"]
    assert cfg["byte_model"] == "table"
    small = runner.load_cell("exact-hashed-sat")["config"]
    assert cfg["guarantees"] == small["guarantees"]
    assert runner.load_cell(CELL)["traffic"]["zipf_s"] == 0.99
