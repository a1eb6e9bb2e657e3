"""The five readers PR 44 added with ``exact-hashed-ttl`` —
``reclaim_passes_per_s``, ``reclaimed_per_pass``,
``reclaim_stall_us_per_pass``, ``reclaim_us_per_pass``,
``reclaim_roofline`` — on a scrape pair and a reduced trace made by hand,
on the traced run of the new cell recorded on the chip
(data/pr44_ttl_traced/README.txt), which must give back the line that run
printed, and on the PR 42 recording of ``exact-hashed-20m``: a program
without the two counter families gives them nothing to read (None, no
raise). The byte model of the new configuration is pinned beside them."""

import json
import os

import pytest

from chipbench import bytes as need
from chipbench import bytes_reclaim, bytes_table, promtext, runner
from chipbench.layers import (
    _reclaim,
    reclaim_passes_per_s,
    reclaim_roofline,
    reclaim_stall_us_per_pass,
    reclaim_us_per_pass,
    reclaimed_per_pass,
)
from chipbench.tests.test_manifest import listed_entry
from chipbench.tests.test_recorded_runs import sources_of

CELL = "exact-hashed-ttl"
RUN = "pr44_ttl_traced"
DATA = os.path.join(os.path.dirname(__file__), "data", RUN)
COUNTERS = (reclaim_passes_per_s, reclaimed_per_pass,
            reclaim_stall_us_per_pass)
READERS = COUNTERS + (reclaim_us_per_pass, reclaim_roofline)
D = "rate_limiter_directory_"


@pytest.fixture(autouse=True)
def published(monkeypatch):
    """The published geometry, whatever JAX_PLATFORMS says here."""
    monkeypatch.setattr(runner, "REHEARSAL", False)


def scrape(passes, reclaimed, seconds) -> dict:
    return promtext.parse("\n".join([
        f'{D}reclaim_passes_total{{shard="0"}} {passes}',
        f'{D}reclaimed_total{{shard="0"}} {reclaimed}',
        f'{D}reclaim_seconds_total{{shard="0"}} {seconds}',
        f'{D}capacity{{shard="0"}} 2097152']))


def trace(modules) -> dict:
    return {"step": {"module": "jit_dense_step", "executions": 2500,
                     "seconds": 2.0},
            "modules": modules, "n_devices": 1, "window_s": 5.0}


# ------------------------------------------------------- made by hand

def test_the_counter_readers_on_a_scrape_pair_made_by_hand():
    sources = {"metrics_start": scrape(3, 1_500_000, 0.024),
               "metrics_end": scrape(23, 11_500_000, 0.184),
               "scrape_s": 20.0}
    assert reclaim_passes_per_s.read(sources) == 1.0
    assert reclaimed_per_pass.read(sources) == 500_000.0
    assert reclaim_stall_us_per_pass.read(sources) == pytest.approx(8_000.0)
    # No pass between the scrapes: a rate of 0, and nothing per pass.
    still = dict(sources, metrics_end=scrape(3, 1_500_000, 0.024))
    assert reclaim_passes_per_s.read(still) == 0.0
    assert reclaimed_per_pass.read(still) is None
    assert reclaim_stall_us_per_pass.read(still) is None
    # A program before PR 44 exports `reclaimed_total` alone: nothing.
    old = promtext.parse(f'{D}reclaimed_total{{shard="0"}} 7')
    for reader in COUNTERS:
        assert reader.read({"metrics_start": old, "metrics_end": old,
                            "scrape_s": 20.0}) is None


def test_the_trace_readers_on_a_reduction_made_by_hand():
    cell = runner.load_cell(CELL)
    with open(os.path.join(runner.HERE, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    sources = {"cell": cell, "peaks": peaks, "scrape_s": 20.0,
               "metrics_start": scrape(3, 1_500_000, 0.024),
               "metrics_end": scrape(23, 11_500_000, 0.184),
               "trace": trace([["jit_dense_step", 2500, 2.0],
                               [_reclaim.MODULE, 5, 0.004]])}
    assert reclaim_us_per_pass.read(sources) == pytest.approx(800.0)
    # 2^21 x 16 B read + 500,000 x 32 B written over 819 GB/s in 800 us.
    must = (1 << 21) * 16 + 500_000 * 32
    assert bytes_reclaim.pass_bytes(cell["config"], 500_000) == must
    assert reclaim_roofline.read(sources) == pytest.approx(
        100 * must / peaks["hbm_bytes_per_s"] / 800e-6)
    assert 0 < reclaim_roofline.read(sources) < 100
    # A capture that holds no pass, no trace at all, no pass between the
    # scrapes, a configuration whose byte model counts no pass.
    none = dict(sources, trace=trace([["jit_dense_step", 2500, 2.0]]))
    for reader in (reclaim_us_per_pass, reclaim_roofline):
        assert reader.read(none) is None
        assert reader.read(dict(sources, trace=None)) is None
    assert reclaim_roofline.read(
        dict(sources, metrics_end=sources["metrics_start"])) is None
    assert reclaim_roofline.read(
        dict(sources, cell=runner.load_cell("exact-hashed-20m"))) is None


def test_the_byte_model_is_the_tables_own_arithmetic():
    cfg = runner.load_cell(CELL)["config"]
    assert cfg["byte_model"] == "reclaim"
    assert need.model_of(cfg) is bytes_reclaim
    assert cfg["capacity"] < cfg["key_population"]      # the active set
    # One arithmetic since PR 50: the model hands over to the table's.
    assert bytes_reclaim.step_bytes is bytes_table.step_bytes
    assert bytes_reclaim.step_ops is bytes_table.step_ops
    assert bytes_table.step_bytes(cfg, 4096, 570.0) == 4096 * 96.125
    sat = runner.load_cell("exact-hashed-sat")["config"]
    assert need.step_bytes(cfg, 4096, 570.0) \
        == bytes_table.step_bytes(sat, 4096, 570.0) == 4096 * 96.125
    assert need.step_ops(cfg, 4096) == bytes_table.step_ops(sat, 4096)


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
    # The whole line, digit for digit: every reader of the cell.
    assert {k: (v["value"], v["unit"])
            for k, v in printed["metrics"].items()} == got
    # ISSUE 44's acceptance, on the recorded line.
    assert got["reclaim_passes_per_s"][0] > 0.3
    assert got["reclaimed_per_pass"][0] > 1e5
    assert got["directory_load_pct"][0] >= 60
    assert got["directory_unplaced_pct"][0] == 0.0
    assert got["directory_inserts_per_dispatch"][0] >= 600
    assert got["directory_probes_per_lookup"][0] >= 1.0
    assert 0 < got["reclaim_roofline"][0] <= 100
    assert 0 < got["step_roofline"][0] <= 100
    assert got["state_resident_mb"][0] == pytest.approx(67.108888)
    # The stall holds the pass and the steps in flight ahead of it.
    assert got["reclaim_stall_us_per_pass"][0] > got["reclaim_us_per_pass"][0]


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise():
    """The PR 42 recording of exact-hashed-20m, under either cell."""
    for cell in ("exact-hashed-20m", CELL):
        sources = sources_of("pr42_20m_traced", cell)
        for reader in READERS:
            assert reader.read(sources) is None, reader.META["name"]


# ------------------------------------------------------------ manifest

def test_their_manifest_entries_and_where_they_apply():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # A predicate over the cell, never a list of names: the dense
    # backend under a closed loop on a table smaller than its key
    # population (expiry is what keeps it from filling).
    small = [w["name"] for w in bench["workloads"]
             if _reclaim.applies(runner.load_cell(w["name"]))]
    assert CELL in small and "exact-hashed-20m" not in small
    for reader in READERS:
        assert listed_entry(reader)[1] == small
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "exact-tb-ttl",
                    "traffic": "hashed-sat-z099", "chips": 1}
    # Everything exact-hashed-20m is listed for, the new cell is too.
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "exact-hashed-20m" in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]
    cfg = runner.load_cell(CELL)["config"]
    assert (cfg["capacity"], cfg["key_population"], cfg["window_s"],
            cfg["limit"]) == (1 << 21, 20_000_000, 1, 100)
    assert cfg["reduced"] == ["key_population"]
    flags = cfg["server_flags"]
    assert flags[flags.index("--dense-capacity") + 1] == str(cfg["capacity"])
    assert flags[flags.index("--window") + 1] == "1"
    assert set(cfg["guarantees"]) == set(
        runner.load_cell("exact-hashed-20m")["config"]["guarantees"]) \
        | {"expiry"}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(2, len(bench["workloads"]) // 2)
