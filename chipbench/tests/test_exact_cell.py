"""The cell ``exact-hashed-sat`` (configuration ``exact-tb-1m``, PR 33): a
rehearsal on the CPU, untraced and traced; its byte model pinned in
test_bytes.py's manner; its four ``directory_*`` readers on scrapes made
by hand and on the traced run recorded on the chip
(data/pr33_exact_traced/README.txt), which must give back the line that
run printed."""

import json
import os

import pytest

from chipbench import bytes as need
from chipbench import bytes_table, layers, promtext, runner
from chipbench.layers import (
    _directory,
    directory_inserts_per_dispatch,
    directory_load_pct,
    directory_probes_per_lookup,
    directory_unplaced_pct,
)
from chipbench.tests.test_manifest import listed_entry
from chipbench.tests.test_rehearsal import rehearse

CELL = "exact-hashed-sat"
DATA = os.path.join(os.path.dirname(__file__), "data", "pr33_exact_traced")
READERS = (directory_probes_per_lookup, directory_inserts_per_dispatch,
           directory_load_pct, directory_unplaced_pct)


# ------------------------------------------------------------- rehearsal

def test_rehearsal_untraced():
    done, lines = rehearse(runner.ROOT, CELL, 0)
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    assert lines["rehearsal"]["metric_names"] == ["decisions_per_s",
                                                  "setup_s"]
    assert "dense_capacity=16384" in lines["server"]["banner"]
    # The probe on the hashed lane against reference.TokenBucket: eight
    # keys allowed their burst, a fresh key allowed; no cold key denied.
    assert lines["probe"]["allowed_per_hot_key"] == [100]
    assert lines["probe"]["fresh_allowed"] == 1
    assert lines["checks"]["cold"]["denied"] == 0
    assert lines["checks"]["cold"]["policy"] == 0
    assert lines["checks"]["failures"] == []
    assert lines["loadgen"]["top_allowed_max"] \
        <= lines["loadgen"]["admitted_cap"]


def test_rehearsal_traced_reports_the_directory():
    done, lines = rehearse(runner.ROOT, CELL, 1, seconds="3")
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    names = lines["rehearsal"]["metric_names"]
    for reader in READERS:
        assert reader.META["name"] in names
    # What the closed-loop dispatch readers read is there too: the same
    # spans and counters as the sketch cells'.
    for name in ("fetches_per_dispatch", "override_lookup_pct",
                 "dispatch_us_exact", "place_us_per_dispatch",
                 "step_enqueue_us_per_dispatch", "fetch_us_per_dispatch",
                 "prewarm_s"):
        assert name in names
    assert "unlisted_layer_metrics" not in lines


# ------------------------------------------------------------ byte model

@pytest.fixture()
def cell(monkeypatch):
    monkeypatch.setattr(runner, "REHEARSAL", False)
    return runner.load_cell(CELL)


def test_the_configuration_is_the_sources_own(cell):
    cfg = cell["config"]
    assert cfg["reduced"] == [] and cfg["key_population"] == 1_000_000
    assert cfg["capacity"] == 1 << 21 and cfg["byte_model"] == "table"
    assert sorted(cfg["assumed"]) == ["capacity", "lanes", "probe_bound"]
    assert "false_deny" not in cfg["guarantees"]
    assert cell["chips"] == 1 and cell["traffic_name"] == "hashed-sat"
    # The load the assumed capacity gives, the check's own keys included.
    assert (cfg["key_population"] + 4096 + 9) / cfg["capacity"] < 0.48
    assert need.model_of(cfg) is bytes_table


def test_bytes_table_is_the_algorithms_need(cell):
    cfg = cell["config"]
    # 12 B of probe + 3 x 16 B of row + 12 B in + 24.125 B out a decision.
    assert need.step_bytes(cfg, 1, 0.0) == 96.125
    assert need.step_bytes(cfg, 4096, 580.0) == 4096 * 96.125 == 393728.0
    # Nothing periodic, nothing sized by the table.
    assert need.step_bytes(cfg, 4096, 1.0) == need.step_bytes(cfg, 4096, 1e6)
    big = dict(cfg, capacity=1 << 27, key_population=10 ** 8)
    assert need.step_bytes(big, 4096, 580.0) == 393728.0
    assert need.step_ops(cfg, 100) == 3100
    for algorithm, row in (("fixed_window", 32), ("sliding_window", 48)):
        assert need.step_bytes(dict(cfg, algorithm=algorithm), 1, 0.0) \
            == 12 + row + 36.125
    # A table sized for the active set moves the same bytes a decision.
    assert need.step_bytes(dict(cfg, capacity=999_999), 1, 0.0) == 96.125


# ---------------------------------------------------------- the readers

def scrape(**values) -> dict:
    text = "\n".join(f'{name}{{shard="0"}} {value}'
                     for name, value in values.items())
    return promtext.parse(text)


def test_the_readers_on_counters_made_by_hand():
    fam = _directory.FAMILY
    start = scrape(**{fam + "lookups_total": 1000, fam + "probes_total": 1010,
                      fam + "inserts_total": 900,
                      fam + "unplaced_total": 0, fam + "entries": 900,
                      fam + "capacity": 2048,
                      "rate_limiter_door_dispatches_total": 10})
    end = scrape(**{fam + "lookups_total": 41960, fam + "probes_total": 43008,
                    fam + "inserts_total": 1412, fam + "unplaced_total": 2048,
                    fam + "entries": 1024, fam + "capacity": 2048,
                    "rate_limiter_door_dispatches_total": 20})
    sources = {"metrics_start": start, "metrics_end": end}
    assert directory_probes_per_lookup.read(sources) \
        == (43008 - 1010) / 40960
    assert directory_inserts_per_dispatch.read(sources) == 51.2
    assert directory_load_pct.read(sources) == 50.0
    assert directory_unplaced_pct.read(sources) == 5.0
    # Nothing decided between the scrapes: nothing to divide by.
    still = {"metrics_start": end, "metrics_end": end}
    assert directory_probes_per_lookup.read(still) is None
    assert directory_inserts_per_dispatch.read(still) is None
    assert directory_unplaced_pct.read(still) is None
    assert directory_load_pct.read(still) == 50.0


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise():
    """The parent's /metrics (any recorded sketch run) has no
    ``rate_limiter_directory_*`` family: every reader returns None."""
    from chipbench.tests.test_recorded_runs import sources_of

    sources = sources_of("pr32_c3_traced", "c3-hashed-sat")
    for reader in READERS:
        assert reader.read(sources) is None


def test_they_apply_to_the_dense_closed_loop_cells_alone():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: runner.load_cell(w["name"])
             for w in bench["workloads"]}
    dense = [name for name, c in cells.items()
             if "dense" in c["config"]["server_flags"]
             and c["traffic"]["loop"] == "closed"]
    assert CELL in dense and "wide-hashed-sat" not in dense
    open_loop = dict(cells[CELL])
    open_loop["traffic"] = dict(open_loop["traffic"], loop="open")
    assert not any(r.META["applies"](open_loop) for r in READERS)
    for reader in READERS:
        entry, on = listed_entry(reader)
        assert on == dense
        assert entry["layer"] == "directory"
        assert entry["moves"] == "decisions_per_s"
        assert entry["source"] == "program_counter"


# ------------------------------------------- the run recorded on the chip

def _read(name: str) -> str:
    with open(os.path.join(DATA, name)) as fh:
        return fh.read()


@pytest.fixture()
def recorded(cell):
    with open(os.path.join(runner.HERE, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    return {"cell": cell, "loadgen": json.loads(_read("loadgen.json")),
            "trace": json.loads(_read("trace_reduced.json")),
            "peaks": peaks, "server_log": _read("server_stderr.txt"),
            "metrics_start": promtext.parse(
                _read("metrics_window_start.txt")),
            "metrics_end": promtext.parse(_read("metrics_window_end.txt")),
            "scrape_s": json.loads(_read("sources.json"))["scrape_s"]}


def test_the_readers_give_back_the_line_the_run_printed(recorded):
    printed = json.loads(_read("result_line.json"))
    assert printed["correct"] is True and printed["failed"] == 0
    assert printed["device"]["platform"] == "tpu"
    assert printed["device"]["busy_s"] > 0
    got = runner.per_layer(recorded["cell"], recorded)
    for name, entry in printed["metrics"].items():
        assert got[name] == (entry["value"], entry["unit"]), name
    # The line is compared on the metrics it holds: a reader added since
    # finds no source in this recording, or reads one the line never had.
    listed = {m["name"] for m in runner.cell_metrics(recorded["cell"],
                                                     "per_layer")}
    assert set(printed["metrics"]) <= set(got) <= listed


def test_the_recorded_run_is_the_cell_the_issue_asks_for(recorded):
    got = {name: value
           for name, (value, _) in runner.per_layer(recorded["cell"],
                                                    recorded).items()}
    assert got["directory_unplaced_pct"] == 0.0
    assert got["override_lookup_pct"] == 0.0
    assert got["fetches_per_dispatch"] == 1.0
    assert 0.999 < got["programs_per_dispatch"] < 1.01
    assert 1.0 <= got["directory_probes_per_lookup"] < 1.2
    assert 0.0 < got["directory_load_pct"] < 50.0
    assert got["directory_inserts_per_dispatch"] > 0.0
    # (a histogram's _sum is still printed to six significant digits)
    assert got["dispatch_batch_mean"] == pytest.approx(4096.0, abs=0.01)
    assert 0.0 < got["step_roofline"] < 100.0
    # The step's roofline share is the byte model's bytes over the
    # device's time, through the configuration's own model.
    batch, step_us = got["dispatch_batch_mean"], got[
        "device_us_per_dispatch"]
    assert got["step_roofline"] == pytest.approx(
        100.0 * (batch * 96.125 / 819e9) / (step_us * 1e-6))
    assert recorded["trace"]["step"]["module"] == "jit_dense_step"
    assert layers.stage_us(recorded, "reclaim") is None   # never ran
