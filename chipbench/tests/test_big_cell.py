"""The cell ``wide-hashed-big`` (configuration ``cms-wide-b64k``, traffic
``hashed-sat-c64``, PR 35): a rehearsal on the CPU that coalesces too,
untraced and traced; the configuration held to ``cms-wide``; its two
coalescing readers on scrapes made by hand, on a program without the
counters, and on the traced run recorded on the chip
(data/pr35_big_traced/README.txt), which must give back the line that
run printed."""

import json
import os

import pytest

from chipbench import promtext, runner
from chipbench.layers import (
    _coalesce,
    carved_frames_pct,
    frames_per_dispatch,
)
from chipbench.tests.test_manifest import listed_entry
from chipbench.tests.test_rehearsal import rehearse

CELL = "wide-hashed-big"
CONTROL = "wide-hashed-sat"
DATA = os.path.join(os.path.dirname(__file__), "data", "pr35_big_traced")
READERS = (frames_per_dispatch, carved_frames_pct)


# ------------------------------------------------------------- rehearsal

def test_rehearsal_untraced():
    done, lines = rehearse(runner.ROOT, CELL, 0)
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    assert lines["rehearsal"]["metric_names"] == ["decisions_per_s",
                                                  "setup_s"]
    assert lines["loadgen"]["conns"] == 64
    assert lines["probe"]["allowed_per_hot_key"] == [100]
    assert lines["checks"]["failures"] == []
    assert lines["loadgen"]["top_allowed_max"] \
        <= lines["loadgen"]["admitted_cap"]


def test_rehearsal_traced_coalesces_and_reports_it():
    done, lines = rehearse(runner.ROOT, CELL, 1, seconds="3")
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    names = lines["rehearsal"]["metric_names"]
    for name in ("frames_per_dispatch", "carved_frames_pct",
                 "dispatch_batch_mean", "fetches_per_dispatch",
                 "override_lookup_pct", "dispatch_us_exact",
                 "fetch_us_per_dispatch", "prewarm_s"):
        assert name in names
    assert "unlisted_layer_metrics" not in lines
    # --max-batch 4096 over 256-id frames: the CPU's dispatches hold
    # several frames each (read from the run's own scrapes).
    out = os.path.join(runner.HERE, "out", f"{CELL}-5-1")
    scrape = {}
    for end in ("start", "end"):
        with open(os.path.join(out, f"metrics_window_{end}.txt")) as fh:
            scrape["metrics_" + end] = promtext.parse(fh.read())
    assert frames_per_dispatch.read(scrape) > 2.0
    assert 0.0 <= carved_frames_pct.read(scrape) < 50.0


# ----------------------------------------------------- the configuration

@pytest.fixture()
def published(monkeypatch):
    monkeypatch.setattr(runner, "REHEARSAL", False)


@pytest.fixture()
def cell(published):
    return runner.load_cell(CELL)


def test_the_configuration_is_cms_wide_behind_a_wider_door(cell):
    cfg, wide = cell["config"], runner.load_cell(CONTROL)["config"]
    for key in ("algorithm", "limit", "window_s", "depth", "width",
                "sub_windows", "key_population", "chips", "reduced"):
        assert cfg[key] == wide[key], key
    assert cfg["server_flags"] == wide["server_flags"] + ["--max-batch",
                                                          "65536"]
    assert cfg["reduced"] == ["key_population"]
    assert cfg["assumed"]["max_batch"] == 65536 == _coalesce.max_batch(cell)
    assert cfg["assumed"]["inflight"] == 8
    for name, said in wide["guarantees"].items():
        assert cfg["guarantees"][name] == said
    assert "524,288" in cfg["guarantees"]["in_flight"]
    assert "none" in cfg["guarantees"]["durability"]
    assert cell["chips"] == 1 and cell["traffic_name"] == "hashed-sat-c64"
    traffic, sat = cell["traffic"], runner.load_cell(CONTROL)["traffic"]
    assert traffic["connections"] == 64
    for key in ("lane", "frame_keys", "inflight", "loop", "zipf_s"):
        assert traffic[key] == sat[key], key
    # Sixteen full dispatches in flight: two --inflight 8 windows.
    in_flight = traffic["connections"] * traffic["inflight"] \
        * traffic["frame_keys"]
    assert in_flight == 16 * cfg["assumed"]["max_batch"]
    # The CPU rehearsal coalesces sixteen frames a dispatch too.
    reh = cfg["rehearsal"]
    flags = reh["server_flags"]
    assert int(flags[flags.index("--max-batch") + 1]) \
        == 16 * traffic["rehearsal"]["frame_keys"]


# ---------------------------------------------------------- the readers

def scrape(**values) -> dict:
    return promtext.parse("\n".join(f"{name} {value}"
                                    for name, value in values.items()))


def test_the_readers_on_counters_made_by_hand():
    start = scrape(**{_coalesce.FRAMES: 100, _coalesce.CARVED: 4,
                      "rate_limiter_door_dispatches_total": 10})
    end = scrape(**{_coalesce.FRAMES: 1700, _coalesce.CARVED: 104,
                    "rate_limiter_door_dispatches_total": 110})
    sources = {"metrics_start": start, "metrics_end": end}
    assert frames_per_dispatch.read(sources) == 16.0
    assert carved_frames_pct.read(sources) == 6.25
    # Nothing dispatched between the scrapes: nothing to divide by.
    still = {"metrics_start": end, "metrics_end": end}
    assert frames_per_dispatch.read(still) is None
    assert carved_frames_pct.read(still) is None


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise():
    """The parent's /metrics (any run recorded before PR 35) has neither
    counter: both readers return None."""
    from chipbench.tests.test_recorded_runs import sources_of

    sources = sources_of("pr32_c3_traced", "c3-hashed-sat")
    for reader in READERS:
        assert reader.read(sources) is None


def test_they_apply_where_a_dispatch_holds_more_than_a_frame(published):
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    on = {w["name"]: all(r.META["applies"](runner.load_cell(w["name"]))
                         for r in READERS) for w in bench["workloads"]}
    assert on == {name: name == CELL for name in on}
    big = runner.load_cell(CELL)
    assert _coalesce.max_batch(big) == 65536
    assert _coalesce.max_batch(runner.load_cell(CONTROL)) == 4096
    open_loop = dict(big, traffic=dict(big["traffic"], loop="open"))
    whole = dict(big, traffic=dict(big["traffic"], frame_keys=65536))
    for other in (open_loop, whole):
        assert not any(r.META["applies"](other) for r in READERS)
    for reader in READERS:
        entry, on = listed_entry(reader)
        assert on == [CELL]
        assert entry["layer"] == "batcher / staging"
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
    assert got["dispatch_batch_mean"] >= 60000
    assert got["frames_per_dispatch"] >= 14
    assert got["carved_frames_pct"] == 0.0
    assert got["programs_per_dispatch"] <= 1.01
    # (the two counters are read a dispatch apart: 1,463 in the window)
    assert got["fetches_per_dispatch"] == pytest.approx(1.0, abs=0.01)
    assert got["override_lookup_pct"] == 0.0
    assert 0.0 < got["step_roofline"] < 105.0
    assert recorded["trace"]["step"]["module"] == "jit_sketch_step"
