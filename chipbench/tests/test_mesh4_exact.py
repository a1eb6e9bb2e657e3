"""PR 51's additions: the configuration ``exact-tb-mesh4`` (the exact
backend over the four chips of a host), its cell ``mesh4-exact-80m`` and
the two readers that cell alone reports — ``directory_load_max_pct``
(the fullest slice's directory, by ``shard``) and
``device_busy_max_pct`` (the busiest chip of the capture) — on sources
made by hand, on a program without the families (None, no raise), in
the manifest, and end to end on four virtual CPU devices."""

import json
import os

import pytest

from chipbench import bytes as need
from chipbench import promtext, runner
from chipbench.layers import (
    _directory,
    _reclaim,
    device_busy_max_pct,
    device_idle_pct,
    directory_load_max_pct,
    directory_load_pct,
)
from chipbench.tests.test_manifest import check_config, listed_entry
from chipbench.tests.test_recorded_runs import sources_of
from chipbench.tests.test_rehearsal import rehearse

CELL = "mesh4-exact-80m"
ONE_CHIP = "exact-hashed-20m"
READERS = (directory_load_max_pct, device_busy_max_pct)
ENTRIES = _directory.FAMILY + "entries"
CAPACITY = _directory.FAMILY + "capacity"


def scrape(*lines) -> dict:
    return promtext.parse("\n".join(lines))


# ------------------------------------------------------- made by hand

def test_the_fullest_slice_on_a_scrape_made_by_hand():
    held = (5_000_000, 5_400_000, 4_900_000, 5_100_000)
    end = scrape(*(f'{ENTRIES}{{shard="{i}"}} {n}'
                   for i, n in enumerate(held)),
                 *(f'{CAPACITY}{{shard="{i}"}} {1 << 26}' for i in range(4)),
                 f'{_directory.FAMILY}lookups_total{{shard="1"}} 9e7')
    sources = {"metrics_end": end}
    assert directory_load_max_pct.read(sources) \
        == 100.0 * 5_400_000 / (1 << 26)
    # The host's total beside it: the mean over equal slices.
    assert directory_load_pct.read(sources) \
        == 100.0 * sum(held) / (4 << 26)
    assert directory_load_max_pct.read(sources) \
        > directory_load_pct.read(sources)
    # One table: the two agree.
    one = {"metrics_end": scrape(f'{ENTRIES}{{shard="0"}} 300',
                                 f'{CAPACITY}{{shard="0"}} 1000')}
    assert directory_load_max_pct.read(one) \
        == directory_load_pct.read(one) == 30.0
    # A slice whose capacity sample is missing or 0 is left out, not
    # divided by.
    odd = {"metrics_end": scrape(f'{ENTRIES}{{shard="0"}} 5',
                                 f'{ENTRIES}{{shard="1"}} 50',
                                 f'{CAPACITY}{{shard="1"}} 100',
                                 f'{CAPACITY}{{shard="2"}} 0',
                                 f'{ENTRIES}{{shard="2"}} 0')}
    assert directory_load_max_pct.read(odd) == 50.0


def test_the_busiest_chip_on_a_trace_made_by_hand():
    trace = {"idle_pct": 75.0, "devices": [
        {"plane": "/device:TPU:0", "busy_s": 1.5, "idle_pct": 70.0},
        {"plane": "/device:TPU:1", "busy_s": 1.0, "idle_pct": 80.0},
        {"plane": "/device:TPU:2", "busy_s": 1.2, "idle_pct": 76.0},
        {"plane": "/device:TPU:3", "busy_s": 1.3, "idle_pct": 74.0}]}
    sources = {"trace": trace}
    assert device_busy_max_pct.read(sources) == 30.0
    assert device_idle_pct.read(sources) == 75.0      # the mean, beside it
    # A plane whose window was empty reads None there: left out.
    trace["devices"].append({"plane": "/device:TPU:4", "idle_pct": None})
    assert device_busy_max_pct.read(sources) == 30.0


def test_without_the_sources_they_give_nothing_and_do_not_raise():
    for sources in ({"metrics_end": scrape("up 1"), "trace": None},
                    {"metrics_end": {}, "trace": {"devices": []}},
                    {"metrics_end": {}, "trace": {}}):
        for reader in READERS:
            assert reader.read(sources) is None
    # A program before PR 33 (no directory families), under the new cell.
    old = sources_of("pr32_c3_traced", CELL)
    assert directory_load_max_pct.read(old) is None
    # Its capture has device planes all the same: a number, not a raise.
    assert 0.0 <= device_busy_max_pct.read(old) <= 100.0


def test_on_the_one_chip_recording_they_agree_with_their_siblings():
    """PR 42's traced run of ``exact-hashed-20m``: one slice, one chip,
    so the fullest slice is the table and the busiest chip the chip."""
    sources = sources_of("pr42_20m_traced", ONE_CHIP)
    assert directory_load_max_pct.read(sources) \
        == pytest.approx(directory_load_pct.read(sources))
    assert device_busy_max_pct.read(sources) \
        == pytest.approx(100.0 - device_idle_pct.read(sources))


# ------------------------------------------------------------ manifest

@pytest.fixture()
def published(monkeypatch):
    """The published geometry, whatever JAX_PLATFORMS says here."""
    monkeypatch.setattr(runner, "REHEARSAL", False)


def test_their_manifest_entries_and_where_they_apply(published):
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for reader in READERS:
        entry, on = listed_entry(reader)
        # The dense backend on more than one chip, closed loop: a
        # predicate over the cell, which today picks this one.
        assert on == [CELL] and entry["moves"] == "decisions_per_s"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "exact-tb-mesh4",
                    "traffic": "hashed-sat-z099", "chips": 4}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(bench["workloads"]) // 2
    # Everything the one-chip deployment reports, and everything the
    # sketch mesh behind the host router reports, this cell reports too.
    for m in bench["per_layer"] + bench["end_to_end"]:
        listed = m.get("workloads", ())
        if ONE_CHIP in listed or "mesh4-hashed-mixed" in listed:
            assert CELL in listed, m["name"]
    # No reclaim reader applies: the table holds every key.
    assert not _reclaim.applies(runner.load_cell(CELL))


def test_the_configuration_is_four_of_exact_tb_20m(published):
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == "exact-tb-mesh4")
    check_config(entry, runner.ROOT)
    assert entry["reduced"] == ["key_population", "chips"]
    assert "YCSB" in entry["source"] and "tokenbucket.go" in entry["source"]
    cfg = runner.load_cell(CELL)["config"]
    one = runner.load_cell(ONE_CHIP)["config"]
    assert cfg["chips"] == 4 and one["chips"] == 1
    assert cfg["key_population"] == 4 * one["key_population"] == 80_000_000
    assert cfg["capacity_a_chip"] == one["capacity"] == 1 << 26
    assert cfg["capacity"] == 4 * cfg["capacity_a_chip"]
    assert cfg["rehearsal"]["capacity"] \
        == 4 * cfg["rehearsal"]["capacity_a_chip"]
    for key in ("algorithm", "limit", "window_s", "byte_model", "lanes",
                "probe_bound", "published_population"):
        assert cfg[key] == one[key], key
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # The guarantees are the one-chip deployment's; placement and
    # durability say what four chips change.
    for key in ("admission", "never_seen", "policy"):
        assert cfg["guarantees"][key] == one["guarantees"][key]
    assert "on no other" in cfg["guarantees"]["placement"]
    assert cfg["guarantees"]["durability"].startswith("none claimed")
    # --backend dense stays ADJACENT (what _directory.dense reads), the
    # router is the server's default and out of the flags.
    flags = cfg["server_flags"]
    assert flags[flags.index("--backend") + 1] == "dense"
    assert flags[flags.index("--mesh-devices") + 1] == "4"
    assert "--router" not in flags and "router" in cfg["assumed"]
    # The byte model is a chip's decision's, whatever the table's size.
    assert need.step_bytes(cfg, 4096, 100.0) \
        == need.step_bytes(one, 4096, 100.0) == 4096 * 96.125
    assert runner.load_cell(CELL)["traffic"] \
        == runner.load_cell(ONE_CHIP)["traffic"]


# ------------------------------------------------------------- rehearsal

def test_rehearsal_untraced_on_four_virtual_devices():
    done, lines = rehearse(runner.ROOT, CELL, 0)
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    assert lines["rehearsal"]["device"]["count"] == 4
    assert lines["rehearsal"]["metric_names"] == ["decisions_per_s",
                                                  "setup_s"]
    banner = lines["server"]["banner"]
    assert "slice_devices=0,1,2,3" in banner
    assert "dense_capacity=16384" in banner        # a slice's entries
    assert lines["probe"]["allowed_per_hot_key"] == [100]
    assert lines["probe"]["fresh_allowed"] == 1
    assert lines["checks"]["cold"]["denied"] == 0
    assert lines["checks"]["cold"]["policy"] == 0
    assert lines["checks"]["failures"] == []
    assert lines["loadgen"]["top_allowed_max"] \
        <= lines["loadgen"]["admitted_cap"]
    # Every slice was sent rows: the generator carries the owner rule.
    assert len(lines["loadgen"]["slice_sent"]) == 4
    assert all(n > 0 for n in lines["loadgen"]["slice_sent"])


def test_rehearsal_traced_reports_the_slices():
    done, lines = rehearse(runner.ROOT, CELL, 1, seconds="3")
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    names = lines["rehearsal"]["metric_names"]
    for name in ("directory_load_max_pct", "directory_load_pct",
                 "directory_unplaced_pct", "directory_probes_per_lookup",
                 "directory_inserts_per_dispatch", "state_resident_mb",
                 "dispatches_per_frame", "slice_imbalance",
                 "dispatch_us_exact", "fetches_per_dispatch", "prewarm_s"):
        assert name in names, name
    assert "unlisted_layer_metrics" not in lines
    # Each family once a slice, read off the run's own closing scrape.
    out = os.path.join(runner.HERE, "out", f"{CELL}-5-1")
    with open(os.path.join(out, "metrics_end.txt")) as fh:
        samples = promtext.parse(fh.read())
    for family in (ENTRIES, CAPACITY, "rate_limiter_state_resident_bytes"):
        shards = sorted(dict(lab)["shard"] for (name, lab) in samples
                        if name == family)
        assert shards == list("0123"), family
    assert promtext.total(samples, CAPACITY) == 4 * 16384
    assert promtext.total(samples, _directory.FAMILY + "unplaced_total") == 0
