"""BENCHMARK.json against the contract's rules of form, the data files
it names, and the shape of the result line."""

import json
import os
import re

import pytest

from chipbench import bytes as need
from chipbench import layers, runner

ROOT = runner.ROOT
NAME = need.NAME
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert 2 <= len(bench["workloads"]) <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for entry in bench["configs"] + bench["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    for cell in cells:
        on = [m["name"] for m in bench["end_to_end"]
              if cell in m.get("workloads", cells)]
        assert "setup_s" in on and len(on) >= 2, cell
        layer = [m for m in bench["per_layer"]
                 if cell in m.get("workloads", cells)]
        assert layer, cell
        for m in layer:      # a per-layer metric only where its `moves` is
            assert m["moves"] in on, (cell, m["name"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


#: What every configuration file carries, whatever its state is.
CONFIG_KEYS = ("server_flags", "algorithm", "limit", "window_s",
               "key_population", "chips", "guarantees", "assumed",
               "rehearsal")
#: Backends whose state is a count-min sketch: their geometry is
#: depth x width, and their false-deny bound follows from it.
SKETCH_BACKENDS = ("sketch", "mesh")


def flag_values(flags: list) -> dict:
    """``--flag value`` pairs of a server's flag list (a flag without a
    value maps to whatever follows it; only its presence is read)."""
    return {a: b for a, b in zip(flags, flags[1:] + [""])
            if a.startswith("--")}


def check_config(entry: dict, root: str) -> None:
    """One ``configs`` entry of a manifest against its file. No branch
    names a configuration: what differs between families hangs on the
    value of ``--backend`` and on the file's own ``byte_model`` and
    ``geometry_flags``."""
    assert entry["file"].startswith("chipbench/configs/")
    with open(os.path.join(root, entry["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in CONFIG_KEYS:
        assert key in cfg, (entry["name"], key)
    try:
        need.model_of(cfg)      # none named: the sketch's, on depth x width
    except need.NoByteModel as exc:
        raise AssertionError(f"{entry['name']}: {exc}") from exc
    assert "--snapshot-dir" not in cfg["server_flags"]
    backend = flag_values(cfg["server_flags"]).get("--backend", "sketch")
    for flags, geo in ((cfg["server_flags"], cfg),
                       (cfg["rehearsal"]["server_flags"],
                        {**cfg, **cfg["rehearsal"]})):
        flag = flag_values(flags)
        assert "--native" in flag
        assert flag.get("--backend", "sketch") == backend
        assert int(flag["--limit"]) == geo["limit"]
        assert float(flag["--window"]) == geo["window_s"]
        assert flag["--algorithm"] == geo["algorithm"]
        if backend in SKETCH_BACKENDS:
            assert int(flag["--sketch-depth"]) == geo["depth"]
            assert int(flag["--sketch-width"]) == geo["width"]
            # width/4 keys per slice IS guarantees.false_deny: with every
            # key saturated a never-seen key is denied with probability
            # (1 - e^-0.25)^depth, under 1 % from depth 3, at any rate.
            assert geo["key_population"] == geo["width"] // 4 * geo["chips"]
            continue
        pairs = cfg.get("geometry_flags")
        assert pairs, (
            f"{entry['name']}: a backend that is no sketch names its "
            f"geometry_flags (server flag -> configuration key)")
        for name, key in pairs.items():
            assert name.startswith("--") and name in flag, (name, flags)
            assert float(flag[name]) == geo[key], (name, key)
    # Another state gives another bound, or none: an exact table denies no
    # never-seen key while it has room. A file that still promises
    # false_deny says how its population bounds it.
    if backend not in SKETCH_BACKENDS and "false_deny" in cfg["guarantees"]:
        assert cfg.get("false_deny_bound"), (
            f"{entry['name']}: guarantees.false_deny without "
            f"false_deny_bound (how key_population bounds it)")


def test_configs_are_files_with_their_geometry_in_the_flags(bench):
    for c in bench["configs"]:
        check_config(c, ROOT)


def test_a_configuration_added_without_the_programs_tests_is_set_apart(bench):
    """tools/lowered_steps.py lowers every ``chipbench/configs/*.json``
    as a sketch and tier-1 (tests/test_policy.py::TestLoweredPrograms)
    pins that set of files to PR 22-27's four. A PR that may not edit
    tests/ or tools/ puts its configuration under ``configs/added/``,
    where that glob does not look (README, "Adding a configuration")."""
    pinned = {"bucket-c3", "cms-wide", "mesh4-c3", "mesh4-c3-coll"}
    top = {f[:-5] for f in os.listdir(os.path.join(runner.HERE, "configs"))
           if f.endswith(".json")}
    assert top == pinned
    for c in bench["configs"]:
        where = "chipbench/configs/" if c["name"] in pinned \
            else "chipbench/configs/added/"
        assert c["file"] == f"{where}{c['name']}.json"


def check_readers_match(bench: dict, root: str) -> None:
    """Every per-layer entry has its reader, with the same META, and its
    cells are those the reader's predicate picks."""
    readers = {m.META["name"]: m.META for m in layers.load()}
    cells = {w["name"]: runner.load_cell(w["name"], root)
             for w in bench["workloads"]}
    assert {m["name"] for m in bench["per_layer"]} == set(readers)
    for m in bench["per_layer"]:
        meta = readers[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == meta[key], (m["name"], key)
        on = [n for n, c in cells.items() if meta["applies"](c)]
        assert m.get("workloads", list(cells)) == on, m["name"]


def listed_entry(reader, root: str = ROOT) -> tuple:
    """(the reader's ``per_layer`` entry, the cells it applies to): the
    entry found BY NAME, held to the reader's META, and its ``workloads``
    to the reader's ``applies`` predicate over the manifest's cells as
    they are today — never to the cells of some PR's day or to a place
    in the list, which the next PR's mandatory append moves."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entry = {m["name"]: m for m in manifest["per_layer"]}[reader.META["name"]]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert entry[key] == reader.META[key], (entry["name"], key)
    cells = [w["name"] for w in manifest["workloads"]]
    on = [n for n in cells
          if reader.META["applies"](runner.load_cell(n, root))]
    assert entry.get("workloads", cells) == on, entry["name"]
    return entry, on


def test_manifest_matches_the_reader_files(bench):
    check_readers_match(bench, ROOT)


def test_traffic_files_use_known_fields(bench):
    for w in bench["workloads"]:
        cell = runner.load_cell(w["name"])
        assert set(cell["traffic"]) <= set(runner.TRAFFIC_DEFAULTS) | {"why"}
        want = ["decisions_per_s"] if cell["traffic"]["loop"] == "closed" \
            else ["latency_p50_ms", "latency_p99_ms"]
        have = [m["name"] for m in runner.cell_metrics(cell, "end_to_end")]
        assert sorted(have) == sorted(want + ["setup_s"]), w["name"]


def test_result_line_shape():
    cell = runner.load_cell("wide-string-rpc")
    gen = {"loop": "open", "sent": 400, "policy": 1, "error_decisions": 8,
           "unanswered": 16, "completed": 375, "window_s": 20.0,
           "latency_ms": {"p50": 1.25, "p99_median_of_seconds": 7.5}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    metrics = runner.end_to_end(cell, gen, 25.5)
    line = runner.result_line(True, gen, metrics, device)
    assert json.loads(json.dumps(line)) == {
        "correct": True, "attempted": 400, "failed": 25,
        "metrics": {"latency_p50_ms": {"value": 1.25, "unit": "ms"},
                    "latency_p99_ms": {"value": 7.5, "unit": "ms"},
                    "setup_s": {"value": 25.5, "unit": "s"}},
        "device": device}
    traced = runner.result_line(True, gen, {}, device,
                                {"device_ops": [], "idle_gaps": []})
    assert list(traced)[-1] == "breakdown"
    gen["loop"] = "closed"       # in flight at the window's end is not failed
    assert runner.result_line(True, gen, {}, device)["failed"] == 9


def test_peaks_know_the_v5e_and_nothing_by_default():
    with open(os.path.join(runner.HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert "cpu" not in peaks
