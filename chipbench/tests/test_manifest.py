"""BENCHMARK.json against the contract's rules of form, the data files
it names, and the shape of the result line."""

import json
import os
import re

import pytest

from chipbench import layers, runner

ROOT = runner.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
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


def test_configs_are_files_with_their_geometry_in_the_flags(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for need in ("server_flags", "algorithm", "limit", "window_s",
                     "depth", "width", "key_population", "chips",
                     "guarantees", "assumed", "rehearsal"):
            assert need in cfg, (c["name"], need)
        for flags, geo in ((cfg["server_flags"], cfg),
                           (cfg["rehearsal"]["server_flags"],
                            {**cfg, **cfg["rehearsal"]})):
            flag = {a: b for a, b in zip(flags, flags[1:] + [""])
                    if a.startswith("--")}
            assert "--native" in flag
            assert int(flag["--sketch-depth"]) == geo["depth"]
            assert int(flag["--sketch-width"]) == geo["width"]
            assert int(flag["--limit"]) == geo["limit"]
            assert float(flag["--window"]) == geo["window_s"]
            assert flag["--algorithm"] == geo["algorithm"]
            # width/4 keys per slice: the false-deny bound at any rate.
            assert geo["key_population"] == geo["width"] // 4 * geo["chips"]
        assert "--snapshot-dir" not in cfg["server_flags"]


def test_manifest_matches_the_reader_files(bench):
    """Every per-layer entry has its reader, with the same META, and its
    cells are those the reader's predicate picks."""
    readers = {m.META["name"]: m.META for m in layers.load()}
    cells = {w["name"]: runner.load_cell(w["name"])
             for w in bench["workloads"]}
    assert {m["name"] for m in bench["per_layer"]} == set(readers)
    for m in bench["per_layer"]:
        meta = readers[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == meta[key], (m["name"], key)
        on = [n for n, c in cells.items() if meta["applies"](c)]
        assert m.get("workloads", list(cells)) == on, m["name"]


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
