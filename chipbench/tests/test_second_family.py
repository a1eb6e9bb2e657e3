"""A configuration of another family fits the harness as FILES: a made-up
``--backend dense`` deployment with a byte model of its own
(``byte_model: "ring"``) and ``geometry_flags`` (data/second_family/),
its byte model beside it on this test's own path, laid BESIDE the real
families — the sketches and, since PR 33, the real exact tables.
``load_cell``, the manifest's checks and ``step_roofline`` on a recorded
fixture take it; without a byte model it is refused in words. Nothing of
it is listed in BENCHMARK.json, and it takes no name a real file has."""

import json
import os
import shutil
import sys

import pytest

import chipbench
from chipbench import bytes as need
from chipbench import layers, runner
from chipbench.layers import step_roofline
from chipbench.tests import test_manifest, test_readers_old_program

DATA = os.path.join(os.path.dirname(__file__), "data", "second_family")
CELL = "ring-hashed-sat"


@pytest.fixture()
def family(tmp_path, monkeypatch):
    """A checkout's manifest and data with the second family ADDED: a
    configuration file, entries, and the cell's name at the end of the
    lists of the metrics it reports. No file that was there is edited.
    Returns (root, manifest)."""
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(runner.HERE, sub),
                        tmp_path / "chipbench" / sub)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(DATA, "ring-tb.json"),
                tmp_path / "chipbench" / "configs" / "ring-tb.json")
    with open(os.path.join(DATA, "ring-tb.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "ring-tb", "source": cfg["source"],
        "file": "chipbench/configs/ring-tb.json",
        "reduced": cfg["reduced"], "why": "test data"})
    bench["workloads"].append({"name": CELL, "config": "ring-tb",
                               "traffic": "hashed-sat", "chips": 1,
                               "why": "test data"})
    facts = {"name": CELL, "chips": 1, "config": cfg,
             "traffic": {**runner.TRAFFIC_DEFAULTS, "loop": "closed"}}
    applies = {m.META["name"]: m.META["applies"] for m in layers.load()}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and (m["name"] == "decisions_per_s"
                                 or (m["name"] in applies
                                     and applies[m["name"]](facts))):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # chipbench/bytes_ring.py as a later PR would add it: here it lives
    # under the test's data, put on the package's path.
    monkeypatch.setattr(chipbench, "__path__",
                        list(chipbench.__path__) + [DATA])
    monkeypatch.delitem(sys.modules, "chipbench.bytes_ring", raising=False)
    yield str(tmp_path), bench
    assert {p: p.read_bytes() for p in before} == before
    sys.modules.pop("chipbench.bytes_ring", None)


def test_nothing_of_it_is_in_the_benchmark():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        text = fh.read()
    assert "ring-tb" not in text and "second_family" not in text
    assert CELL not in text
    assert not os.path.exists(os.path.join(runner.HERE, "bytes_ring.py"))
    assert not os.path.exists(os.path.join(runner.HERE, "configs",
                                           "ring-tb.json"))


def test_load_cell_takes_it(family):
    root, _ = family
    cell = runner.load_cell(CELL, root)
    assert cell["config"]["byte_model"] == "ring"
    assert "depth" not in cell["config"] and "width" not in cell["config"]
    assert need.model_of(cell["config"]).__name__ == "chipbench.bytes_ring"


def test_the_manifests_checks_take_it(family):
    root, bench = family
    test_manifest.test_names_units_and_lines(bench)
    test_manifest.test_every_cell_reports_what_the_contract_asks(bench)
    for entry in bench["configs"]:
        test_manifest.check_config(entry, root)
    test_manifest.check_readers_match(bench, root)


def test_step_roofline_reads_through_its_own_model(family):
    root, _ = family
    sources = test_readers_old_program._sources("wide-hashed-sat", "half",
                                                True)
    sources["cell"] = runner.load_cell(CELL, root)
    batch = layers.dispatch_batch_mean.read(sources)
    step_us = layers.device_us_per_dispatch.read(sources)
    # 12 B of probe + 32 B of slot + 12 B in + 24.125 B out a decision.
    assert step_roofline.read(sources) == pytest.approx(
        100.0 * (batch * 80.125 / 819e9) / (step_us * 1e-6))
    assert need.step_ops(sources["cell"]["config"], 100) == 2300
    got = runner.per_layer(sources["cell"], sources)
    assert "step_roofline" in got


@pytest.mark.parametrize("spoil, said", [
    (lambda c: c.pop("byte_model"), "byte_model"),
    (lambda c: c.update(byte_model="heap"), "bytes_heap"),
], ids=["no-model-no-depth", "model-without-module"])
def test_without_its_model_it_is_refused_in_words(family, spoil, said):
    root, bench = family
    path = os.path.join(root, "chipbench", "configs", "ring-tb.json")
    with open(path) as fh:
        cfg = json.load(fh)
    spoil(cfg)
    with open(path, "w") as fh:
        json.dump(cfg, fh)
    with pytest.raises(runner.RunFailure, match=said):
        runner.load_cell(CELL, root)
    with pytest.raises(AssertionError, match=said):
        test_manifest.check_config(bench["configs"][-1], root)
    sources = test_readers_old_program._sources("wide-hashed-sat", "half",
                                                True)
    sources["cell"] = dict(sources["cell"], config=cfg)
    with pytest.raises(need.NoByteModel, match=said) as err:
        step_roofline.read(sources)
    assert not isinstance(err.value, KeyError)


@pytest.mark.parametrize("spoil, said", [
    (lambda c: c.pop("geometry_flags"), "geometry_flags"),
    (lambda c: c.update(capacity=4096), "--dense-capacity"),
    (lambda c: c["geometry_flags"].update({"--dense-rows": "capacity"}),
     "--dense-rows"),
    (lambda c: c["guarantees"].update(false_deny="<= 1 %"),
     "false_deny_bound"),
    (lambda c: c["server_flags"].extend(["--snapshot-dir", "/x"]),
     "snapshot"),
], ids=["no-geometry-flags", "flag-disagrees", "flag-not-passed",
        "false-deny-unexplained", "snapshot-dir"])
def test_what_holds_for_every_family_still_holds(family, spoil, said):
    root, bench = family
    path = os.path.join(root, "chipbench", "configs", "ring-tb.json")
    with open(path) as fh:
        cfg = json.load(fh)
    spoil(cfg)
    with open(path, "w") as fh:
        json.dump(cfg, fh)
    with pytest.raises(AssertionError, match=said):
        test_manifest.check_config(bench["configs"][-1], root)
