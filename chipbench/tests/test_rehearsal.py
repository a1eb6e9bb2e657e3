"""End to end on the CPU: a rehearsal of one cell at tiny geometry, and
the dry addition — a configuration, a mix, a per-layer metric and a cell
added as NEW files to a copy, with no file of the copy edited except the
manifest, which only gains entries."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import layers, runner

ROOT = runner.ROOT


def rehearse(root, workload, trace, seconds="2"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", workload, "--seed",
         "5", "--seconds", seconds, "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    return done, {ln["line"]: ln for ln in lines if "line" in ln}


@pytest.mark.parametrize("workload", ["wide-hashed-sat", "c3-hashed-sat"])
def test_rehearsal_of_one_cell_stays_off_jax_and_prints_no_result(workload):
    done, lines = rehearse(ROOT, workload, 0)
    assert done.returncode == 3, done.stderr[-3000:]
    # The runner asserts "jax" not in sys.modules before its last line.
    assert lines["rehearsal"]["correct"] is True
    assert lines["rehearsal"]["device"]["platform"] == "cpu"
    assert lines["rehearsal"]["metric_names"] == ["decisions_per_s", "setup_s"]
    assert lines["probe"]["allowed_per_hot_key"] == [100]
    assert lines["checks"]["failures"] == []
    last = done.stdout.strip().splitlines()[-1]
    assert not last.startswith("{") and "no result" in last
    assert "decisions_per_s" not in json.dumps(lines["loadgen"])
    assert lines["holes"]["held_s"] == len(lines["holes"]["seconds"])


def checkout_copy(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(os.path.join(ROOT, "chipbench"), copy / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ratelimiter_tpu"), copy / "ratelimiter_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy / "BENCHMARK.json")
    return copy


def test_the_control_a_server_that_breaks_the_stated_limit_is_not_correct(
        tmp_path):
    """The rest of a run with the timed path broken underneath: the
    configuration states limit 100 and the server is started with 120, so
    an answer is altered where it is produced (every key's 101st to 120th
    request is allowed). The probe's exact comparison sees it, `correct`
    is false and the run exits 1 with no result."""
    copy = checkout_copy(tmp_path)
    path = copy / "chipbench/configs/added/cms-c3.json"
    cfg = json.loads(path.read_text())
    flags = cfg["rehearsal"]["server_flags"]
    flags[flags.index("--limit") + 1] = "120"
    path.write_text(json.dumps(cfg))
    done, lines = rehearse(str(copy), "c3-hashed-sat", 0)
    assert done.returncode == 1, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is False
    failures = lines["checks"]["failures"]
    assert any("the reference says" in f for f in failures), failures
    assert not done.stdout.strip().splitlines()[-1].startswith("{")


def test_dry_addition_of_config_mix_metric_and_cell(tmp_path):
    copy = checkout_copy(tmp_path)
    before = {p: p.read_bytes() for p in (copy / "chipbench").rglob("*")
              if p.is_file() and ".build" not in p.parts}

    with open(os.path.join(ROOT, "chipbench/configs/cms-wide.json")) as fh:
        cfg = json.load(fh)
    cfg["source"] = "dry addition: a geometry no configuration has"
    cfg.update(depth=4, width=32768, key_population=8192)
    cfg["rehearsal"] = {"width": 4096, "key_population": 1024,
                        "server_flags": [
                            "--native", "--backend", "sketch", "--algorithm", "tpu_sketch",
                            "--limit", "100", "--window", "60",
                            "--sketch-depth", "4", "--sketch-width", "4096",
                            "--sub-windows", "60", "--max-batch", "256"]}
    (copy / "chipbench/configs/cms-dry.json").write_text(json.dumps(cfg))
    mix = {"lane": "hashed", "frame_keys": 4096, "loop": "open",
           "rate": 400000, "arrival": "uniform", "connections": 8,
           "rehearsal": {"frame_keys": 128, "rate": 20000}}
    (copy / "chipbench/traffic/hashed-open.json").write_text(json.dumps(mix))
    (copy / "chipbench/layers/allowed_share.py").write_text(
        'META = {"name": "allowed_share", "unit": "ratio", "better": "higher",'
        ' "layer": "client", "moves": "latency_p50_ms",'
        ' "source": "program_counter",'
        ' "applies": lambda cell: cell["traffic"]["arrival"] == "uniform"}\n'
        "def read(sources):\n"
        "    gen = sources['loadgen']\n"
        "    return gen['allowed'] / gen['completed']\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    old = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "cms-dry", "source": cfg["source"],
                             "file": "chipbench/configs/cms-dry.json",
                             "reduced": ["key_population"], "why": "dry"})
    bench["workloads"].append({"name": "c3-hashed-r80", "config": "cms-dry",
                               "traffic": "hashed-open", "chips": 1,
                               "why": "dry"})
    bench["per_layer"].append({"name": "allowed_share", "unit": "ratio",
                               "better": "higher", "layer": "client",
                               "moves": "latency_p50_ms",
                               "source": "program_counter",
                               "workloads": ["c3-hashed-r80"]})
    # The new cell joins each metric it reports: the open loop's end-to-end
    # metrics, and every reader whose predicate holds for it. Lists only grow.
    facts = {"name": "c3-hashed-r80", "chips": 1, "config": cfg,
             "traffic": {**runner.TRAFFIC_DEFAULTS, **mix}}
    applies = {m.META["name"]: m.META["applies"] for m in layers.load()}
    for m in bench["end_to_end"]:
        if m["name"].startswith("latency_"):
            m["workloads"].append("c3-hashed-r80")
    for m in bench["per_layer"][:-1]:
        if "workloads" in m and applies[m["name"]](facts):
            m["workloads"].append("c3-hashed-r80")
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    # Nothing that was there changed: entries were added, none edited.
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], bench[key]):
            grown = {k: v for k, v in now.items() if k != "workloads"}
            assert grown == {k: v for k, v in was.items() if k != "workloads"}
            assert now.get("workloads", [])[:len(was.get("workloads", []))] \
                == was.get("workloads", [])

    done, lines = rehearse(str(copy), "c3-hashed-r80", 1, seconds="3")
    assert done.returncode == 3, done.stderr[-3000:]
    names = lines["rehearsal"]["metric_names"]
    assert "allowed_share" in names and "gen_late_p99_ms" in names
    assert "dispatch_batch_mean_open" in names
    assert "dispatch_batch_mean" not in names        # the closed loop's
    assert lines["loadgen"]["loop"] == "open"
    after = {p: p.read_bytes() for p in before}
    assert after == before                            # no file was edited
