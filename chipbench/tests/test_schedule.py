"""The generator's schedule (PR 50): it builds its tables, says ``ready``
and is THEN given the port and the instant its warm-up starts, so every
worker gets the whole warm-up whatever ``key_population`` is, and the
build rides under the server's start. ``runner.Generator`` on stub binaries (one that never
says ``ready``, one that exits first), and a rehearsal of a real cell at
5,000,000 keys."""

import json
import os
import stat
import time

import pytest

from chipbench import runner
from chipbench.tests.test_rehearsal import checkout_copy, rehearse

CELL = {"chips": 1,
        "config": {"key_population": 80_000_000},
        "traffic": {**runner.TRAFFIC_DEFAULTS}}


def stub(tmp_path, body: str) -> str:
    path = tmp_path / "loadgen-stub"
    path.write_text("#!/bin/sh\n" + body)
    path.chmod(path.stat().st_mode | stat.S_IXUSR)
    return str(path)


def test_loadgen_args_await_the_schedule_unless_a_hand_gives_one():
    args = runner.loadgen_args(CELL, None, 9, 20.0)
    assert args[args.index("--await-start") + 1] == "1"
    assert "--start-at" not in args and "--port" not in args
    assert args[args.index("--keys") + 1] == "80000000"
    assert args[args.index("--warmup") + 1] == str(runner.WARMUP_S)
    by_hand = runner.loadgen_args(CELL, 4000, 9, 20.0, 1234.5)
    assert by_hand[by_hand.index("--start-at") + 1] == "1234.500000"
    assert "--await-start" not in by_hand
    pairs, hand = (dict(zip(a[::2], a[1::2])) for a in (args, by_hand))
    del pairs["--await-start"], hand["--start-at"], hand["--port"]
    assert pairs == hand                      # nothing else differs


def test_no_ready_by_the_ceiling_raises_run_failure_naming_the_keys(
        tmp_path, monkeypatch):
    assert runner.READY_CEILING_S == 120.0
    monkeypatch.setattr(runner, "READY_CEILING_S", 0.5)
    gen = runner.Generator(stub(tmp_path, "exec sleep 30\n"), CELL, 9, 1.0)
    with pytest.raises(runner.RunFailure) as err:
        gen.start(4000)
    assert "80000000 keys" in str(err.value) and "'ready'" in str(err.value)
    assert "ceiling 0.5 s" in str(err.value)
    assert gen.proc.poll() is not None          # nothing is left running


def test_a_ready_said_in_time_is_read_however_late_start_is_called(
        tmp_path, monkeypatch):
    """``start`` comes a server's start after the spawn (a checkout's
    first run compiles for 140-630 s, past the ceiling): the ``ready``
    line written in time sits in the pipe and is the answer, not a
    deadline judged before the pipe is read."""
    monkeypatch.setattr(runner, "READY_CEILING_S", 0.2)
    body = ("echo '{\"line\": \"ready\", \"keys\": 80000000, \"build_s\": 0.01,"
            " \"t_ready\": 1.0, \"peak_rss_bytes\": 1}'\nread t port\n"
            "echo '{\"line\": \"schedule\", \"t_start\": 10.0,"
            " \"t_window_start\": 13.0, \"t_window_end\": 14.0}'\n"
            "echo '{\"completed\": 3}'\n")
    gen = runner.Generator(stub(tmp_path, body), CELL, 9, 1.0)
    time.sleep(0.6)                       # the server's start, past the ceiling
    assert time.monotonic() > gen.t_spawn + runner.READY_CEILING_S
    assert gen.start(4321)["t_window_start"] == 13.0
    assert gen.result() == {"completed": 3}


def test_a_generator_that_exits_before_ready_is_told_with_its_words(tmp_path):
    gen = runner.Generator(
        stub(tmp_path, "echo 'loadgen: out of memory' >&2\nexit 4\n"), CELL,
        9, 1.0)
    with pytest.raises(runner.RunFailure,
                       match="exited 4 before its 'ready' line") as err:
        gen.start(4000)
    assert "out of memory" in str(err.value)


def test_the_window_is_read_from_the_generators_own_words(tmp_path):
    """The stub answers a schedule of its own (not start + 3 s): the
    runner's window is that one."""
    body = ("echo '{\"line\": \"ready\", \"keys\": 80000000, \"build_s\": 7.4,"
            " \"t_ready\": 1.0, \"peak_rss_bytes\": 1}'\nread t port\n"
            "test \"$port\" = 4321 || exit 9\n"
            "echo '{\"line\": \"schedule\", \"t_start\": 10.0,"
            " \"t_window_start\": 17.5, \"t_window_end\": 18.5}'\n"
            "echo '{\"completed\": 3}'\n")
    gen = runner.Generator(stub(tmp_path, body), CELL, 9, 1.0)
    assert gen.start(4321) == {"line": "schedule", "t_start": 10.0,
                           "t_window_start": 17.5, "t_window_end": 18.5}
    assert gen.result() == {"completed": 3}
    # A line out of turn is no schedule.
    gen = runner.Generator(stub(tmp_path, "echo '{\"line\": \"schedule\"}'\n"),
                           CELL, 9, 1.0)
    with pytest.raises(runner.RunFailure, match="'ready' line was due"):
        gen.start(4000)


def test_rehearsal_at_five_million_keys_builds_under_the_servers_start(
        tmp_path):
    """A real cell's rehearsal with the population raised to 5 M keys (the
    sketch's false-deny bound hangs on the keys that SATURATE, a few
    hundred in a rehearsal's seconds): the window starts a whole warm-up
    after ``ready``, the generator was spawned before the server and built
    beside its start (the probe found it ready), and the first second is
    not short."""
    copy = checkout_copy(tmp_path)
    path = copy / "chipbench/configs/added/cms-c3.json"
    cfg = json.loads(path.read_text())
    cfg["rehearsal"]["key_population"] = 5_000_000
    path.write_text(json.dumps(cfg))
    done, lines = rehearse(str(copy), "c3-hashed-sat", 0, seconds="3")
    assert done.returncode == 3, done.stderr[-3000:]
    assert lines["rehearsal"]["correct"] is True
    with open(copy / "chipbench/out/c3-hashed-sat-5-0/loadgen.json") as fh:
        gen = json.load(fh)
    assert gen["keys"] == 5_000_000 and gen["build_s"] > 0.05
    assert gen["t_start"] >= gen["t_ready"]
    assert gen["t_window_start"] >= gen["t_ready"] + runner.WARMUP_S
    assert gen["t_window_start"] == pytest.approx(
        gen["t_start"] + runner.WARMUP_S)
    setup = lines["setup"]
    assert setup["gen_build_s"] == gen["build_s"]
    assert setup["gen_peak_rss_bytes"] > 5_000_000 * 24
    # Spawned BEFORE the server: its build began a server's start before
    # the probe and was over when the probe was, so set-up holds none of it.
    began = gen["t_ready"] - gen["build_s"]
    assert began < gen["t_start"] - lines["server"]["start_s"]
    assert gen["t_ready"] < gen["t_start"] - setup["probe_s"]
    assert setup["gen_wait_s"] == 0.0
    assert lines["holes"]["seconds"] == []      # the first one included
    assert lines["per_second"]["slices"][0]["completed"] > 0
