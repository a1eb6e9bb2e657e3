"""The generator's sampler: Zipf rank frequencies, the fixed rank -> id
permutation and seed determinism, through ``--dump-ids``."""

import hashlib
import json
import os
import re
import subprocess
import time

import pytest

from chipbench import runner


@pytest.fixture(scope="module")
def binary():
    return runner.build_loadgen()[0]


def dump(binary, seed, n=200_000, keys=1000, s=1.1, base=0):
    out = subprocess.run(
        [binary, "--keys", str(keys), "--zipf-s", str(s), "--seed", str(seed),
         "--id-base", str(base), "--dump-ids", str(n)],
        capture_output=True, text=True, check=True).stdout
    return [tuple(int(x) for x in line.split()) for line in out.splitlines()]


def test_build_is_cached(binary):
    again, seconds = runner.build_loadgen()
    assert again == binary and seconds == 0.0


def test_same_seed_same_stream(binary):
    assert dump(binary, 7, n=5000) == dump(binary, 7, n=5000)
    assert dump(binary, 7, n=5000) != dump(binary, 8, n=5000)


def test_zipf_rank_frequencies(binary):
    rows = dump(binary, 3)
    n, keys, s = len(rows), 1000, 1.1
    norm = sum((k + 1) ** -s for k in range(keys))
    counts = {}
    for rank, _ in rows:
        counts[rank] = counts.get(rank, 0) + 1
    for rank in (0, 1, 2, 9, 99):
        want = n * (rank + 1) ** -s / norm
        assert abs(counts.get(rank, 0) - want) < 5 * want ** 0.5 + 1, rank
    assert max(counts) < keys


def test_uniform_when_s_is_zero(binary):
    rows = dump(binary, 3, n=100_000, keys=10, s=0)
    for rank in range(10):
        assert abs(sum(1 for r, _ in rows if r == rank) - 10_000) < 500


def test_rank_to_id_is_one_permutation_whatever_the_seed(binary):
    rows = dump(binary, 5, n=100_000, keys=64, base=1000)
    ids = {}
    for rank, key in rows:
        assert ids.setdefault(rank, key) == key     # one id per rank
    assert sorted(ids.values()) == list(range(1000, 1064))   # a permutation
    assert [ids[r] for r in range(64)] != list(range(1000, 1064))
    # The seed draws the stream, not the population: which id is hot, and
    # so which slice of a mesh it loads, is the same in every run.
    other = dict(dump(binary, 6, n=100_000, keys=64, base=1000))
    assert other == ids
    assert rows[:100] != dump(binary, 6, n=100, keys=64, base=1000)


def test_bad_options_are_refused(binary):
    done = subprocess.run([binary, "--keys", "10", "--port", "1", "--bogus",
                           "1"], capture_output=True, text=True)
    assert done.returncode == 2 and "bogus" in done.stderr


# ------------------------------------------ the stream is the parent's

with open(os.path.join(os.path.dirname(__file__), "data",
                       "dump_ids.json")) as _fh:
    RECORDED = json.load(_fh)["streams"]


@pytest.mark.parametrize(
    "stream", RECORDED,
    ids=[f"{s['keys']}-{s['zipf_s']}-{s['seed']}" for s in RECORDED])
def test_the_stream_of_a_population_skew_and_seed_is_the_recorded_one(
        binary, stream):
    """``--dump-ids`` against data/dump_ids.json (the parent's generator,
    PR 49): alias table, permutation, samplers and seeds byte for byte —
    which ids are hot decides ``slice_imbalance`` and every mesh cell."""
    out = subprocess.run(
        [binary, "--keys", str(stream["keys"]), "--zipf-s",
         str(stream["zipf_s"]), "--seed", str(stream["seed"]), "--dump-ids",
         str(stream["n"])], capture_output=True, check=True).stdout
    assert out.decode().splitlines()[:6] == stream["first"]
    assert hashlib.sha256(out).hexdigest() == stream["sha256"]


# -------------------------------- no window the generator was not sending in

def monotonic_s() -> float:
    return time.clock_gettime(time.CLOCK_MONOTONIC)


def test_tables_ready_after_start_at_plus_warmup_end_in_exit_4(binary):
    """A hand run whose ``--start-at`` + ``--warmup`` passed while the
    tables were built measures nothing: exit 4, naming the keys and the
    build's seconds (the parent opened its window all the same)."""
    done = subprocess.run(
        [binary, "--keys", "3000000", "--port", "1", "--warmup", "0.05",
         "--start-at", f"{monotonic_s() + 0.01:.6f}"],
        capture_output=True, text=True)
    assert done.returncode == 4 and done.stdout == ""
    assert "3000000 keys" in done.stderr
    assert re.search(r"took \d+\.\d+ s to build", done.stderr)
    # ... and one that is ready inside the warm-up goes on (to a port
    # nothing listens on: exit 1 is its connection's, not the schedule's).
    done = subprocess.run(
        [binary, "--keys", "1000", "--port", "1", "--warmup", "0.2",
         "--seconds", "0.1", "--start-at", f"{monotonic_s() + 0.05:.6f}"],
        capture_output=True, text=True)
    assert done.returncode == 1 and "connection" in done.stderr


def test_await_start_says_ready_and_takes_its_schedule_from_stdin(binary):
    proc = subprocess.Popen(
        [binary, "--keys", "200000", "--await-start", "1",
         "--warmup", "0.5", "--seconds", "0.25", "--drain", "0.1"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    ready = json.loads(proc.stdout.readline())
    assert ready["line"] == "ready" and ready["keys"] == 200000
    assert 0 < ready["build_s"] < 30 and ready["peak_rss_bytes"] > 200000 * 24
    assert ready["t_ready"] <= monotonic_s()
    start = monotonic_s() + 0.2
    proc.stdin.write(f"{start:.6f} 1\n")       # the instant and the port
    proc.stdin.flush()
    schedule = json.loads(proc.stdout.readline())
    assert schedule == {"line": "schedule", "t_start": round(start, 6),
                        "t_window_start": pytest.approx(start + 0.5),
                        "t_window_end": pytest.approx(start + 0.75)}
    assert schedule["t_window_start"] >= ready["t_ready"] + 0.5
    _, err = proc.communicate(timeout=30)
    assert proc.returncode == 1 and "connection" in err   # port 1


@pytest.mark.parametrize("given, code, said", [
    ("1.0 7\n", 4, "the start instant given on stdin is past"),
    ("", 2, 'no "<T> <port>" on stdin'),
    ("99999999.0\n", 2, 'no "<T> <port>" on stdin'),
], ids=["past", "none", "no-port"])
def test_await_start_refuses_an_instant_it_cannot_keep(binary, given, code,
                                                       said):
    done = subprocess.run(
        [binary, "--keys", "1000", "--await-start", "1"],
        input=given, capture_output=True, text=True)
    assert done.returncode == code and said in done.stderr
    assert [json.loads(ln)["line"] for ln in done.stdout.splitlines()] \
        == ["ready"]


@pytest.mark.parametrize("flag, value", [("--start-at", "5"), ("--port", "1")])
def test_await_start_takes_its_instant_and_port_from_stdin_alone(binary, flag,
                                                                  value):
    done = subprocess.run(
        [binary, "--keys", "10", "--await-start", "1", flag, value],
        input="99999999.0 1\n", capture_output=True, text=True)
    assert done.returncode == 2 and done.stdout == ""
