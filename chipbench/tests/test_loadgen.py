"""The generator's sampler: Zipf rank frequencies, the fixed rank -> id
permutation and seed determinism, through ``--dump-ids``."""

import subprocess

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
