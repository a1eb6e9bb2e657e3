"""The dense backend's device-resident key directory (ISSUE 33, ADR-027),
held to the plain reference request for request.

``algorithms/exact.py::ExactLimiter`` (a Python dict and integers, no
JAX) is fed the same requests at the same instants; ``allowed``,
``remaining``, ``retry_after`` and ``reset_at`` must be EQUAL — the
arithmetic is integer, there is no tolerance — through the synchronous
string lane (``allow_batch``), the finalized-hash lane (``launch_hashed``
/ ``resolve``, several tickets in flight) and the raw-id lane
(``launch_ids``). The streams are built to hurt the directory: a table
three quarters full of four-lane buckets (long probes), one new key many
times in one batch, many new keys with one home bucket in one batch, a
batch that fills the last free entries, a full table (answered by policy,
nothing overwritten), reuse after ``prune`` and ``reset``, tombstones a
displaced key still has to walk past, both lanes on one bucket, overrides
set and cleared, snapshot -> restore.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from ratelimiter_tpu import (
    Algorithm,
    CheckpointError,
    Config,
    DenseParams,
    ManualClock,
    StorageUnavailableError,
    create_limiter,
)
from ratelimiter_tpu.ops import directory
from ratelimiter_tpu.ops.hashing import (
    hash_prefixed_u64,
    splitmix64,
    splitmix64_inv,
)

T0 = 1_700_000_000.5
ALGOS = {"bucket": Algorithm.TOKEN_BUCKET,
         "fixed": Algorithm.FIXED_WINDOW,
         "sliding": Algorithm.SLIDING_WINDOW}
every_rule = pytest.mark.parametrize("algo", list(ALGOS))


def pair(algo, *, capacity=64, lanes=4, probe_bound=16, limit=5,
         window=60.0, **kw):
    """(dense, exact, clock): one rule, one clock, two backends."""
    clock = ManualClock(T0)
    cfg = Config(algorithm=ALGOS[algo], limit=limit, window=window,
                 dense=DenseParams(capacity=capacity, lanes=lanes,
                                   probe_bound=probe_bound), **kw)
    return (create_limiter(cfg, backend="dense", clock=clock),
            create_limiter(cfg, backend="exact", clock=clock), clock)


def same(got, want, where=""):
    """All four result columns equal, no tolerance."""
    for col in ("allowed", "remaining", "retry_after", "reset_at"):
        np.testing.assert_array_equal(
            getattr(got, col), getattr(want, col), err_msg=f"{col} {where}")


def names(ids) -> list:
    return [f"id{int(i)}" for i in ids]


def bucket_of(ids, nb: int, w: int) -> np.ndarray:
    """Home bucket of finalized 64-bit hashes, by the directory's own
    function."""
    keys = directory.canon(jnp.asarray(np.asarray(ids, np.uint64)))
    return np.asarray(directory.home(keys, nb, w)[0])


def with_home(bucket: int, count: int, nb: int, w: int) -> np.ndarray:
    """``count`` distinct hashes whose home bucket is ``bucket``."""
    cand = np.arange(10_000, 10_000 + 400 * nb, dtype=np.uint64)
    hit = cand[bucket_of(cand, nb, w) == bucket]
    assert hit.shape[0] >= count
    return hit[:count]


def table(lim) -> np.ndarray:
    return lim._dir_keys()


# ------------------------------------------------------------ long probes

LANES = ("strings", "hashed-pipelined", "ids-pipelined")


@pytest.mark.parametrize("lane", LANES)
@every_rule
def test_48_keys_in_64_entries_of_4_lane_buckets(algo, lane):
    """Load 0.75 on 16 buckets of 4: probes run over several buckets.
    Thirty batches of 50 rows, with time passing, four tickets in flight
    on the pipelined lanes."""
    dense, exact, clock = pair(algo)
    rng = np.random.default_rng(7)
    pending = []
    for step in range(30):
        ids = rng.integers(0, 48, size=50).astype(np.uint64) + 1
        ns = rng.integers(1, 3, size=50).astype(np.int64)
        want = exact.allow_batch(names(ids), ns.tolist())
        if lane == "strings":
            same(dense.allow_batch(names(ids), ns.tolist()), want, step)
        else:
            launch = (dense.launch_hashed if lane == "hashed-pipelined"
                      else dense.launch_ids)
            pending.append((launch(ids, ns), want, step))
            if len(pending) == 4:
                for ticket, w, s in pending:
                    same(dense.resolve(ticket), w, s)
                pending.clear()
        clock.advance(float(rng.uniform(0.0, 9.0)))
    for ticket, w, s in pending:
        same(dense.resolve(ticket), w, s)
    st = dense.directory_stats()
    assert st["entries"] == st["inserts"] == dense.key_count() == 48
    assert st["unplaced"] == 0 and st["lookups"] == 30 * 50
    assert st["probes"] > st["lookups"]          # some keys left home
    assert dense.result_fetches == 30
    dense.close()
    exact.close()


@every_rule
def test_one_new_key_many_times_in_one_batch(algo):
    dense, exact, _ = pair(algo, limit=9)
    same(dense.allow_batch(["warm"]), exact.allow_batch(["warm"]))
    keys = ["new"] * 40 + ["warm"] * 3 + ["new"] * 21
    same(dense.allow_batch(keys), exact.allow_batch(keys))
    st = dense.directory_stats()
    assert st["inserts"] == st["entries"] == 2       # ONE slot for "new"
    assert np.count_nonzero(table(dense)) == 2
    same(dense.allow_batch(keys), exact.allow_batch(keys))
    assert dense.directory_stats()["inserts"] == 2
    dense.close()
    exact.close()


@every_rule
def test_many_new_keys_with_one_home_bucket_in_one_batch(algo):
    """Fourteen never-seen keys of ONE home bucket (4 lanes) in one batch,
    each several times: they fill the bucket and spill over the next
    three, each to a slot of its own."""
    dense, exact, _ = pair(algo, limit=3)
    geo = directory.geometry(64, 4, 16)
    ids = with_home(5, 14, geo["nb"], geo["w"])
    rng = np.random.default_rng(3)
    rows = rng.permutation(np.repeat(ids, 4))
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    st = dense.directory_stats()
    assert st["inserts"] == st["entries"] == 14 and st["unplaced"] == 0
    keys = table(dense)
    assert sorted(keys[keys != 0].tolist()) == sorted(ids.tolist())
    assert np.all(keys[5] != 0)                  # the home bucket is full
    # Found again where they were put (no second insertion), in any order.
    rows = rng.permutation(np.repeat(ids, 2))
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    assert dense.directory_stats()["inserts"] == 14
    np.testing.assert_array_equal(table(dense), keys)
    dense.close()
    exact.close()


@every_rule
def test_a_batch_fills_the_last_free_entries(algo):
    dense, exact, _ = pair(algo, capacity=16, probe_bound=4)
    first = np.arange(1, 13, dtype=np.uint64) * 977
    same(dense.resolve(dense.launch_hashed(first)),
         exact.allow_batch(names(first)))
    last = np.arange(13, 17, dtype=np.uint64) * 977
    rows = np.concatenate([last, first[:5], last])
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    st = dense.directory_stats()
    assert st["entries"] == 16 == dense.key_count()
    assert st["unplaced"] == 0
    assert np.all(table(dense) != 0)
    dense.close()
    exact.close()


# ------------------------------------- identity under the two-phase claim

def word_key(lo: int, hi: int) -> int:
    return (hi << 32) | lo


def canon_host(ids) -> np.ndarray:
    """The directory's key of each finalized id, as uint64, on the host."""
    lo, hi = directory.canon(jnp.asarray(np.asarray(ids, np.uint64)))
    return np.asarray(directory.join(lo, hi)).view(np.uint64)


def sharing_a_lane(nb: int, w: int, count: int):
    """Keys built to collide word by word: ``count`` distinct keys of ONE
    low word and ``count`` of ONE high word that all have the same home
    bucket AND the same preferred lane there, and two each of low word 0
    and low word 1 (high word non-zero) with that home too."""
    def homes(lo, hi):
        b, l = directory.home((jnp.asarray(lo, jnp.uint32),
                               jnp.asarray(hi, jnp.uint32)), nb, w)
        return np.asarray(b), np.asarray(l)

    span = np.arange(1, 1 + 4000 * nb * w, dtype=np.uint32)
    fixed = np.full(span.shape, 0xC0FFEE42, np.uint32)
    b, l = homes(fixed, span)                      # one low word
    bucket, lane = int(b[0]), int(l[0])
    low = span[(b == bucket) & (l == lane)][:count]
    b, l = homes(span, fixed)                      # one high word
    high = span[(b == bucket) & (l == lane)][:count]
    out = ([word_key(0xC0FFEE42, int(h)) for h in low]
           + [word_key(int(x), 0xC0FFEE42) for x in high])
    assert len(out) == 2 * count
    for reserved_low in (directory.EMPTY, directory.TOMB):
        b, l = homes(np.full(span.shape, reserved_low, np.uint32), span)
        hit = span[(b == bucket) & (l == lane)][:2]
        assert hit.shape[0] == 2
        out += [word_key(reserved_low, int(h)) for h in hit]
    return np.array(out, np.uint64), bucket, lane


#: 64-bit ids the directory keeps for itself, and what it remaps them to.
RESERVED = np.array([0, 1], np.uint64)
REMAPPED = np.array([0x9E3779B97F4A7C15, 0x9E3779B97F4A7C14], np.uint64)


def test_canon_remaps_the_reserved_ids_only():
    np.testing.assert_array_equal(canon_host(RESERVED), REMAPPED)
    np.testing.assert_array_equal(canon_host(REMAPPED), REMAPPED)
    ordinary = np.array([word_key(0, 7), word_key(1, 7), word_key(7, 0),
                         2, (1 << 64) - 1, 1 << 63], np.uint64)
    np.testing.assert_array_equal(canon_host(ordinary), ordinary)


@pytest.mark.parametrize("insert", [True, False], ids=["insert", "lookup"])
def test_a_probe_resolves_only_to_an_entry_that_reads_back_its_key(insert):
    """``directory.probe`` alone, on the colliding keys: every placed
    row's slot holds BOTH of its words, distinct keys get distinct
    slots, duplicates get one; without ``insert`` nothing is placed and
    nothing written."""
    nb, w, pb = 16, 4, 16
    keys, _, _ = sharing_a_lane(nb, w, 6)
    ids = np.concatenate([keys, RESERVED, REMAPPED, keys[:3], keys[:1]])
    k = directory.canon(jnp.asarray(ids))
    table, slot, placed, claimed, _ = directory.probe(
        directory.init_keys(nb, w), k, jnp.ones(ids.shape, bool),
        nb=nb, w=w, pb=pb, insert=insert)
    held = np.asarray(directory.join(*table)).view(np.uint64).ravel()
    if not insert:
        assert not np.asarray(placed).any() and not held.any()
        return
    assert np.asarray(placed).all() and np.asarray(claimed).all()
    want = canon_host(ids)
    np.testing.assert_array_equal(held[np.asarray(slot)], want)
    assert np.unique(np.asarray(slot)).shape[0] == np.unique(want).shape[0]
    live = held[held != 0]
    assert np.array_equal(np.sort(live), np.unique(want))   # each key once


@pytest.mark.parametrize("lane", ["hashed", "ids"])
@every_rule
def test_new_keys_that_share_a_word_and_a_lane_in_one_batch(algo, lane):
    """One batch brings distinct NEW keys of one low word and distinct
    new keys of one high word that all prefer the SAME lane of the same
    bucket, keys of low word 0 and 1 (ordinary keys: their high word is
    not 0), the reserved ids 0 and 1 WITH the ids they are remapped to
    (by design one entry each pair), and one new key several times.
    The low word's claim alone settles nothing: every row decides as
    the plain rule does for its 64-bit key, the directory inserted
    exactly the distinct keys, and holds each once."""
    dense, exact, clock = pair(algo, limit=5)
    geo = directory.geometry(64, 4, 16)
    keys, bucket, lane_of = sharing_a_lane(geo["nb"], geo["w"], 6)
    assert (bucket_of(keys, geo["nb"], geo["w"]) == bucket).all()
    rows = np.concatenate([keys, RESERVED, REMAPPED, RESERVED[:1],
                           np.repeat(keys[3], 3), np.repeat(keys[8], 2)])
    distinct = np.unique(canon_host(rows))
    assert distinct.shape[0] == keys.shape[0] + 2
    rng = np.random.default_rng(43)
    launch = dense.launch_hashed if lane == "hashed" else dense.launch_ids
    for step in range(4):
        ids = rows if step == 0 else rng.permutation(np.concatenate(
            [rows, rng.choice(keys, size=9)]))
        ns = rng.integers(1, 3, size=ids.shape[0]).astype(np.int64)
        want = exact.allow_batch(names(canon_host(ids)), ns.tolist())
        sent = ids if lane == "hashed" else splitmix64_inv(ids)
        same(dense.resolve(launch(sent, ns)), want, step)
        st = dense.directory_stats()
        assert st["inserts"] == st["entries"] == distinct.shape[0] \
            == exact.key_count(), step
        assert st["unplaced"] == 0
        held = dense._dir_keys().view(np.uint64).ravel()
        np.testing.assert_array_equal(np.sort(held[held != 0]), distinct)
        clock.advance(float(rng.uniform(0.0, 25.0)))
    # More keys than one bucket's lanes preferred one lane: they spread.
    assert st["probes"] > st["lookups"]
    dense.close()
    exact.close()


# ------------------------------------------------------------- full table

@pytest.mark.parametrize("fail_open", [True, False],
                         ids=["fail-open", "fail-closed"])
@every_rule
def test_a_full_table_answers_by_policy_and_overwrites_nothing(algo,
                                                               fail_open):
    dense, exact, _ = pair(algo, capacity=8, probe_bound=2, limit=4,
                           fail_open=fail_open)
    old = np.arange(1, 9, dtype=np.uint64) * 31
    same(dense.resolve(dense.launch_hashed(old)),
         exact.allow_batch(names(old)))
    before = table(dense)
    columns = dense._columns()
    new = np.array([1001, 1002, 1003], dtype=np.uint64)
    rows = np.concatenate([new[:2], old, new])
    is_new = np.isin(rows, new)
    if fail_open:
        got = dense.resolve(dense.launch_hashed(rows))
        want = exact.allow_batch(names(rows[~is_new]))
        assert got.fail_open
        # The policy's answer for the rows without an entry ...
        assert got.allowed[is_new].all()
        assert (got.remaining[is_new] == 0).all()
        assert (got.retry_after[is_new] == 0.0).all()
        # ... and every key that has one is decided as ever.
        for col in ("allowed", "remaining", "retry_after", "reset_at"):
            np.testing.assert_array_equal(getattr(got, col)[~is_new],
                                          getattr(want, col))
    else:
        ticket = dense.launch_hashed(rows)
        for _ in range(2):                       # idempotent: raises again
            with pytest.raises(StorageUnavailableError,
                               match="dense store full: 5 of 13 rows"):
                dense.resolve(ticket)
        exact.allow_batch(names(rows[~is_new]))
    st = dense.directory_stats()
    assert st["unplaced"] == 5 and st["entries"] == 8 == st["inserts"]
    np.testing.assert_array_equal(table(dense), before)   # no key replaced
    # The slots of the keys that have an entry moved on; the padding slot
    # (where the unplaced rows went, with n = 0) holds no consumption:
    # its first column (tokens / count / curr) is what it was.
    now = dense._columns()
    assert (now[0, :8] != columns[0, :8]).any()
    assert now[0, 8] == columns[0, 8]
    # Still exact afterwards.
    same(dense.resolve(dense.launch_hashed(old)),
         exact.allow_batch(names(old)))
    dense.close()
    exact.close()


@every_rule
def test_the_probe_bound_is_held_even_with_room_elsewhere(algo):
    """Bound 2 on eight buckets of 2: the ninth key of one home bucket
    finds neither its key nor a free lane in two buckets and is answered
    by policy although the table is three quarters empty."""
    dense, _, _ = pair(algo, capacity=16, lanes=2, probe_bound=2,
                       fail_open=True)
    geo = directory.geometry(16, 2, 2)
    ids = with_home(3, 5, geo["nb"], geo["w"])
    got = dense.resolve(dense.launch_hashed(ids))
    assert got.fail_open and got.allowed.all()
    st = dense.directory_stats()
    assert st["inserts"] == 4 and st["unplaced"] == 1
    assert st["probes"] >= 4 + 2 + 2     # the unplaced row examined both
    dense.close()


# ------------------------------------------------------ reclaim and reuse

@every_rule
def test_reuse_after_prune(algo):
    dense, exact, clock = pair(algo, capacity=8, probe_bound=2, limit=3,
                               window=10.0)
    old = np.arange(1, 9, dtype=np.uint64) * 13
    rows = np.repeat(old, 3)
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    clock.advance(9.0)
    assert dense.prune() == 0 and dense.key_count() == 8   # not idle yet
    clock.advance(12.0)                                    # two windows
    assert dense.prune() == 8
    assert dense.key_count() == 0
    assert not table(dense).any()           # tombstones swept: all EMPTY
    assert (dense._columns()[:, :8] == dense._fresh[:, None]).all()
    new = np.arange(1, 9, dtype=np.uint64) * 17
    rows = np.repeat(new, 4)
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    st = dense.directory_stats()
    assert st["entries"] == 8 and st["reclaimed"] == 8
    assert st["unplaced"] == 0
    dense.close()
    exact.close()


@every_rule
def test_a_nearly_full_directory_is_swept_before_a_launch(algo):
    """No prune() call: the launch that could fill the table past the
    gate (0.79875 of 1,024 entries: 817) runs the pass itself (idle keys
    give way) and not again within a sixteenth of a window — unless the
    batch could cross the line (0.8188: 838), where it runs whenever
    the last one was."""
    dense, exact, clock = pair(algo, capacity=1024, lanes=128,
                               probe_bound=8, window=16.0)
    assert (int(dense._reclaim_above * 1024),
            int(dense._reclaim_line * 1024)) == (817, 838)

    def launch(lo, hi, mult):
        ids = np.arange(lo, hi, dtype=np.uint64) * np.uint64(mult)
        same(dense.resolve(dense.launch_hashed(ids)),
             exact.allow_batch(names(ids)))
        return dense.directory_stats()

    passes = launch(1, 801, 19)["reclaim_passes"]     # 800: under the gate
    clock.advance(33.0)
    st = launch(1, 21, 23)                            # 820: over it, idle
    assert st["reclaim_passes"] == passes + 1 and st["reclaimed"] == 800
    assert st["entries"] == 20 and st["unplaced"] == 0
    clock.advance(0.9)
    assert launch(21, 811, 23)["reclaim_passes"] == passes + 1    # 810
    st = launch(811, 821, 23)       # 820: over the gate, inside the 1/16
    assert st["reclaim_passes"] == passes + 1 and st["entries"] == 820
    st = launch(821, 841, 23)       # 840: over the line, at once
    assert st["reclaim_passes"] == passes + 2 and st["reclaimed"] == 800
    assert st["entries"] == 840 and st["unplaced"] == 0
    dense.close()
    exact.close()


@every_rule
def test_reuse_after_reset(algo):
    dense, exact, _ = pair(algo, capacity=4, probe_bound=1, limit=2)
    keys = ["a", "b", "c", "d"]
    same(dense.allow_batch(keys * 2), exact.allow_batch(keys * 2))
    assert dense.key_count() == 4
    dense.reset("b")
    exact.reset("b")
    assert dense.key_count() == 3
    dense.reset("never-seen")                    # no entry: nothing moves
    assert dense.key_count() == 3
    rows = ["e", "a", "e", "e"]                  # e takes the free entry
    same(dense.allow_batch(rows), exact.allow_batch(rows))
    assert dense.key_count() == 4
    with pytest.raises(StorageUnavailableError):  # b finds none now
        dense.allow("b")
    for lim in (dense, exact):
        lim.reset("e")
    rows = ["b", "a", "b", "b", "c"]             # b is back, and fresh
    same(dense.allow_batch(rows), exact.allow_batch(rows))
    assert dense.key_count() == 4
    dense.close()
    exact.close()


@every_rule
def test_a_displaced_key_is_still_found_past_tombstones(algo):
    """Six keys of home bucket 2 fill buckets 2 and 3 of a 4-lane table.
    The four in bucket 2 go idle and are reclaimed; the two in bucket 3
    stay live, so bucket 2's tombstones must NOT become EMPTY — a lookup
    of the displaced keys still walks past them — until those keys go
    too."""
    dense, exact, clock = pair(algo, capacity=32, probe_bound=8, limit=50,
                               window=10.0)
    geo = directory.geometry(32, 4, 8)
    ids = with_home(2, 6, geo["nb"], geo["w"])
    same(dense.resolve(dense.launch_hashed(ids)),
         exact.allow_batch(names(ids)))
    keys = table(dense)
    at_home = keys[2][keys[2] != 0].astype(np.uint64)
    displaced = keys[3][keys[3] != 0].astype(np.uint64)
    assert at_home.shape[0] == 4 and displaced.shape[0] == 2
    for _ in range(3):                  # keep the displaced two alive
        clock.advance(9.0)
        same(dense.resolve(dense.launch_hashed(displaced)),
             exact.allow_batch(names(displaced)))
    assert dense.prune() == 4
    keys = table(dense)
    assert (keys[2] == directory.TOMB).all()             # covered: kept
    assert sorted(keys[3][keys[3] > 1].astype(np.uint64).tolist()) \
        == sorted(displaced.tolist())
    inserts = dense.directory_stats()["inserts"]
    same(dense.resolve(dense.launch_hashed(displaced)),
         exact.allow_batch(names(displaced)))
    assert dense.directory_stats()["inserts"] == inserts   # found, not new
    # A reclaimed key that comes back is a fresh key; it may not sit in
    # its (tombstoned) home bucket, and must not collide with anyone.
    clock.advance(1.0)
    same(dense.resolve(dense.launch_hashed(at_home[:2])),
         exact.allow_batch(names(at_home[:2])))
    assert dense.directory_stats()["unplaced"] == 0
    clock.advance(25.0)
    assert dense.prune() == 4
    assert not table(dense).any()            # nobody walks past: swept
    dense.close()
    exact.close()


# --------------------------------------------------------------- identity

@every_rule
def test_string_lane_and_hashed_lane_land_on_one_bucket(algo):
    dense, exact, _ = pair(algo, limit=6)
    users = ["u1", "u2", "u1", "u3"]
    h = hash_prefixed_u64(users, dense.config.prefix)
    same(dense.allow_batch(users), exact.allow_batch(users))
    same(dense.resolve(dense.launch_hashed(h)), exact.allow_batch(users))
    same(dense.allow_hashed(h), exact.allow_batch(users))
    same(dense.allow_batch(users), exact.allow_batch(users))
    assert dense.key_count() == 3
    # The raw-id lane finalizes in the step: an id reaches the bucket of
    # its splitmix64, which is another key than the id taken as a hash.
    ids = np.array([5, 6, 5], dtype=np.uint64)
    other = [f"mixed{int(i)}" for i in ids]
    same(dense.allow_ids(ids), exact.allow_batch(other))
    same(dense.allow_hashed(splitmix64(ids)), exact.allow_batch(other))
    same(dense.allow_hashed(ids),
         exact.allow_batch([f"raw{int(i)}" for i in ids]))
    assert dense.key_count() == 3 + 2 + 2
    dense.close()
    exact.close()


@every_rule
def test_the_reserved_values_are_keys_like_any_other(algo):
    """Hashes 0 and 1 are the directory's EMPTY and TOMB marks: remapped,
    each is a key of its own."""
    dense, exact, _ = pair(algo, limit=2)
    h = np.array([0, 1, 0, 2, 1, 0, 3], dtype=np.uint64)
    same(dense.allow_hashed(h), exact.allow_batch(names(h)))
    same(dense.allow_hashed(h), exact.allow_batch(names(h)))
    assert dense.key_count() == 4
    keys = table(dense)
    assert np.count_nonzero(keys > 1) + np.count_nonzero(keys < 0) == 4
    dense.close()
    exact.close()


# --------------------------------------------------------------- overrides

@pytest.mark.parametrize("lane", ["strings", "hashed"])
@every_rule
def test_overrides_set_and_cleared(algo, lane):
    """The override lookup runs — and is counted — only while the table
    holds an entry; with it or without, every answer is the plain
    rule's."""
    dense, exact, clock = pair(algo, limit=3)
    users = ["vip", "u1", "vip", "u2", "vip", "vip", "u1", "vip"]

    def both(k=1):
        for _ in range(k):
            want = exact.allow_batch(users)
            if lane == "strings":
                got = dense.allow_batch(users)
            else:
                got = dense.resolve(dense.launch_hashed(
                    hash_prefixed_u64(users, dense.config.prefix)))
            same(got, want)
            if want.limits is None:
                assert got.limits is None
            else:
                np.testing.assert_array_equal(got.limits, want.limits)
            clock.advance(0.5)

    both(2)
    assert dense.override_lookup_dispatches == 0
    for lim in (dense, exact):
        lim.set_override("vip", 7)
    both(3)
    assert dense.override_lookup_dispatches == 3
    for lim in (dense, exact):
        lim.set_override("u2", 1, window_scale=0.5)
        assert lim.delete_override("vip")
    both(2)
    assert dense.override_lookup_dispatches == 5
    for lim in (dense, exact):
        assert lim.delete_override("u2")
    both(2)
    assert dense.override_lookup_dispatches == 5
    dense.close()
    exact.close()


# ------------------------------------------------------- snapshot, restore

@every_rule
def test_snapshot_restore_same_answers(algo, tmp_path):
    dense, exact, clock = pair(algo, limit=4)
    rng = np.random.default_rng(11)
    for _ in range(6):
        ids = rng.integers(1, 40, size=30).astype(np.uint64)
        same(dense.resolve(dense.launch_hashed(ids)),
             exact.allow_batch(names(ids)))
        clock.advance(2.5)
    dense.set_override("id3", 9)
    path = str(tmp_path / "dense.npz")
    in_flight = dense.launch_hashed(np.array([3, 3, 7], np.uint64))
    exact.allow_batch(names([3, 3, 7]))
    dense.save(path)                 # the ticket's step is in the snapshot
    dense.resolve(in_flight)
    kind, arrays, extra = dense.capture_state()
    assert kind == "dense" and extra["capacity"] == 64
    assert arrays["state_dir_keys"].shape == (16, 4)
    assert "slot_keys" not in arrays and "last_used" not in arrays

    restored = create_limiter(dense.config, backend="dense", clock=clock)
    restored.restore(path)
    assert restored.key_count() == dense.key_count()
    np.testing.assert_array_equal(table(restored), table(dense))
    assert restored.get_override("id3").limit == 9
    for _ in range(6):
        ids = rng.integers(1, 60, size=30).astype(np.uint64)
        want = exact.allow_batch(names(ids))
        same(dense.resolve(dense.launch_hashed(ids)), want)
        same(restored.resolve(restored.launch_hashed(ids)), want)
        clock.advance(2.5)
    assert restored.directory_stats()["entries"] \
        == dense.directory_stats()["entries"]

    # Another bucket width puts a key elsewhere: refused, as another
    # capacity is (tests/test_checkpoint.py).
    for params in (DenseParams(capacity=64, lanes=8, probe_bound=16),
                   DenseParams(capacity=128, lanes=4, probe_bound=16)):
        other = create_limiter(
            Config(algorithm=ALGOS[algo], limit=4, window=60.0,
                   dense=params), backend="dense", clock=clock)
        with pytest.raises(CheckpointError):
            other.restore(path)
        other.close()
    for lim in (dense, exact, restored):
        lim.close()


# ------------------------------------------------------ what the host holds

def test_the_host_holds_no_key_map_and_runs_no_per_key_loop():
    """algorithms/dense.py: no dict from key to slot, no free list, no
    loop over a batch's keys — by its source and by its instance."""
    import ast
    import inspect

    from ratelimiter_tpu.algorithms import dense as module

    dense, _, _ = pair("bucket")
    dense.allow_batch(["a", "b"])
    for gone in ("_slots", "_free", "_last_used", "_assign_slots",
                 "_zero_slots", "_fresh_row"):
        assert not hasattr(dense, gone), gone
    host = {k: v for k, v in vars(dense).items()
            if isinstance(v, (dict, list)) and k not in ("_state",)}
    assert all(len(v) <= 8 for v in host.values()), \
        {k: len(v) for k, v in host.items()}
    # What loops there are run over the state's three leaves, the
    # override table's columns or a result's four tail words (snapshots,
    # the table's device copy) — none on the dispatch path, none over a
    # batch.
    tree = ast.parse(inspect.getsource(module))
    loops = {fn.name: [ast.unparse(n)[:50] for n in ast.walk(fn)
                       if isinstance(n, (ast.For, ast.While, ast.ListComp,
                                         ast.DictComp, ast.GeneratorExp))]
             for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)}
    assert {k for k, v in loops.items() if v} <= {
        "_note_tail_locked", "capture_state",
        "restore", "_policy_device"}, loops
    for fn in ("_gate_locked", "_step_args", "_resolve_ticket",
               "_policy_limits", "_reclaim_locked", "_reset"):
        assert loops[fn] == [], fn
    dense.close()


@every_rule
def test_the_step_touches_nothing_sized_by_the_table(algo):
    """The lowered step at two capacities, 2**10 and 2**16 entries: no
    operation but the donated state's own parameters and results has an
    operand of the table's size — no ``zeros(capacity)``, no whole-column
    pass."""
    import re

    import jax

    from ratelimiter_tpu.ops import dense_kernels

    def table_sized_ops(capacity):
        cfg = Config(algorithm=ALGOS[algo], limit=5, window=60.0,
                     dense=DenseParams(capacity=capacity))
        lim = create_limiter(cfg, backend="dense", clock=ManualClock(T0))
        step = dense_kernels.build_hashed_step(cfg, capacity)
        with lim._lock:
            policy = lim._policy_device()
        staged = jax.ShapeDtypeStruct((2 * 64 + 1,), jnp.uint64)
        text = step.lower(lim._state, staged, policy).as_text()
        lim.close()
        big = {f"[46]x{capacity + 1}", f"{capacity // 128}x128"}
        ops = set()
        for line in text.splitlines():
            m = re.search(r"= \"?(stablehlo\.[a-z_]+|func\.call)", line)
            if m and any(re.search(rf"tensor<{b}x", line) for b in big):
                ops.add(m.group(1))
        return ops

    small, large = table_sized_ops(1 << 10), table_sized_ops(1 << 16)
    assert small == large and "stablehlo.gather" in large   # not vacuous
    # Gathers read the table, scatters write it in place, a while loop
    # carries it; nothing else may take or give a table-sized value.
    assert large <= {"stablehlo.gather", "stablehlo.scatter",
                     "stablehlo.while", "stablehlo.return",
                     "func.call"}, large


# ------------------------------------------------------- behind the door

@every_rule
def test_the_native_door_serves_it_hashed_and_pipelined(algo):
    """The C++ door gives the dense limiter what it gives the sketches:
    the ALLOW_HASHED lane and the launch / resolve pipeline, for hashed
    and string frames alike; every reply is the plain rule's (the door
    finalizes an id with splitmix64, as ``launch_ids`` does in the
    step). More than one dispatch shard is still refused."""
    from ratelimiter_tpu.serving import Client
    from ratelimiter_tpu.serving.native_server import (
        NativeRateLimitServer,
        native_server_available,
    )

    if not native_server_available():
        pytest.skip("needs g++ for the native server")
    dense, exact, _ = pair(algo, capacity=256, lanes=16, limit=4)
    with pytest.raises(ValueError, match="shards > 1"):
        NativeRateLimitServer(dense, "127.0.0.1", 0, shards=2)
    srv = NativeRateLimitServer(dense, "127.0.0.1", 0)
    assert srv._fast and srv._pipelined
    srv.start()
    try:
        with Client(port=srv.port) as c:
            rng = np.random.default_rng(5)
            for _ in range(6):
                ids = rng.integers(1, 30, size=40).astype(np.uint64)
                got = c.allow_hashed(ids)
                want = exact.allow_batch([f"mixed{int(i)}" for i in ids])
                np.testing.assert_array_equal(got.allowed, want.allowed)
                np.testing.assert_array_equal(got.remaining, want.remaining)
                users = [f"user:{int(i)}" for i in ids[:9]]
                got = c.allow_batch(users)
                want = exact.allow_batch(users)
                assert [r.allowed for r in got] == want.allowed.tolist()
                assert [r.remaining for r in got] == want.remaining.tolist()
            # Both lanes of one server reach one bucket for one key.
            h = hash_prefixed_u64(["user:7"], dense.config.prefix)
            before = dense.key_count()
            dense.allow_hashed(h)
            assert dense.key_count() == before
    finally:
        srv.shutdown()
    st = dense.directory_stats()
    assert st["unplaced"] == 0 and st["entries"] == st["inserts"]
    assert dense.result_fetches >= 12
    dense.close()
    exact.close()


# ------------------------------------------ YCSB's skew: a cold tail all day

ZIPF_S = 0.99          # YCSB's ZipfianGenerator.ZIPFIAN_CONSTANT
BATCH = 256


def zipf_stream(population: int, dispatches: int, seed: int) -> np.ndarray:
    """``dispatches`` batches of BATCH ids in [2, population + 2): ranks
    drawn Zipf(0.99), rank -> id by a seeded permutation (the
    generator's scheme, chipbench/loadgen)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, population + 1) ** ZIPF_S
    ranks = rng.choice(population, size=(dispatches, BATCH), p=p / p.sum())
    return (rng.permutation(population)[ranks] + 2).astype(np.uint64)


def first_seen(stream: np.ndarray) -> list:
    """Per dispatch, the rows whose key no earlier dispatch held."""
    seen, out = set(), []
    for rows in stream:
        out.append(np.array([int(r) not in seen for r in rows]))
        seen.update(int(r) for r in rows)
    return out


#: geometry -> (capacity, lanes, population, dispatches). The small table
#: has 4-lane buckets, so first-seen keys share home buckets all the
#: time; the large one is the server's geometry (128 lanes) at a capacity
#: that is not tiny: 2^20 entries are 32 MB of state on the CPU.
ZIPF_GEOMETRY = {"small": (16_384, 4, 32 * BATCH, 12),
                 "2^20": (1 << 20, 128, 256 * BATCH, 6)}
ZIPF_CASES = ([("small", algo, "hashed") for algo in ALGOS]
              + [("small", "bucket", "ids"), ("small", "bucket", "door"),
                 ("2^20", "bucket", "hashed")])


@pytest.mark.parametrize("geometry, algo, lane", ZIPF_CASES,
                         ids=["-".join(c) for c in ZIPF_CASES])
def test_a_zipf_099_stream_whose_tail_keeps_arriving(geometry, algo, lane):
    """exact-tb-20m's traffic in small (ISSUE 42): YCSB's zipfian constant
    over a population >= 32x the batch, so that every dispatch brings
    >= 10 % rows of keys never seen before — the directory's claim path
    does a large share of the rows, not 1 % of them. Decisions and
    ``remaining`` equal the plain rule's fed the same requests in the
    same order, four tickets in flight; the directory inserted exactly
    the reference's distinct keys; the state's resident bytes are
    32 B an entry (the bucket's) + the padding slot's row."""
    capacity, lanes, population, dispatches = ZIPF_GEOMETRY[geometry]
    dense, exact, clock = pair(algo, capacity=capacity, lanes=lanes,
                               probe_bound=8, limit=3)
    stream = zipf_stream(population, dispatches, seed=99)
    assert population >= 32 * BATCH
    # The door finalizes an id with splitmix64 (as launch_ids does in the
    # step); the hashed lane takes the hash as it comes.
    hashes = stream if lane == "hashed" else splitmix64(stream)
    geo = directory.geometry(capacity, lanes, 8)
    new = first_seen(stream)
    assert min(n.mean() for n in new) >= 0.10, [n.mean() for n in new]
    # What hurts a claim: several first-seen keys of one home bucket in
    # one dispatch, and one new key twice in one dispatch.
    crowded = twice = 0
    for rows, h, n in zip(stream, hashes, new):
        twice += int(np.sum(np.unique(rows[n], return_counts=True)[1] > 1))
        homes = bucket_of(np.unique(h[n]), geo["nb"], geo["w"])
        crowded += int(np.sum(np.unique(homes, return_counts=True)[1] > 1))
    assert twice > 0 and (crowded > 0 or lanes == 128), (twice, crowded)

    def decided(rows):
        return exact.allow_batch(names(rows))

    if lane == "door":
        from ratelimiter_tpu.serving import Client
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )

        if not native_server_available():
            pytest.skip("needs g++ for the native server")
        srv = NativeRateLimitServer(dense, "127.0.0.1", 0)
        srv.start()
        try:
            with Client(port=srv.port) as c:
                for step, rows in enumerate(stream):
                    got, want = c.allow_hashed(rows), decided(rows)
                    np.testing.assert_array_equal(got.allowed, want.allowed,
                                                  err_msg=str(step))
                    np.testing.assert_array_equal(
                        got.remaining, want.remaining, err_msg=str(step))
                    clock.advance(7.0)
        finally:
            srv.shutdown()
    else:
        launch = dense.launch_hashed if lane == "hashed" else dense.launch_ids
        pending = []
        for step, rows in enumerate(stream):
            pending.append((launch(rows), decided(rows), step))
            if len(pending) == 4:
                for ticket, want, s in pending:
                    same(dense.resolve(ticket), want, s)
                pending.clear()
            clock.advance(7.0)
        for ticket, want, s in pending:
            same(dense.resolve(ticket), want, s)
    st = dense.directory_stats()
    distinct = np.unique(stream).shape[0]
    assert st["inserts"] == st["entries"] == exact.key_count() == distinct
    assert st["unplaced"] == 0 and st["lookups"] == stream.size
    assert st["inserts"] >= 0.10 * st["lookups"]
    # A row of the rule's int64 columns + the directory's 64-bit key an
    # entry (the bucket's three columns: 32 B), and the padding slot's row.
    row = dense._fresh.nbytes
    assert row == 24 or algo != "bucket"
    (resident,) = dense.state_resident_bytes().values()
    assert resident == (row + 8) * capacity + row == sum(
        leaf.nbytes for leaf in dense._state.values())
    dense.close()
    exact.close()


# ------------------------------------------------------- the fill curve

#: (capacity, lanes, probe bound): the server's geometry at two sizes and
#: two others of ``directory.unplaced_from``'s table.
FILLS = [(1 << 17, 128, 8), (1 << 18, 128, 8), (1 << 17, 64, 8),
         (1 << 17, 128, 4)]


@pytest.mark.parametrize("capacity, lanes, pb", FILLS,
                         ids=[f"2^{c.bit_length() - 1}-{w}x{pb}"
                              for c, w, pb in FILLS])
def test_no_row_is_unplaced_under_the_load_the_gate_is_reckoned_from(
        capacity, lanes, pb):
    """A fresh table filled with uniform keys, 2,048 a dispatch: not one
    row is left unplaced up to ``directory.unplaced_from(w, pb)`` — the
    reclaim gate's load plus its crest and its margin, so neither
    constant can drift back over the cliff unnoticed — and rows ARE left
    unplaced before the table is full (the curve measures something)."""
    from ratelimiter_tpu.algorithms import dense as dense_mod

    cliff = directory.unplaced_from(lanes, pb)
    gate = dense_mod.reclaim_above(lanes, pb)
    line = dense_mod.reclaim_line(lanes, pb)
    assert line == pytest.approx(cliff - dense_mod._MARGIN)
    assert gate == pytest.approx(line - dense_mod._CREST)
    assert dense_mod._MARGIN >= 0.05 and gate <= cliff - 0.07
    cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=5, window=60.0,
                 fail_open=True,
                 dense=DenseParams(capacity=capacity, lanes=lanes,
                                   probe_bound=pb))
    dense = create_limiter(cfg, backend="dense", clock=ManualClock(T0))
    assert (dense._reclaim_above, dense._reclaim_line) == (gate, line)
    rng = np.random.default_rng(capacity + lanes + pb)
    clean_up_to = first = None
    while dense.key_count() < 0.99 * capacity:
        dense.allow_hashed(rng.integers(2, 1 << 63, 2048, dtype=np.uint64))
        st = dense.directory_stats()
        if st["unplaced"] and first is None:
            first = st["entries"] / capacity
        if not st["unplaced"]:
            clean_up_to = st["entries"] / capacity
    assert clean_up_to >= cliff, (clean_up_to, cliff)
    assert first is not None and first > cliff
    # Nothing idle, so the passes the gate ran on the way freed nothing.
    assert dense.directory_stats()["reclaimed"] == 0
    dense.close()


def test_the_gate_at_the_servers_geometry_is_four_fifths():
    from ratelimiter_tpu.algorithms import dense as dense_mod

    assert directory.unplaced_from(128, 8) == pytest.approx(0.86875)
    assert dense_mod.reclaim_line(128, 8) == pytest.approx(0.81875)
    assert dense_mod.reclaim_above(128, 8) == pytest.approx(0.79875)
    # Never over the cliff's measured side for any geometry, never under
    # a quarter.
    for w, pb in ((128, 16), (64, 8), (32, 8), (8, 64), (4, 8), (1, 1)):
        assert 0.25 <= dense_mod.reclaim_above(w, pb) \
            <= max(0.25, directory.unplaced_from(w, pb) - 0.07)
        assert dense_mod.reclaim_above(w, pb) \
            <= dense_mod.reclaim_line(w, pb) \
            <= max(0.25, directory.unplaced_from(w, pb) - 0.05)
