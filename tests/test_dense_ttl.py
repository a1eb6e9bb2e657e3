"""The dense backend kept at its ACTIVE set by expiry (ISSUE 44,
``exact-tb-ttl``): a one-second window, a Zipf(0.99) stream whose
distinct keys exceed the table several times over, the reclaim pass run
by the launches themselves while tickets are in flight — held, request
for request, to ``algorithms/exact.py::ExactLimiter`` fed the same
requests on the same clock and NEVER pruned (a dict that never forgets:
an entry idle for two windows answers as a fresh one under every rule, so
no reply may differ).

What the stream is built to hurt, and what the parent's directory did
not hold (PERF.md §6, PR 44): at every pass the table is nearly full, so
buckets are full, keys are displaced, the entries the pass gives up stay
TOMBSTONES where a displaced key still lives — and a probe that claims
EMPTY lanes only never gets those lanes back: every bucket ends full of
tombstones and first-seen keys are answered by policy. ``ops/directory.
probe`` takes a tombstone once its key is known to be absent.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from ratelimiter_tpu import StorageUnavailableError
from ratelimiter_tpu.algorithms import dense as dense_mod
from ratelimiter_tpu.ops import directory
from tests.test_dense_directory import (
    ALGOS,
    names,
    pair,
    same,
    table,
    with_home,
)

#: Rows a dispatch: four tickets in flight and the batch at the gate count
#: as new keys (5 x 64 = 8 % of 4,096 entries), so the launch gate runs
#: its pass at ~0.72 of the table by the host's count.
BATCH = 64
ZIPF_S = 0.99


def ttl_stream(population: int, dispatches: int, seed: int) -> np.ndarray:
    """``dispatches`` batches of BATCH finalized hashes: ranks drawn
    Zipf(0.99) over ``population``, rank -> hash by a seeded
    permutation (the generator's scheme)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, population + 1) ** ZIPF_S
    ranks = rng.choice(population, size=(dispatches, BATCH), p=p / p.sum())
    return (rng.permutation(population)[ranks].astype(np.uint64)
            * np.uint64(0x9E3779B97F4A7C15) + np.uint64(2))


def present(dense, hashes) -> np.ndarray:
    """Which of ``hashes`` the directory holds now, read off the host
    view of its keys."""
    keys = directory.canon(jnp.asarray(np.asarray(hashes, np.uint64)))
    mine = directory.join(*keys)
    return np.isin(np.asarray(mine), dense._dir_keys().ravel())


#: case -> (capacity, lanes, population, dispatches, seconds a dispatch,
#: load at which a pass is run). The 8-lane buckets are full all the time
#: at these loads (a bucket holds 6.4 +- 2.5 keys at 0.8), so keys walk
#: far and their probe bound is 64 buckets; the server's geometry, 128
#: lanes, keeps its own bound of 8. ``None`` is the launch gate's own
#: threshold (``dense.reclaim_above`` of the geometry, 0.80 and 0.75
#: here: nothing is called, the launches run the passes); 0.5 calls
#: ``prune()`` — the same pass — whenever the host's count passes half
#: the table, tickets in flight.
TTL_CASES = {
    "128-lanes-at-the-gate": (4096, 128, 65_536, 960, 1 / 40, None),
    "8-lanes-at-the-gate": (4096, 8, 65_536, 960, 1 / 40, None),
    "128-lanes-at-0.5": (4096, 128, 65_536, 960, 1 / 20, 0.5),
    "8-lanes-at-0.5": (4096, 8, 65_536, 960, 1 / 20, 0.5),
}
TTL_RULES = ([(case, "bucket") for case in TTL_CASES]
             + [("8-lanes-at-the-gate", "fixed"),
                ("8-lanes-at-the-gate", "sliding")])


@pytest.mark.parametrize("case, algo", TTL_RULES,
                         ids=["-".join(c) for c in TTL_RULES])
def test_expiry_keeps_a_table_far_smaller_than_its_keys(case, algo):
    """Four tickets in flight all along; every column equals the
    reference's; nothing is left unplaced; the host's count never
    passes the capacity; at least three passes freed entries; the books
    close."""
    capacity, lanes, population, dispatches, dt, at = TTL_CASES[case]
    dense, exact, clock = pair(algo, capacity=capacity, lanes=lanes,
                               probe_bound=8 if lanes == 128 else 64,
                               limit=3, window=1.0)
    stream = ttl_stream(population, dispatches, seed=44)
    distinct = np.unique(stream).shape[0]
    assert distinct >= 3 * capacity, (distinct, capacity)
    pending, most = [], 0

    def drain():
        nonlocal most
        for ticket, want, step in pending:
            same(dense.resolve(ticket), want, step)
            most = max(most, dense.key_count())
        pending.clear()

    for step, rows in enumerate(stream):
        if at is not None and dense.key_count() > at * capacity:
            dense.prune()                       # tickets in flight
        pending.append((dense.launch_hashed(rows),
                        exact.allow_batch(names(rows)), step))
        if len(pending) == 4:
            drain()
        clock.advance(dt)
    drain()
    st = dense.directory_stats()
    assert st["unplaced"] == 0 and st["lookups"] == stream.size
    assert most <= capacity
    assert most > 0.45 * capacity, most         # the table did fill up
    assert st["reclaim_passes"] >= 3 and st["reclaimed"] > capacity // 2
    assert st["reclaim_seconds"] > 0
    # The books: what was inserted and not given up is what it holds —
    # by the host's count and by the table itself.
    keys = table(dense)
    held = int(np.count_nonzero((keys != directory.EMPTY)
                                & (keys != directory.TOMB)))
    assert st["inserts"] - st["reclaimed"] == st["entries"] == held
    # Keys were given up and came back (as fresh keys, equal to the
    # reference's all along): more inserts than distinct keys.
    assert st["inserts"] > distinct
    dense.close()
    exact.close()


#: What a launch waits for a pass, read on the chip (PERF.md section 6,
#: PR 48): 2-4 ms while the host paces the server (a cold start: the
#: device runs nothing ahead of the pass), 13-27 ms with eight
#: device-paced steps in flight ahead of it. The clock moves by this much
#: between the launch's stamp and its step.
PASS_STALL_EARLY_S, PASS_STALL_S = 0.003, 0.02
#: The closed loop at a sixteenth of the cell: decisions a second while
#: the table holds no tombstone yet (the host's pace, 3.65 M in the cell:
#: its two-second key set is 0.90 of the table here as there) and after
#: (the device's, ~2.9-3.0 M there: 0.74).
FAST, STEADY = 162_000, 130_000


@pytest.mark.parametrize("line, seconds", [(True, 8.0), (False, 3.0)],
                         ids=["the-line-holds-it", "without-the-line"])
def test_the_cells_stream_from_a_cold_start_eight_tickets_four_frames(
        line, seconds):
    """``exact-hashed-ttl``'s shape at a sixteenth of its table: 2^17
    entries of 128 lanes, bound 8, Zipf(0.99) over twenty times as many
    keys, a one-second window, dispatches of four 256-id frames, EIGHT
    tickets in flight, from an EMPTY table under a CLOSED LOOP: nothing
    is idle for the first two windows and until a pass has given
    something up the server runs at the host's pace, a rate whose
    two-second key set does not fit under the cliff — what failed two to
    seventeen frames a run on the chip under the single gate (PR 48). The
    clock moves through every pass by what the launch waits on the chip.
    Every column equals the reference's, no row is unplaced, the
    launches ran ten passes or more after the start, and the host's
    count never came within half the margin of
    ``directory.unplaced_from``: past the line a pass at every launch
    throttles the loop to what expires. Without the line (the same gate
    and cadence) the same stream takes the table over that load: the
    control."""
    capacity, rows = 1 << 17, 1024
    dense, exact, clock = pair("bucket", capacity=capacity, lanes=128,
                               probe_bound=8, limit=100, window=1.0,
                               fail_open=True)
    if not line:
        dense._reclaim_line = 2.0
    inner = dense._reclaim_locked

    def stalled(now_us):
        freed = inner(now_us)
        clock.advance(PASS_STALL_S if dense._dir["reclaimed"]
                      else PASS_STALL_EARLY_S)
        return freed

    dense._reclaim_locked = stalled
    dispatches = int(seconds * FAST / rows)
    rng = np.random.default_rng(48)
    p = 1.0 / np.arange(1, 20 * capacity + 1) ** ZIPF_S
    ranks = rng.choice(20 * capacity, size=(dispatches, rows), p=p / p.sum())
    stream = (ranks.astype(np.uint64) + np.uint64(1)) \
        * np.uint64(0x9E3779B97F4A7C15)
    t0, pending, most, sent = clock.now(), [], 0, 0

    def settle(ticket, want, at):
        got = dense.resolve(ticket)
        if line:
            same(got, want, at)

    for step, batch in enumerate(stream):
        if clock.now() - t0 >= seconds:
            break
        # The reference first: the launch stamps its rows with the clock
        # as it stands now, then may wait for a pass.
        want = exact.allow_batch(names(batch)) if line else None
        pending.append((dense.launch_hashed(batch), want, step))
        sent += 1
        if len(pending) == 8:
            settle(*pending.pop(0))
            most = max(most, dense.key_count())
        clock.advance(rows / (STEADY if dense._dir["reclaimed"] else FAST))
    for item in pending:
        settle(*item)
    st = dense.directory_stats()
    assert st["lookups"] == sent * rows
    cliff = directory.unplaced_from(128, 8) * capacity
    if not line:
        # Over the load from which a fill leaves rows unplaced (a table
        # of 2^17 entries does at 0.88-0.95, the cell's 2^21 at
        # 0.87-0.92): whether THIS seed's rows found lanes is the draw.
        assert most > cliff, (most, cliff)
        return
    assert st["unplaced"] == 0
    assert st["reclaim_passes"] >= 10 + 16, st    # the start's, then more
    assert 0.75 * capacity < most < cliff - dense_mod._MARGIN / 2 * capacity, \
        (most, cliff)
    assert st["inserts"] - st["reclaimed"] == st["entries"]
    assert st["inserts"] > np.unique(stream[:sent]).shape[0]   # came back
    dense.close()
    exact.close()


def test_a_bucket_that_was_full_once_gives_its_lanes_back():
    """The ratchet, in one bucket of a 4-lane table. Six keys of home
    bucket 2: four fill it, two are displaced to bucket 3. The four go
    idle and are given up while the displaced two stay live, so bucket
    2 is four TOMBSTONES that may not become EMPTY. Four new keys of
    home bucket 2 must take those lanes — after having looked into
    bucket 3 for themselves — and not walk on (the parent put them in
    buckets 3 and 4: bucket 2 never came back and its neighbours filled
    in turn)."""
    dense, exact, clock = pair("bucket", capacity=32, probe_bound=8,
                               limit=50, window=10.0)
    geo = directory.geometry(32, 4, 8)
    ids = with_home(2, 10, geo["nb"], geo["w"])
    first, again = ids[:6], ids[6:]
    same(dense.resolve(dense.launch_hashed(first)),
         exact.allow_batch(names(first)))
    keys = table(dense)
    displaced = keys[3][keys[3] != 0].astype(np.uint64)
    assert displaced.shape[0] == 2
    for _ in range(3):                  # keep the displaced two alive
        clock.advance(9.0)
        same(dense.resolve(dense.launch_hashed(displaced)),
             exact.allow_batch(names(displaced)))
    assert dense.prune() == 4
    assert (table(dense)[2] == directory.TOMB).all()
    probes = dense.directory_stats()["probes"]
    same(dense.resolve(dense.launch_hashed(again)),
         exact.allow_batch(names(again)))
    keys = table(dense)
    assert sorted(keys[2].astype(np.uint64).tolist()) == sorted(again.tolist())
    assert sorted(keys[3][keys[3] != 0].astype(np.uint64).tolist()) \
        == sorted(displaced.tolist())
    assert not keys[4].any()
    # Each looked at bucket 2, at bucket 3 (an EMPTY lane: the key is
    # absent) and at bucket 2 again to take its lane; one that lost a
    # lane to another looked once more.
    assert 3 * 4 <= dense.directory_stats()["probes"] - probes <= 3 * 4 + 3
    # The displaced keys are still found, and the new ones too.
    inserts = dense.directory_stats()["inserts"]
    rows = np.concatenate([displaced, again])
    same(dense.resolve(dense.launch_hashed(rows)),
         exact.allow_batch(names(rows)))
    st = dense.directory_stats()
    assert st["inserts"] == inserts and st["unplaced"] == 0
    dense.close()
    exact.close()


@pytest.mark.parametrize("algo", list(ALGOS))
def test_a_pass_with_tickets_in_flight_frees_no_key_they_touch(algo):
    """Old keys, two and a half windows idle; four launches not yet
    resolved touch a third of them again and bring new keys; then the
    pass. It gives up exactly the old keys no ticket touched — the steps
    ahead of it on the device stamped theirs — and a key given up and
    seen again answers as a fresh one."""
    dense, exact, clock = pair(algo, capacity=2048, lanes=128,
                               probe_bound=8, limit=4, window=1.0)
    old = np.arange(1, 601, dtype=np.uint64) * np.uint64(2_654_435_761)
    for rows in np.array_split(np.repeat(old, 5), 12):   # limit 4: used up
        same(dense.resolve(dense.launch_hashed(rows)),
             exact.allow_batch(names(rows)))
    assert dense.key_count() == 600
    clock.advance(2.5)
    kept, new = old[::3], np.arange(1, 301, dtype=np.uint64) * 7919
    touched = np.concatenate([kept, new])
    tickets = [(dense.launch_hashed(rows), exact.allow_batch(names(rows)))
               for rows in np.array_split(touched, 4)]
    passes = dense.directory_stats()["reclaim_passes"]
    freed = dense.prune()                        # four tickets in flight
    assert freed == 600 - kept.shape[0]
    for ticket, want in tickets:
        same(dense.resolve(ticket), want)
    st = dense.directory_stats()
    assert st["reclaim_passes"] == passes + 1 and st["unplaced"] == 0
    assert st["entries"] == touched.shape[0] == st["inserts"] - st["reclaimed"]
    assert present(dense, touched).all()
    gone = np.setdiff1d(old, kept)
    assert not present(dense, gone).any()
    # Given up and seen again: a fresh key, which is what the reference
    # (which forgot nothing) answers after two idle windows.
    back = np.repeat(gone[:100], 5)
    same(dense.resolve(dense.launch_hashed(back)),
         exact.allow_batch(names(back)))
    assert dense.directory_stats()["inserts"] == st["inserts"] + 100
    dense.close()
    exact.close()


@pytest.mark.parametrize("fail_open", [True, False],
                         ids=["fail-open", "fail-closed"])
def test_live_keys_beyond_the_table_are_answered_by_policy(fail_open):
    """The one case that does fill the table: more keys live inside two
    windows than it has entries. The passes run (past the line the gate
    asks for one at every launch) and find nothing idle; the rows without an
    entry are counted and answered by the policy — flagged, or raised —
    and the keys that have an entry still answer as the reference."""
    dense, exact, clock = pair("bucket", capacity=256, lanes=8,
                               probe_bound=4, limit=3, window=1.0,
                               fail_open=fail_open)
    held = np.arange(1, 201, dtype=np.uint64) * 104_729
    same(dense.resolve(dense.launch_hashed(held)),
         exact.allow_batch(names(held)))
    unplaced = raised = 0
    for step in range(12):
        clock.advance(0.15)
        rows = (np.arange(1, 65, dtype=np.uint64) + np.uint64(64 * step)) \
            * np.uint64(15_485_863)
        ticket = dense.launch_hashed(rows)
        try:
            res = dense.resolve(ticket)
        except StorageUnavailableError:
            assert not fail_open
            raised += 1
            continue
        if res.fail_open:
            assert fail_open
        before, unplaced = unplaced, dense.directory_stats()["unplaced"]
        assert bool(res.fail_open) == (unplaced > before)
    st = dense.directory_stats()
    assert st["unplaced"] > 0 and st["entries"] <= 256
    assert st["reclaim_passes"] >= 4 and raised == (0 if fail_open else raised)
    assert fail_open or raised > 0
    # Books: every row either found an entry or was counted.
    assert st["inserts"] - st["reclaimed"] == st["entries"]
    # The keys that were placed first are untouched by all this.
    clock.advance(0.1)
    same(dense.resolve(dense.launch_hashed(held)),
         exact.allow_batch(names(held)))
    dense.close()
    exact.close()


def test_the_pass_is_counted_on_metrics():
    """``rate_limiter_directory_reclaim_passes_total`` and
    ``…reclaim_seconds_total`` beside ``…reclaimed_total``, set at scrape
    from ``directory_stats()``, recorder off."""
    from ratelimiter_tpu import (
        Algorithm,
        Config,
        DenseParams,
        ManualClock,
        create_limiter,
    )
    from ratelimiter_tpu.observability import MetricsDecorator, Registry

    clock = ManualClock(1_700_000_000.5)
    cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=3, window=1.0,
                 dense=DenseParams(capacity=1024, lanes=128, probe_bound=8))
    reg = Registry()
    lim = MetricsDecorator(create_limiter(cfg, backend="dense", clock=clock),
                           registry=reg)
    lim.allow_batch([f"k{i}" for i in range(300)])
    clock.advance(2.5)
    assert lim.prune() == 300
    text = reg.render()
    stats = lim.directory_stats()
    assert stats["reclaim_passes"] == 1 and stats["reclaim_seconds"] > 0
    for family, want in (("reclaimed_total", 300.0),
                         ("reclaim_passes_total", 1.0),
                         ("reclaim_seconds_total", stats["reclaim_seconds"])):
        gauge = reg.get(f"rate_limiter_directory_{family}")
        assert gauge.value(shard="0") == pytest.approx(want)
        assert f"rate_limiter_directory_{family}{{" in text
    lim.close()
