"""The dense backend's keyspace directory, on the device (ADR-027).

A table of ``capacity`` entries keyed by the 64-bit id a decision row
carries. Lookup AND insertion run inside the one jitted decision step, so
the host never maps a key to a slot: an entry's position IS the slot of
the key's state row.

Layout: a key is its two 32-bit words, everywhere in this module a pair
``(low, high)`` of uint32 arrays of one shape — the table is the pair
``(dir_lo, dir_hi)``, each ``uint32[NB, W]``: ``NB`` buckets of ``W``
lanes, ``NB * W == capacity``; entry ``(b, l)`` is slot ``b * W + l`` of
the state columns. Two leaves of 32-bit words and not one of int64: the
TPU has no 64-bit vectors, so XLA splits a 64-bit array where it enters
a program and recombines it where it leaves — a pass over the whole
table at both ends of every dispatch (30.7 of a 32.2 ms step at 2^26
entries, PERF.md §6, PR 42 / 43) — and not one stacked ``[2, NB, W]``
array either, which every pass of the probe loop would relay out. ``W =
gcd(capacity, DenseParams.lanes)`` (128, a vector register's lanes, for
any capacity that is a multiple of 128): a probe reads one whole bucket
row of each leaf, so at a load of one half almost every key is found by
one pair of row gathers.

Probing: a key's home bucket is a 32-bit mix of its two words; a probe
walks home, home+1, ... (mod NB) for at most ``probe_bound`` buckets. A
bucket answers a key when it holds it (hit), or when it holds an EMPTY
lane (the key is in no later bucket, so it is absent). Invariant: every
bucket between a live key's home and the bucket it sits in has no EMPTY
lane.

Inserting: an absent key takes the first FREE lane on its path — EMPTY
or a tombstone (below). A tombstone says a key may have walked past, so
the row first walks on until a bucket with an EMPTY lane (or the bound)
tells it the key is absent, remembering the first bucket that had a free
lane; if that bucket lies behind, it goes back there and takes the first
free lane from it on, judged by the rows the same pass read. Without
this a table that expires entries under load dies: a bucket that was
full once keeps its tombstones while a displaced key lives, new keys of
that bucket walk past it too and keep it covered, and every bucket ends
full of tombstones no insert may take (PERF.md §6, PR 44).

Identity: two rows of a batch that carry the same new key compute the
same claim (a function of key and table only) and both read their key
back; two DIFFERENT keys that claim one lane are told apart by the
re-read — the scatter keeps one, the loser tries again in the same
bucket. The claim has two phases, one a word: every claimant of a lane
writes its LOW word and reads it back; the survivors (low word equal —
they may still differ in the high word) write their HIGH word and read
that back; a row has won only when BOTH words read back its own key.
The lane always ends the pass holding one claimant's whole key: the low
word's scatter keeps one claimant's, that claimant survives, and only
survivors — all of that low word — write a high word. A row is never
given another key's slot: it resolves only to an entry that reads back
its own key. A row that finds neither its key nor an EMPTY lane within
the bound is UNPLACED: it touches no state and the host answers it by
the fail-open / fail-closed policy.

Reserved values: EMPTY = (0, 0) and TOMB = (1, 0), the 64-bit values 0
and 1. An id equal to one of them is remapped (xor with a constant);
beside a 64-bit collision that is the only way two ids can share a
bucket — a key whose low word is 0 or 1 with a non-zero high word is an
ordinary key, and an equal low or high word alone matches nothing.

Reclaim (``reclaim``, a program of its own): an entry idle for the
horizon equals a fresh one, so it becomes a tombstone and its state row
is reset; a tombstone matches no key and is not EMPTY, so lookups of
surviving keys that walked past it still do, and the next insert whose
path holds it takes it. A tombstone goes back to EMPTY when no live key
sits in a later bucket having walked past its bucket (the cover rule
below) — at any sane load nearly all do at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMPTY = 0
TOMB = 1
#: What a reserved id is xor-ed with (the 64-bit golden ratio), by word.
_REMAP_LO, _REMAP_HI = 0x7F4A7C15, 0x9E3779B9

#: Words the step appends to its packed result: rows looked up, buckets
#: examined, entries inserted (distinct keys), rows left unplaced.
TAIL_WORDS = 4


def geometry(capacity: int, lanes: int, probe_bound: int) -> dict:
    """``dict(nb, w, pb)``: buckets, lanes a bucket, buckets a probe may
    examine — the statics every directory program is built with."""
    w = math.gcd(int(capacity), int(lanes))
    nb = int(capacity) // w
    return dict(nb=nb, w=w, pb=min(int(probe_bound), nb))


def unplaced_from(w: int, pb: int) -> float:
    """The load (live entries / capacity) under which no fill of a table
    of ``w``-lane buckets probed ``pb`` deep has left a row unplaced: a
    row finds no lane when the ``w * pb`` lanes of its path are all
    live, the keys homed on a path are ~Poisson, so the first unplaced
    row comes a number of standard deviations under a full path — and
    WHICH number is a draw: ``1 - 4.2 / sqrt(w * pb)`` lies under every
    one seen up to 2^24 entries. At w, pb = 128, 8 that is 0.8688.
    Measured (ISSUE 48), the load at a fill's first unplaced row,
    uniform keys: the bucket counts alone, 2^21 entries, 40 seeds:
    lowest 0.8723, first decile 0.8885, median 0.8992 (2^17 entries, 200
    seeds: 0.8779 / 0.9053 / 0.9214; 2^24, 6 seeds: lowest 0.8764; 2^26,
    2 seeds: 0.8654 and 0.8720 — a larger table has more paths to
    overflow, which the margin ``algorithms/dense.py`` keeps under this
    covers); the limiter itself on a CPU, 2^21 entries: 0.8965 and
    0.8984 fresh, 0.8966-0.9036 on the churned table of an expiring
    Zipf(0.99) stream (4,096 and 16,384 rows a dispatch); on the chip
    under that stream from a cold start: 0.882-0.899 by the host's count
    at the first unplaced rows; other geometries, one seed each at 2^21:
    128, 16: 0.9336; 128, 4: 0.8613; 64, 8: 0.8535; 32, 8: 0.8047 (the
    formula: 0.9072, 0.8144, 0.8144, 0.7375).
    ``tests/test_dense_directory.py`` holds the curve."""
    return 1.0 - 4.2 / math.sqrt(w * pb)


def words(x):
    """A 64-bit integer array as its ``(low, high)`` uint32 words, by a
    mask and a shift (a ``bitcast_convert_type`` on a 64-bit type is
    what the TPU's X64 rewriter refuses, PERF.md §5)."""
    u = x.astype(jnp.uint64)
    return ((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (u >> jnp.uint64(32)).astype(jnp.uint32))


def join(lo, hi):
    """The int64 array of uint32 words ``(lo, hi)``: ``words``' inverse.
    For a batch's rows and inside table-sized control programs only — a
    capacity-sized 64-bit array never crosses a program's boundary."""
    return ((hi.astype(jnp.uint64) << jnp.uint64(32))
            | lo.astype(jnp.uint64)).astype(jnp.int64)


def init_keys(nb: int, w: int):
    """An empty table: ``(dir_lo, dir_hi)``, every lane EMPTY."""
    return jnp.zeros((nb, w), jnp.uint32), jnp.zeros((nb, w), jnp.uint32)


def canon(ids):
    """uint64 ids -> directory keys (word pairs), reserved values
    remapped."""
    lo, hi = k = words(ids)
    reserved = _reserved(k)
    return (jnp.where(reserved, lo ^ jnp.uint32(_REMAP_LO), lo),
            jnp.where(reserved, jnp.uint32(_REMAP_HI), hi))


def _is(keys, value: int):
    """Where ``keys`` holds the reserved 64-bit ``value`` (EMPTY, TOMB)."""
    return (keys[0] == value) & (keys[1] == 0)


def _reserved(keys):
    """Where ``keys`` holds EMPTY or TOMB: no key."""
    return (keys[1] == 0) & (keys[0] <= TOMB)


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def home(k, nb: int, w: int):
    """(home bucket, preferred lane) int32 of keys, any shape."""
    lo, hi = k
    h = _fmix32(lo ^ (hi * jnp.uint32(0x9E3779B1) + jnp.uint32(0x7F4A7C15)))
    bucket = (h & jnp.uint32(nb - 1)) if nb & (nb - 1) == 0 \
        else h % jnp.uint32(nb)
    lane = (h >> jnp.uint32(16)) % jnp.uint32(w)
    return bucket.astype(jnp.int32), lane.astype(jnp.int32)


def probe(keys2d, k, valid, *, nb: int, w: int, pb: int, insert: bool):
    """Resolve the keys ``k`` (a pair of ``uint32[B]``; rows with
    ``valid``) against the table ``keys2d`` (a pair of ``uint32[NB, W]``).

    Returns ``(keys2d, slot int32[B], placed bool[B], claimed bool[B],
    probes int32[])``: ``slot`` is the entry of the row's key where
    ``placed``; ``claimed`` marks the rows whose key this call inserted
    (every row of a new key, so count distinct slots); ``probes`` counts
    the buckets examined over all rows. With ``insert`` an absent key
    claims a free lane (EMPTY or tombstone) of the first bucket on its
    path that has one; without, an absent key is simply not ``placed``.
    """
    klo, khi = k
    B = klo.shape[0]
    pos0, pref = home(k, nb, w)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (B, w), 1)
    # Every pass places at least one claimant of each contested lane, and
    # a row walks its path at most twice (to learn its key is absent,
    # then back to the first bucket with a free lane): 2 pb + B passes
    # always suffice; the cap only bounds a faulty device.
    cap = 2 * pb + B

    def cond(c):
        return jnp.any(c[3]) & (c[-1] < cap)

    def body(c):
        lo2d, hi2d, pos, active, slot, claimed, tpos, placing, probes, it = c
        hops = (pos - pos0) % nb
        with jax.named_scope("directory_probe"):
            rows = (lo2d.at[pos].get(mode="promise_in_bounds"),    # [B, w]
                    hi2d.at[pos].get(mode="promise_in_bounds"))
            hit = (rows[0] == klo[:, None]) & (rows[1] == khi[:, None])
            found = active & jnp.any(hit, axis=1)
            slot = jnp.where(
                found, pos * w + jnp.argmax(hit, axis=1).astype(jnp.int32),
                slot)
            probes = probes + jnp.sum(active, dtype=jnp.int32)
            active = active & ~found
            empty = _is(rows, EMPTY)
            has_empty = jnp.any(empty, axis=1)
        if insert:
            with jax.named_scope("directory_insert"):
                # A lane is free when it is EMPTY or a tombstone. A
                # tombstone may be taken only by a key known to be
                # absent — a later bucket may hold it — so a row first
                # walks on to a bucket with an EMPTY lane (or to the
                # bound), noting the first bucket that had a free lane
                # (``tpos``), then goes back there ``placing``: it takes
                # the first free lane from that bucket on, judged by the
                # rows this pass read, never by what an earlier pass saw.
                free = empty | _is(rows, TOMB)
                has_free = jnp.any(free, axis=1)
                searching = active & ~placing
                tpos = jnp.where(searching & (tpos < 0) & has_free, pos, tpos)
                absent = searching & (has_empty | (hops + 1 >= pb))
                back = absent & (tpos >= 0) & (tpos != pos)
                claim = active & has_free & (placing | (absent & ~back))
                # The first free lane at or (cyclically) after the key's
                # preferred one: keys of one bucket spread over its free
                # lanes, so one pass places nearly all of them.
                dist = jnp.where(free, (lane_iota - pref[:, None]) % w, w)
                lane = jnp.argmin(dist, axis=1).astype(jnp.int32)
                # Phase one, the low word: the scatter keeps one
                # claimant's; whoever reads its own back goes on.
                lo2d = lo2d.at[jnp.where(claim, pos, nb), lane].set(
                    klo, mode="drop")
                low = claim & (lo2d.at[pos, lane].get(
                    mode="promise_in_bounds") == klo)
                # Phase two, the high word, among those of that low word.
                hi2d = hi2d.at[jnp.where(low, pos, nb), lane].set(
                    khi, mode="drop")
                won = low & (hi2d.at[pos, lane].get(
                    mode="promise_in_bounds") == khi)
                slot = jnp.where(won, pos * w + lane, slot)
                claimed = claimed | won
                active = active & ~won
                placing = placing | back
                stay = claim | back     # a loser looks at this bucket again
        else:
            active = active & ~has_empty    # absent: an EMPTY lane ends it
            stay = back = jnp.zeros_like(active)
        move = active & ~stay
        active = active & ~(move & (hops + 1 >= pb))
        pos = jnp.where(back, tpos, jnp.where(move, (pos + 1) % nb, pos))
        return (lo2d, hi2d, pos, active, slot, claimed, tpos, placing,
                probes, it + 1)

    init = (*keys2d, pos0, valid, jnp.full((B,), -1, jnp.int32),
            jnp.zeros((B,), bool), jnp.full((B,), -1, jnp.int32),
            jnp.zeros((B,), bool), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32))
    lo2d, hi2d, _, _, slot, claimed, _, _, probes, _ = jax.lax.while_loop(
        cond, body, init)
    return (lo2d, hi2d), slot, valid & (slot >= 0), claimed, probes


def distinct(slot, mask):
    """How many distinct values ``slot int32[B] >= 0`` takes under
    ``mask`` (one small sort; nothing sized by the table)."""
    s = jnp.sort(jnp.where(mask, slot, -1))
    return (jnp.sum((s[1:] != s[:-1]) & (s[1:] >= 0), dtype=jnp.int32)
            + (s[0] >= 0).astype(jnp.int32))


def reclaim(keys2d, stamp, now_us, *, nb: int, w: int, pb: int,
            horizon_us: int):
    """One table-sized pass: ``(keys2d, freed bool[NB, W])``.

    ``stamp int64[NB, W]`` is each entry's last-touched instant (the
    bucket's ``last`` / the window rules' ``win_start``), joined from
    its words inside the calling program; a live entry with ``stamp <=
    now - horizon`` is given up. Then tombstones — these and earlier
    ones — go back to EMPTY wherever the cover rule allows: bucket ``b``
    is covered while some live key sits ``j >= 1`` buckets past it
    having been displaced at least ``j``, i.e. having walked through
    ``b``; only a covered bucket's tombstones must stay.
    """
    live = ~_reserved(keys2d)
    freed = live & (stamp <= now_us - horizon_us)
    live = live & ~freed
    tomb = freed | _is(keys2d, TOMB)
    hb, _ = home(keys2d, nb, w)
    here = jax.lax.broadcasted_iota(jnp.int32, (nb, w), 0)
    disp = jnp.where(live, (here - hb) % nb, 0)
    reach = jnp.max(disp, axis=1)                            # [nb]
    covered = jnp.zeros((nb,), bool)
    for j in range(1, pb):
        covered = covered | (jnp.roll(reach, -j) >= j)
    # A tombstone is (TOMB, 0) where it must stay, (EMPTY, 0) elsewhere.
    lo, hi = keys2d
    lo = jnp.where(tomb, jnp.where(covered[:, None], jnp.uint32(TOMB),
                                   jnp.uint32(EMPTY)), lo)
    return (lo, jnp.where(tomb, jnp.uint32(0), hi)), freed
