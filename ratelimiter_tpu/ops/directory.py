"""The dense backend's keyspace directory, on the device (ADR-027).

A table of ``capacity`` entries keyed by the 64-bit id a decision row
carries. Lookup AND insertion run inside the one jitted decision step, so
the host never maps a key to a slot: an entry's position IS the slot of
the key's state row.

Layout: ``dir_keys int64[NB, W]`` — ``NB`` buckets of ``W`` lanes,
``NB * W == capacity``; entry ``(b, l)`` is slot ``b * W + l`` of the
state columns. ``W = gcd(capacity, DenseParams.lanes)`` (128, a vector
register's lanes, for any capacity that is a multiple of 128): a probe
reads one whole bucket row, so at a load of one half almost every key is
found by ONE row gather.

Probing: a key's home bucket is a 32-bit mix of its two words; a probe
walks home, home+1, ... (mod NB) for at most ``probe_bound`` buckets. A
bucket answers a key when it holds it (hit), or when it holds an EMPTY
lane (the key is in no later bucket, so it is absent — and, inserting,
the lane is claimed). Invariant: every bucket between a live key's home
and the bucket it sits in has no EMPTY lane.

Identity: two rows of a batch that carry the same new key compute the
same claim (a function of key and table only) and both read their key
back; two DIFFERENT keys that claim one lane are told apart by the
re-read — the scatter keeps one, the loser tries again in the same
bucket. A row is never given another key's slot: it resolves only to an
entry that reads back its own key. A row that finds neither its key nor
an EMPTY lane within the bound is UNPLACED: it touches no state and the
host answers it by the fail-open / fail-closed policy.

Reserved values: EMPTY = 0 and TOMB = 1. An id equal to one of them is
remapped (xor with a constant); beside a 64-bit collision that is the
only way two ids can share a bucket.

Reclaim (``reclaim``, a program of its own): an entry idle for the
horizon equals a fresh one, so it becomes a tombstone and its state row
is reset; a tombstone matches no key and is not EMPTY, so lookups of
surviving keys that walked past it still do. A tombstone goes back to
EMPTY when no live key sits in a later bucket having walked past its
bucket (the cover rule below) — at any sane load nearly all do at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMPTY = 0
TOMB = 1
#: What a reserved id is xor-ed with (the 64-bit golden ratio, as int64).
_REMAP = 0x9E3779B97F4A7C15 - (1 << 64)

#: Words the step appends to its packed result: rows looked up, buckets
#: examined, entries inserted (distinct keys), rows left unplaced.
TAIL_WORDS = 4


def geometry(capacity: int, lanes: int, probe_bound: int) -> dict:
    """``dict(nb, w, pb)``: buckets, lanes a bucket, buckets a probe may
    examine — the statics every directory program is built with."""
    w = math.gcd(int(capacity), int(lanes))
    nb = int(capacity) // w
    return dict(nb=nb, w=w, pb=min(int(probe_bound), nb))


def init_keys(nb: int, w: int):
    return jnp.zeros((nb, w), jnp.int64)


def canon(ids):
    """uint64 ids -> int64 directory keys, reserved values remapped."""
    k = ids.astype(jnp.int64)
    return jnp.where((k == EMPTY) | (k == TOMB), k ^ jnp.int64(_REMAP), k)


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def home(k, nb: int, w: int):
    """(home bucket, preferred lane) int32 of int64 keys, any shape."""
    lo = (k & 0xFFFFFFFF).astype(jnp.uint32)
    hi = (k >> 32).astype(jnp.uint32)
    h = _fmix32(lo ^ (hi * jnp.uint32(0x9E3779B1) + jnp.uint32(0x7F4A7C15)))
    bucket = (h & jnp.uint32(nb - 1)) if nb & (nb - 1) == 0 \
        else h % jnp.uint32(nb)
    lane = (h >> jnp.uint32(16)) % jnp.uint32(w)
    return bucket.astype(jnp.int32), lane.astype(jnp.int32)


def probe(keys2d, k, valid, *, nb: int, w: int, pb: int, insert: bool):
    """Resolve ``k int64[B]`` (rows with ``valid``) against ``keys2d``.

    Returns ``(keys2d, slot int32[B], placed bool[B], claimed bool[B],
    probes int32[])``: ``slot`` is the entry of the row's key where
    ``placed``; ``claimed`` marks the rows whose key this call inserted
    (every row of a new key, so count distinct slots); ``probes`` counts
    the buckets examined over all rows. With ``insert`` an absent key
    claims an EMPTY lane of the first bucket on its path that has one;
    without, an absent key is simply not ``placed``.
    """
    B = k.shape[0]
    pos0, pref = home(k, nb, w)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (B, w), 1)
    # Every pass places at least one claimant of each contested lane, so
    # pb + B passes always suffice; the cap only bounds a faulty device.
    cap = pb + B

    def cond(c):
        return jnp.any(c[3]) & (c[7] < cap)

    def body(c):
        keys2d, pos, hops, active, slot, claimed, probes, it = c
        with jax.named_scope("directory_probe"):
            rows = keys2d.at[pos].get(mode="promise_in_bounds")   # [B, w]
            hit = rows == k[:, None]
            found = active & jnp.any(hit, axis=1)
            slot = jnp.where(
                found, pos * w + jnp.argmax(hit, axis=1).astype(jnp.int32),
                slot)
            probes = probes + jnp.sum(active, dtype=jnp.int32)
            active = active & ~found
            empty = rows == EMPTY
            has_empty = jnp.any(empty, axis=1)
        if insert:
            with jax.named_scope("directory_insert"):
                claim = active & has_empty
                # The first EMPTY lane at or (cyclically) after the key's
                # preferred one: keys of one bucket spread over its free
                # lanes, so one pass places nearly all of them.
                dist = jnp.where(empty, (lane_iota - pref[:, None]) % w, w)
                lane = jnp.argmin(dist, axis=1).astype(jnp.int32)
                keys2d = keys2d.at[jnp.where(claim, pos, nb), lane].set(
                    k, mode="drop")
                won = claim & (keys2d.at[pos, lane].get(
                    mode="promise_in_bounds") == k)
                slot = jnp.where(won, pos * w + lane, slot)
                claimed = claimed | won
                active = active & ~won
                stay = claim            # a loser looks at this bucket again
        else:
            active = active & ~has_empty    # absent: an EMPTY lane ends it
            stay = jnp.zeros_like(active)
        move = active & ~stay
        hops = hops + move.astype(jnp.int32)
        active = active & (hops < pb)
        pos = jnp.where(move, (pos + 1) % nb, pos)
        return keys2d, pos, hops, active, slot, claimed, probes, it + 1

    init = (keys2d, pos0, jnp.zeros((B,), jnp.int32), valid,
            jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), bool),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    keys2d, _, _, _, slot, claimed, probes, _ = jax.lax.while_loop(
        cond, body, init)
    return keys2d, slot, valid & (slot >= 0), claimed, probes


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
    bucket's ``last`` / the window rules' ``win_start``); a live entry
    with ``stamp <= now - horizon`` is given up. Then tombstones — these
    and earlier ones — go back to EMPTY wherever the cover rule allows:
    bucket ``b`` is covered while some live key sits ``j >= 1`` buckets
    past it having been displaced at least ``j``, i.e. having walked
    through ``b``; only a covered bucket's tombstones must stay.
    """
    live = (keys2d != EMPTY) & (keys2d != TOMB)
    freed = live & (stamp <= now_us - horizon_us)
    keys2d = jnp.where(freed, jnp.int64(TOMB), keys2d)
    live = live & ~freed
    hb, _ = home(keys2d, nb, w)
    here = jax.lax.broadcasted_iota(jnp.int32, (nb, w), 0)
    disp = jnp.where(live, (here - hb) % nb, 0)
    reach = jnp.max(disp, axis=1)                            # [nb]
    covered = jnp.zeros((nb,), bool)
    for j in range(1, pb):
        covered = covered | (jnp.roll(reach, -j) >= j)
    keys2d = jnp.where((keys2d == TOMB) & ~covered[:, None],
                       jnp.int64(EMPTY), keys2d)
    return keys2d, freed
