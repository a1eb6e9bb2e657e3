"""Bytes one chip MUST put on the inter-chip links per dispatch of the
collective router (ops/route_kernels.py): the algorithm's own need from
shapes, not what today's program ships (it ships fixed-capacity bins of
``bin_headroom`` x the mean, padding included, and a whole byte per
verdict).

A dispatch of ``batch`` decisions lands as ``rows = batch / n`` rows on
each of ``n`` chips, in frame order. A key's owner is ``hash % n``, so
(n - 1) / n of a chip's rows belong to another chip and must leave it:

out    the key and its cost: u64 id + u32 n (``bytes.WIRE_IN``);
back   what the source cannot compute itself: 1 bit allowed and the i64
       ``remaining``; the token bucket's ``retry_after`` is per key
       (i64 us) and crosses too. The window's ``retry_after`` and
       ``reset_at`` follow from ``allowed`` and the dispatch's one
       timestamp, which every chip holds, so they need not cross
       (``bytes.WIRE_OUT`` counts them for the client's wire, where
       they do).

As many bytes arrive as leave; the links are full duplex, so one
direction's bytes over the published rate is the least time.
"""

from __future__ import annotations

from chipbench.bytes import WIRE_IN

ROW_OUT = WIRE_IN                      # u64 id + u32 n
ROW_BACK = {"tpu_sketch": 1 / 8 + 8,   # allowed bit + i64 remaining
            "sliding_window": 1 / 8 + 8,
            "token_bucket": 1 / 8 + 8 + 8}   # + i64 retry_us


def rows_leaving(rows_per_chip: float, n_chips: int) -> float:
    """Rows of a chip's share that another chip owns, owners uniform."""
    return rows_per_chip * (n_chips - 1) / n_chips


def exchange_bytes(algorithm: str, rows_per_chip: float,
                   n_chips: int) -> float:
    """Bytes one chip must send per dispatch, both exchanges."""
    return rows_leaving(rows_per_chip, n_chips) * (
        ROW_OUT + ROW_BACK[algorithm])
