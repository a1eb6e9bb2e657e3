"""Bytes the exact table MUST move when it is sized for the ACTIVE set
and kept there by expiry (``"byte_model": "reclaim"``; ``--backend
dense`` under a window short enough that the reclaim pass runs all run
long — ``exact-tb-ttl``). Two counts, both the algorithm's own need from
shapes, never what today's program moves (chipbench/bytes.py states the
rule):

``step_bytes`` / ``step_ops``  a dispatch: ``bytes_table``'s own
    functions (one arithmetic) — one directory probe and one
    read-modify-write of each int64 column of the key's row a decision,
    the wire columns. Here the table holds the keys of the last two
    windows, and what keeps it so is the pass below. The pass is not
    shared over the dispatches: a table that expired lazily would need
    none, so it is no part of what a DECISION must move.

``pass_bytes``  one reclaim pass (``jit_dense_reclaim``): to know which
    entries are idle it must read every entry's last-touched stamp and,
    to know which entries are live and where their keys' home buckets
    are (a tombstone may go back to EMPTY only where no live key walked
    past it), every entry's key — 8 B + 8 B an entry; of each entry it
    gives up it must write the key's 8 B (the tombstone) and the row's
    columns back to pristine. ``reclaim_roofline`` divides it by the
    pass's device time; bound by bytes.
"""

from chipbench import bytes_table
from chipbench.bytes_table import step_bytes, step_ops  # noqa: F401

STAMP_READ = 8
KEY_READ = 8
KEY_WRITE = 8
COLUMN_WRITE = 8


def pass_bytes(cfg: dict, freed: float) -> float:
    """Bytes one pass over ``cfg["capacity"]`` entries must move when it
    gives up ``freed`` of them."""
    columns = bytes_table.COLUMNS[cfg["algorithm"]]
    return (cfg["capacity"] * (STAMP_READ + KEY_READ)
            + freed * (KEY_WRITE + columns * COLUMN_WRITE))
